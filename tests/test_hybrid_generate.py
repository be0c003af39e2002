"""The hybrid state-space / attention decoder on the serving path.

A tiny hybrid (2 periods of ``[mamba, mamba, attention, mamba]``, hidden
64, 4 query over 2 key/value heads, 4 state-space heads of 16 with state
16, chunk 8, vocabulary 97) is held against the plain reference
(``benchmark/reference_granite.py``: float32, a per-position scan, no
cache) on seeded weights, at every place where recurrent state can go
wrong that keys and values forgive: a padded position, a padding row, a
prompt prefilled in chunks, a lane that another request used, a stream
replayed from its prompt and prefix.  Logits are compared, not tokens;
on the CPU in float32 the program and the reference differ by rounding
only, so every tolerance is 2e-5 on logits of size 0.2 (a path that
dropped a term would miss by 1e-2 and more).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, obs, profiler
from mxtpu import symbol as sym_mod
from mxtpu.models.hybrid import HybridDecoderModel
from mxtpu.ndarray import rnn_impl
from mxtpu.serving import DeviceLogits, GenerateBatcher, GenerateRunner

from benchmark import reference_granite as ref
from benchmark import weights_granite

CFG = {"vocab_size": 97, "hidden_size": 64, "shared_intermediate_size": 128,
       "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
       "mamba_d_conv": 4, "mamba_chunk_size": 8, "mamba_n_groups": 1,
       "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
       "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
       "logits_scaling": 8, "num_local_experts": 0,
       "position_embedding_type": "nope"}
LANES, CAP, BUCKETS = 3, 48, (4, 8)
TOL = 2e-5
SEED = 2 ** 31 + 5


@pytest.fixture(scope="module")
def weights():
    return weights_granite.make(CFG, SEED)


@pytest.fixture(scope="module")
def net(weights):
    n = HybridDecoderModel.from_config(CFG)
    n.initialize()
    leaves = n.named_leaves()
    assert set(leaves) == set(weights)
    for name, p in leaves.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p.set_data(nd.array(np.asarray(weights[name].astype(jnp.float32))))
    return n


def _export(net):
    """The incremental graph and the program's parameters by name: no
    eager forward, no file."""
    out = net(*[sym_mod.var(f"data{i}") for i in range(6)])
    params = {p.name: p.data() for p in net.collect_params().values()}
    return sym_mod.Group(list(out)), params


@pytest.fixture(scope="module")
def runner(net):
    symbol, params = _export(net)
    return GenerateRunner(symbol, params, net.state_spec(LANES, CAP),
                          prompt_buckets=BUCKETS, cache=None)


N_MAMBA = CFG["layer_types"].count("mamba")


def _take_the_kernel(patch):
    """The chip's answers, given here: Pallas kernels run (under the
    interpreter) and the ``ssm`` table is taken for whole tiles."""
    patch.setenv("MXTPU_PALLAS", "interpret")
    patch.setattr(rnn_impl, "_state_in_whole_tiles", lambda t: True)


@pytest.fixture(scope="module")
def kernel_runner(net):
    """The same runner with every program built as the chip builds it,
    so the decode program's one-token state updates are the kernel's
    (``mxtpu.kernels.ssm_update``).  What a program is made of is
    settled when it is built, so the patches end with the warm-up."""
    symbol, params = _export(net)
    with pytest.MonkeyPatch.context() as m:
        _take_the_kernel(m)
        r = GenerateRunner(symbol, params, net.state_spec(LANES, CAP),
                           prompt_buckets=BUCKETS, cache=None)
        r.warmup()
    return r


@pytest.fixture(params=["xla", "kernel"])
def either_runner(request):
    """The runner whose one-token state update is XLA's two fusions,
    and the one whose update is the kernel."""
    name = "runner" if request.param == "xla" else "kernel_runner"
    r = request.getfixturevalue(name)
    if request.param == "kernel":
        assert r._entries[("decode", (LANES + 1,))][
            "ssm_kernel_updates"] == N_MAMBA
    return r


def _logits(weights, tokens):
    return np.asarray(ref.forward(CFG, weights, np.asarray(tokens)[None]))[0]


def _prompt(n, salt=0):
    return np.random.default_rng(100 + salt).integers(1, 97, n).tolist()


def _prefill_rows(runner, kv, rows, bucket):
    """Prefill ``rows`` = [(lane, tokens)] together, in chunks of
    ``bucket`` on the rung that holds them, as the batcher does; returns
    each row's logits at its last position, and the tables."""
    b = runner.batch_rung_for(len(rows))
    need = [len(t) for _, t in rows]
    out = [None] * len(rows)
    for base in range(0, max(need), bucket):
        tok = np.zeros((b, bucket), np.float32)
        step = np.zeros(b, np.float32)
        length = np.zeros(b, np.float32)
        lane = np.full(b, runner.scratch_slot, np.float32)
        for r, (at, t) in enumerate(rows):
            if base >= need[r]:
                continue
            valid = min(bucket, need[r] - base)
            tok[r, :valid] = t[base:base + valid]
            step[r], length[r], lane[r] = base, valid, at
        logits, kv = runner.prefill(tok, step, lane, kv, length)
        assert isinstance(logits, DeviceLogits)
        assert logits.shape == (b, 1, CFG["vocab_size"])
        for r in range(len(rows)):
            if base <= need[r] - 1 < base + bucket:
                assert logits[r, 0].first_maximum == \
                    np.argmax(np.asarray(logits)[r, 0])
                out[r] = np.asarray(logits[r, 0])
    return out, kv


def _decode(runner, kv, lane_tokens):
    """One decode step: {lane: (token, frontier)}; returns the logits of
    those lanes."""
    slots = runner.max_lanes + 1
    tok = np.zeros((slots, 1), np.float32)
    step = np.zeros(slots, np.float32)
    length = np.zeros(slots, np.float32)
    for lane, (t, at) in lane_tokens.items():
        tok[lane, 0], step[lane], length[lane] = t, at, 1
    logits, kv = runner.decode(tok, step, kv, length)
    logits = np.asarray(logits)
    return {lane: logits[lane, 0] for lane in lane_tokens}, kv


# ------------------------------------------------------------ the model
def test_full_forward_matches_the_reference(net, weights):
    seq = _prompt(21)
    want = _logits(weights, seq)
    spec = net.state_spec(1, 32)
    for n in (1, 5, 8, 9, 21):         # each length's last position
        tables = [nd.array(np.zeros(s, np.float32)) for _, s, _, _ in spec]
        out = net(nd.array(np.asarray(seq, np.float32)[None]),
                  nd.array(np.zeros(1)), nd.array(np.array([float(n)])),
                  *tables)
        np.testing.assert_allclose(out[0].asnumpy()[0, 0], want[n - 1],
                                   atol=TOL, rtol=0)


def test_state_spec_declares_three_tables(net):
    kv, ssm, conv = net.state_spec(5, 40, kv_dtype="bfloat16")
    assert kv == ("kv", (2, 2, 5, 2, 40, 16), 2, "bfloat16")
    assert ssm == ("ssm", (6, 5, 4, 16, 16), 1, "float32")
    assert conv == ("conv", (6, 5, 3, 96), 1, "float32")


# ----------------------------------------------------------- the runner
def test_tables_follow_the_spec(net):
    symbol, params = _export(net)
    r = GenerateRunner(symbol, params,
                       net.state_spec(LANES, CAP, kv_dtype="bfloat16"),
                       prompt_buckets=BUCKETS, cache=None)
    kv, ssm, conv = r.new_cache()
    assert kv.dtype == jnp.bfloat16 and kv.shape == (2, 2, LANES + 1, 2,
                                                     CAP, 16)
    assert ssm.dtype == conv.dtype == jnp.float32
    assert ssm.shape[1] == conv.shape[1] == LANES + 1
    assert r.kv_spec == (2, 2, LANES, 2, CAP, 16)
    # both kinds of program are told each row's valid length
    assert [len(r._structs(b)) for b in (("prefill", (1, 4)),
                                         r.default_bucket("decode"))] \
        == [5, 4]
    assert [t.name for t in r.state_spec] == ["kv", "ssm", "conv"]
    series = obs.snapshot()["mxtpu_gen_state_bytes"]["series"]
    got = {v["labels"]["table"]: int(v["value"]) for v in series}
    # (the gauge keeps a series for every table name this process has
    # allocated: another model's tables may stand beside these)
    assert {k: got[k] for k in ("kv", "ssm", "conv")} == \
        {"kv": kv.nbytes, "ssm": ssm.nbytes, "conv": conv.nbytes}
    # a bfloat16 table is written and read as bfloat16, state stays f32
    (first,), tables = _prefill_rows(r, (kv, ssm, conv),
                                     [(0, _prompt(5))], 8)
    assert tables[0].dtype == jnp.bfloat16 and np.isfinite(first).all()


def test_a_six_tuple_runner_is_what_it_was(net):
    """``kv_spec`` as six ints: one float32 table and the three-input
    graph, whose prefill program alone is told each row's length and
    keeps that position's row of the graph's whole logits."""
    from mxtpu.models.transformer import BERTModel
    bert = BERTModel(40, 16, 32, 2, 2, max_length=16, dropout=0.0,
                     use_token_type=False, causal=True)
    bert.initialize()
    out = bert(*[sym_mod.var(f"data{i}") for i in range(3)])
    bert(nd.array(np.ones((1, 3))), nd.array(np.zeros(1)),
         nd.array(np.zeros(bert.kv_cache_spec(1), np.float32)))
    params = {p.name: p.data() for p in bert.collect_params().values()}
    r = GenerateRunner(sym_mod.Group(list(out)), params,
                       bert.kv_cache_spec(2, 16), prompt_buckets=(4,),
                       cache=None)
    assert len(r.state_spec) == 1
    assert [len(r._structs(b)) for b in (("prefill", (1, 4)),
                                         r.default_bucket("decode"))] \
        == [5, 3]
    kv = r.new_cache()
    assert kv.shape == (2, 2, 3, 2, 16, 8) and kv.dtype == jnp.float32
    logits, kv = r.prefill(np.ones((1, 4), np.float32),
                           np.zeros(1, np.float32),
                           np.zeros(1, np.float32), kv)
    assert isinstance(logits, DeviceLogits)
    assert logits.shape == (1, 1, 40) and kv.shape == (2, 2, 3, 2, 16, 8)


@pytest.mark.parametrize("plen", [1, 5, 8])
def test_prefill_then_decode_equals_the_full_forward(either_runner, weights,
                                                     plen):
    runner = either_runner
    seq = _prompt(plen + 6, salt=plen)
    want = _logits(weights, seq)
    (first,), kv = _prefill_rows(runner, runner.new_cache(),
                                 [(1, seq[:plen])], 8)
    np.testing.assert_allclose(first, want[plen - 1], atol=TOL, rtol=0)
    for at in range(plen, len(seq)):
        got, kv = _decode(runner, kv, {1: (seq[at], at)})
        np.testing.assert_allclose(got[1], want[at], atol=TOL, rtol=0)


def test_three_chunks_with_a_padded_last_chunk(runner, weights):
    """19 tokens through the 8-wide bucket: 8, 8, then 3 valid of 8.
    The state is carried from chunk to chunk and the five padded
    positions must not advance it."""
    seq = _prompt(23, salt=7)
    want = _logits(weights, seq)
    (first,), kv = _prefill_rows(runner, runner.new_cache(),
                                 [(0, seq[:19])], 8)
    np.testing.assert_allclose(first, want[18], atol=TOL, rtol=0)
    for at in range(19, 23):
        got, kv = _decode(runner, kv, {0: (seq[at], at)})
        np.testing.assert_allclose(got[0], want[at], atol=TOL, rtol=0)


def test_a_rung_with_padding_rows_and_unequal_prompts(runner, weights):
    """Three prompts on the rung of four: one padding row (scratch slot,
    length 0), and the short rows finish chunks before the long one."""
    seqs = [_prompt(n + 2, salt=n) for n in (3, 14, 8)]
    cut = [3, 14, 8]
    firsts, kv = _prefill_rows(
        runner, runner.new_cache(),
        [(lane, s[:n]) for lane, (s, n) in enumerate(zip(seqs, cut))], 8)
    wants = [_logits(weights, s) for s in seqs]
    for got, want, n in zip(firsts, wants, cut):
        np.testing.assert_allclose(got, want[n - 1], atol=TOL, rtol=0)
    # all three decode together, each at its own frontier
    for k in range(2):
        got, kv = _decode(runner, kv, {lane: (s[n + k], n + k) for lane,
                                       (s, n) in enumerate(zip(seqs, cut))})
        for lane, (want, n) in enumerate(zip(wants, cut)):
            np.testing.assert_allclose(got[lane], want[n + k], atol=TOL,
                                       rtol=0)


def test_a_reused_lane_starts_from_zero_state(runner, weights):
    """A long request, then a short one in the same lane: neither the
    recurrent state nor the keys of the first may reach the second."""
    long_seq, short = _prompt(20, salt=1), _prompt(6, salt=2)
    _, kv = _prefill_rows(runner, runner.new_cache(), [(2, long_seq[:16])],
                          8)
    for at in range(16, 20):
        _, kv = _decode(runner, kv, {2: (long_seq[at], at)})
    want = _logits(weights, short)
    (first,), kv = _prefill_rows(runner, kv, [(2, short[:4])], 4)
    np.testing.assert_allclose(first, want[3], atol=TOL, rtol=0)
    for at in (4, 5):
        got, kv = _decode(runner, kv, {2: (short[at], at)})
        np.testing.assert_allclose(got[2], want[at], atol=TOL, rtol=0)


def test_an_idle_lanes_recurrent_state_is_untouched_by_decode(
        either_runner):
    """A row of length 0 in the decode program (a free lane) leaves its
    lane's planes of ``ssm`` and ``conv`` bit for bit as they were."""
    runner = either_runner
    (_,), kv = _prefill_rows(runner, runner.new_cache(),
                             [(0, _prompt(8, salt=3))], 8)
    other = _prompt(5, salt=4)
    (_,), kv = _prefill_rows(runner, kv, [(1, other[:4])], 4)
    before = [np.asarray(t[:, 0]) for t in kv[1:]]
    assert all(np.abs(t).max() > 0 for t in before)
    for _ in range(3):                  # lane 0 sits out three steps
        _, kv = _decode(runner, kv, {1: (other[4], 4)})
    for was, table in zip(before, kv[1:]):
        assert (np.asarray(table[:, 0]) == was).all()


@pytest.mark.parametrize("forced", [False, True], ids=["xla", "kernel"])
def test_compile_regions_say_how_the_state_is_updated(net, monkeypatch,
                                                      forced):
    """``ssm_kernel_updates`` on every ``compile`` region: the decode
    program's count of one-token state updates made by the kernel — its
    Mamba layers where the kernel is taken, 0 where XLA's form is — and
    0 on a prefill program, whose scan is the chunked form either way."""
    if forced:
        _take_the_kernel(monkeypatch)
    symbol, params = _export(net)
    r = GenerateRunner(symbol, params, net.state_spec(LANES, CAP),
                       prompt_buckets=(4,), cache=None)
    profiler.set_state("run")
    try:
        r.warmup([("prefill", (1, 4)), ("decode", (LANES + 1,))])
        events = profiler.events()
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
    done = {e["args"]["kind"]: int(e["args"]["ssm_kernel_updates"])
            for e in events if e["name"] == obs.SPAN_COMPILE}
    assert done == {"prefill": 0, "decode": N_MAMBA if forced else 0}
    assert {k[0]: e["ssm_kernel_updates"]
            for k, e in r._entries.items()} == done


# ---------------------------------------------------------- the batcher
class _Clock:
    t = 100.0

    def __call__(self):
        return self.t


def _greedy(weights, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        toks.append(int(np.argmax(_logits(weights, toks)[-1])))
    return toks[len(prompt):]


def _drive(b, reqs, n=200):
    for _ in range(n):
        b.step()
        if all(r.done() for r in reqs):
            return
    raise AssertionError("requests not done")


def test_batcher_streams_equal_the_references_greedy_streams(runner,
                                                             weights):
    """Five requests over three lanes: lanes are reused, a prompt of 19
    prefills in three chunks while its neighbours decode, and every
    stream is the reference's greedy stream token for token."""
    b = GenerateBatcher(runner, clock=_Clock(), max_lanes=LANES)
    prompts = [_prompt(n, salt=n) for n in (3, 19, 6, 9, 2)]
    lens = [5, 4, 7, 3, 6]
    reqs = [b.submit(p, max_tokens=n) for p, n in zip(prompts, lens)]
    _drive(b, reqs)
    assert b.joins == 5
    for r, p, n in zip(reqs, prompts, lens):
        assert r.result() == _greedy(weights, p, n)


def test_replay_from_prompt_and_prefix_resumes_the_stream(runner, weights):
    """Replay-on-steal: a second attempt is given the prompt and the
    tokens already streamed, rebuilds the lane's recurrent state from
    them (19 + 4 tokens: three chunks), and continues the uninterrupted
    stream."""
    prompt = _prompt(19, salt=11)
    whole = _greedy(weights, prompt, 9)
    b = GenerateBatcher(runner, clock=_Clock(), max_lanes=LANES)
    first = b.submit(prompt, max_tokens=9)
    _drive(b, [first])
    assert first.result() == whole
    again = b.submit(prompt, max_tokens=9, prefix=whole[:4])
    _drive(b, [again])
    assert again.result() == whole


def test_regions_carry_the_new_counts(runner):
    """``gen/decode``: ``active`` and ``context_tokens``;
    ``gen/prefill/call``: ``tokens`` (valid, not padded) and ``resets``;
    the reset counter counts the same rows."""
    resets = lambda: obs.summary().get("mxtpu_gen_state_reset_total", 0)
    before = resets()
    b = GenerateBatcher(runner, clock=_Clock(), max_lanes=LANES)
    profiler.set_state("run")
    try:
        reqs = [b.submit(_prompt(11, salt=5), max_tokens=3),
                b.submit(_prompt(9, salt=6), max_tokens=3)]
        _drive(b, reqs)
        events = profiler.events()
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
    calls = [e["args"] for e in events if e["name"] == obs.SPAN_PREFILL_CALL]
    # one group of two on the 8-wide bucket: chunks of 8 + 8, then 3 + 1
    assert [(c["tokens"], c["resets"]) for c in calls] == [(16, 2), (4, 0)]
    decodes = [e["args"] for e in events if e["name"] == obs.SPAN_DECODE]
    assert [(d["active"], d["context_tokens"]) for d in decodes] == \
        [(2, 20), (2, 22)]
    assert resets() - before == 2


# -------------------------------------------------------------- the ops
def test_chunked_scan_equals_the_recurrence():
    """``_ssd_chunked`` (chunk 8, 29 positions: three whole chunks and
    a padded one) against the reference's per-position scan, from zero
    state and, cut in two, from a carried state."""
    rng = np.random.default_rng(0)
    B, T, H, P, N = 2, 29, 4, 16, 16
    x = jnp.asarray(rng.normal(size=(B, T, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.3, size=(B, T, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=H), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    want = np.asarray(ref.selective_scan(None, x, dt, a, bm, cm))
    zero = jnp.zeros((B, H, P, N), jnp.float32)
    y, s_end = rnn_impl._ssd_chunked(x, dt, a, bm, cm, zero, 8)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4, rtol=1e-4)
    cut = 13
    y1, s_mid = rnn_impl._ssd_chunked(x[:, :cut], dt[:, :cut], a,
                                      bm[:, :cut], cm[:, :cut], zero, 8)
    y2, s_two = rnn_impl._ssd_chunked(x[:, cut:], dt[:, cut:], a,
                                      bm[:, cut:], cm[:, cut:], s_mid, 8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_two), np.asarray(s_end),
                               atol=1e-4, rtol=1e-4)


def test_padded_positions_and_step_zero_in_the_state_ops():
    """``ssm_scan`` and ``ssm_conv``: positions from ``length`` on
    change nothing; ``step`` 0 starts from zeros whatever the table
    held; T = 1 equals the chunked form's first position."""
    rng = np.random.default_rng(1)
    B, T, H, P, N, K = 2, 8, 4, 16, 16, 4
    C = H * P + 2 * N
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    table = arr(3, B, H, P, N)
    x, dt, bm, cm = arr(B, T, H * P), arr(B, T, H), arr(B, T, N), arr(B, T, N)
    a_log, d_skip, dt_bias = arr(H), arr(H), arr(H)
    step = jnp.asarray([0.0, 5.0])
    length = jnp.asarray([3.0, 8.0])
    scan = lambda tb, n, ln, st: rnn_impl._ssm_scan_op(
        tb, x[:, :n], dt[:, :n], bm[:, :n], cm[:, :n], a_log, d_skip,
        dt_bias, st, ln, layer=1, chunk=4)
    y, out = scan(table, T, length, step)
    y3, out3 = scan(table, 3, jnp.asarray([3.0, 3.0]), step)
    np.testing.assert_allclose(np.asarray(y[0, :3]), np.asarray(y3[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1, 0]), np.asarray(out3[1, 0]),
                               atol=1e-5)
    # the other planes are handed on untouched
    assert (np.asarray(out[0]) == np.asarray(table[0])).all()
    # lane 0 (step 0) did not see what the table held
    y_z, _ = scan(table.at[1, 0].set(0.0), T, length, step)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_z), atol=1e-6)
    # one position at a time equals the chunked form
    y1, out1 = scan(table, 1, jnp.ones(2), step)
    np.testing.assert_allclose(np.asarray(y1[:, 0]), np.asarray(y[:, 0]),
                               atol=1e-5)
    conv_t = arr(3, B, K - 1, C)
    xc, w, bias = arr(B, T, C), arr(C, K), arr(C)
    conv = lambda n, ln: rnn_impl._ssm_conv_op(conv_t, xc[:, :n], w, bias,
                                               step, ln, layer=2)
    yc, tc = conv(T, length)
    yc3, tc3 = conv(3, jnp.asarray([3.0, 3.0]))
    np.testing.assert_allclose(np.asarray(yc[0, :3]), np.asarray(yc3[0]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(tc[2, 0]), np.asarray(tc3[2, 0]),
                               atol=1e-6)
    # lane 0's new state: its last three valid inputs
    np.testing.assert_allclose(np.asarray(tc[2, 0]), np.asarray(xc[0, :3]),
                               atol=1e-6)
    # a lane with nothing valid keeps what it had (lane 1, step > 0)
    _, keep = rnn_impl._ssm_conv_op(conv_t, xc, w, bias, step,
                                    jnp.asarray([3.0, 0.0]), layer=2)
    assert (np.asarray(keep[2, 1]) == np.asarray(conv_t[2, 1])).all()


def _todays_cached_attention(q, k_cache, v_cache, step):
    """``cached_attention`` as it was before it took unequal head
    counts, kept here as the equal-heads oracle."""
    B, H, T, D = q.shape
    L = k_cache.shape[2]
    s = jnp.asarray(step).astype(jnp.int32)
    scores = jnp.einsum("bhtd,bhld->bhtl", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32),
                        preferred_element_type=jnp.float32) \
        * (1.0 / float(np.sqrt(D)))
    pos_q = s[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    mask = jnp.arange(L, dtype=jnp.int32)[None, None, :] <= pos_q[:, :, None]
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    out = jnp.einsum("bhtl,bhld->bhtd", jax.nn.softmax(scores, axis=-1),
                     v_cache.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def test_grouped_query_attention():
    rng = np.random.default_rng(2)
    B, H, HK, T, L, D = 2, 4, 2, 3, 10, 16
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = arr(B, H, T, D), arr(B, HK, L, D), arr(B, HK, L, D)
    step = jnp.asarray([2.0, 6.0])
    got = rnn_impl._cached_attention_op(q, k, v, step, sm_scale=0.0625)
    # the repeated-heads form: each key/value head copied for its group
    want = rnn_impl._cached_attention_op(
        q, jnp.repeat(k, H // HK, axis=1), jnp.repeat(v, H // HK, axis=1),
        step, sm_scale=0.0625)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # with equal heads: bit for bit what it was
    k4, v4 = arr(B, H, L, D), arr(B, H, L, D)
    same = rnn_impl._cached_attention_op(q, k4, v4, step)
    assert (np.asarray(same) ==
            np.asarray(_todays_cached_attention(q, k4, v4, step))).all()
    with pytest.raises(mx.base.MXNetError):
        rnn_impl._cached_attention_op(q, arr(B, 3, L, D), arr(B, 3, L, D),
                                      step)


def test_new_ops_ride_the_symbol_json(net):
    symbol, _ = _export(net)
    text = symbol.tojson()
    for op in ("ssm_scan", "ssm_conv", "rms_norm", "gated_rms_norm",
               "cached_attention", "kv_cache_write"):
        assert f'"op": "{op}"' in text
    again = sym_mod.load_json(text)
    assert again.tojson() == text
    assert '"chunk": "8"' in text and '"sm_scale": "0.0625"' in text


def test_the_memory_oracle_reads_every_declared_table(runner):
    """``mxmem``'s KV oracle on a runner with a state spec: the three
    tables are declared to it, allocated bytes equal the declared
    geometry plus the scratch slot, and no hazard fires."""
    from mxtpu.analysis import memflow
    record = memflow.generate_record(
        runner, buckets=[runner.default_bucket("decode")])
    kv = record["kv"]
    assert [t["name"] for t in kv["tables"]] == ["kv", "ssm", "conv"]
    assert kv["table_bytes"] == kv["expected_bytes"] == \
        sum(runner.state_bytes().values())
    assert kv["spec"] == list(runner.kv_spec) and kv["itemsize"] == 4
    assert memflow.kv_hazards(record) == []
    donation = record["programs"]["decode_step"]["donation"]
    assert donation["declared"] == [3]      # tokens, step, length, state
    view = runner.memory_summary([runner.default_bucket("decode")])
    assert view["kv"]["table_bytes"] == kv["table_bytes"]


def test_the_prefill_ladder_ends_where_it_is_told_to(net, weights):
    """``max_prefill_batch``: where lanes are too large to gather many
    at once, the ladder ends at that rung and a step admits no more
    requests than it holds; the streams are what they would have been."""
    symbol, params = _export(net)
    r = GenerateRunner(symbol, params, net.state_spec(LANES, CAP),
                       prompt_buckets=BUCKETS, max_prefill_batch=2,
                       cache=None)
    assert r.batch_buckets == (1, 2)
    assert ("prefill", (3, 4)) not in r.buckets()
    with pytest.raises(mx.base.MXNetError):
        r.batch_rung_for(3)
    b = GenerateBatcher(r, clock=_Clock(), max_lanes=LANES)
    prompts = [_prompt(3, salt=s) for s in (21, 22, 23)]
    reqs = [b.submit(p, max_tokens=3) for p in prompts]
    assert b.step()["admitted"] == 2 and b.step()["admitted"] == 1
    _drive(b, reqs)
    for req, p in zip(reqs, prompts):
        assert req.result() == _greedy(weights, p, 3)


def test_bfloat16_weights_reach_the_programs_as_bfloat16(net):
    """Served under ``amp`` the weights are staged in bfloat16 and a
    graph with a state spec is given them as they are: no program holds
    a float32 copy of a matrix (at the published widths a float32
    embedding alone is 822 MB, made anew every step)."""
    symbol, params = _export(net)
    r = GenerateRunner(symbol, params,
                       net.state_spec(LANES, CAP, kv_dtype="bfloat16"),
                       prompt_buckets=BUCKETS, amp=True, cache=None)
    assert all(str(v.dtype) == "bfloat16" for v in r.weight_buffers())
    for bucket in (r.default_bucket("decode"), ("prefill", (2, 8))):
        text = r.lowered_program_text(bucket)
        assert "bf16[97,64]" in text and "f32[97,64]" not in text
        assert "bf16[256,64]" in text and "f32[256,64]" not in text
    (first,), _ = _prefill_rows(r, r.new_cache(), [(0, _prompt(5))], 8)
    assert np.isfinite(first).all()
