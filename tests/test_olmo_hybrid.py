"""The Gated DeltaNet / full-attention decoder (``olmo_hybrid``) on the
serving path.

A tiny model (2 periods of ``[linear, linear, linear, full]``, hidden 64,
4 heads of 16, delta-rule 4 heads with keys of 8 and values of 16, chunk
8, vocabulary 97, norms on the sublayers' outputs, QK-norm, untied head)
is held against the plain reference
(``benchmark/reference_olmo_hybrid.py``: float32, the delta rule one
position at a time, no cache) on seeded weights, at every place where a
carried matrix of state can go wrong that keys and values forgive: a
padded position, a padding row, a prompt prefilled in chunks, a lane
that another request used, a stream replayed from its prompt and prefix.
Logits are compared, not tokens; on the CPU in float32 the program and
the reference differ by rounding only, so every tolerance on logits of
size 0.4 is 3e-5 (a path that dropped a term would miss by 1e-2 and
more).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, obs, profiler
from mxtpu import symbol as sym_mod
from mxtpu.models.hybrid import (GatedDeltaNetMixer, HybridDecoderModel,
                                 olmo_hybrid_7b)
from mxtpu.ndarray import rnn_impl
from mxtpu.serving import DeviceLogits, GenerateBatcher, GenerateRunner

from benchmark import reference_olmo_hybrid as ref
from benchmark import weights_olmo_hybrid

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
CFG = {"model_type": "olmo_hybrid", "vocab_size": 97, "hidden_size": 64,
       "intermediate_size": 128, "layer_types": PERIOD * 2,
       "num_hidden_layers": 8, "num_attention_heads": 4,
       "num_key_value_heads": 4, "linear_num_key_heads": 4,
       "linear_num_value_heads": 4, "linear_key_head_dim": 8,
       "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
       "linear_allow_neg_eigval": True, "linear_chunk_size": 8,
       "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
       "attention_bias": False, "rope_parameters": {"rope_theta": None}}
LANES, CAP, BUCKETS = 3, 48, (4, 8)
TOL = 3e-5
SEED = 2 ** 31 + 7


def _built(cfg, weights):
    n = HybridDecoderModel.from_config(cfg)
    n.initialize()
    leaves = n.named_leaves()
    assert {k: tuple(p.shape) for k, p in leaves.items()} == \
        weights_olmo_hybrid.leaf_shapes(cfg)
    for name, p in leaves.items():
        p.set_data(nd.array(np.asarray(weights[name].astype(jnp.float32))))
    return n


@pytest.fixture(scope="module")
def weights():
    return weights_olmo_hybrid.make(CFG, SEED)


@pytest.fixture(scope="module")
def net(weights):
    return _built(CFG, weights)


def _export(net):
    out = net(*[sym_mod.var(f"data{i}") for i in range(6)])
    params = {p.name: p.data() for p in net.collect_params().values()}
    return sym_mod.Group(list(out)), params


@pytest.fixture(scope="module")
def runner(net):
    symbol, params = _export(net)
    return GenerateRunner(symbol, params, net.state_spec(LANES, CAP),
                          prompt_buckets=BUCKETS, cache=None)


def _logits(weights, tokens, cfg=CFG):
    return np.asarray(ref.forward(cfg, weights, np.asarray(tokens)[None]))[0]


def _prompt(n, salt=0):
    return np.random.default_rng(200 + salt).integers(1, 97, n).tolist()


def _eager_last(net, seq, n):
    """The model's logits at position ``n - 1`` of ``seq``: one call from
    fresh tables, ``n`` of the tokens valid."""
    tables = [nd.array(np.zeros(s, np.float32))
              for _, s, _, _ in net.state_spec(1, 32)]
    out = net(nd.array(np.asarray(seq, np.float32)[None]),
              nd.array(np.zeros(1)), nd.array(np.array([float(n)])), *tables)
    return out[0].asnumpy()[0, 0]


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
    """One decode step: {lane: (token, frontier)}."""
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
@pytest.mark.parametrize("n", [1, 5, 8, 9, 21])
def test_full_forward_matches_the_reference(net, weights, n):
    """Each length's last position: 1 is the one-step form, 5 one padded
    chunk, 8 a whole chunk, 9 and 21 carry state over chunks."""
    seq = _prompt(21)
    want = _logits(weights, seq)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(_eager_last(net, seq, n), want[n - 1],
                               atol=TOL, rtol=0)


def test_state_spec_declares_three_tables(net):
    kv, delta, conv = net.state_spec(5, 40, kv_dtype="bfloat16")
    assert kv == ("kv", (2, 2, 5, 4, 40, 16), 2, "bfloat16")
    assert delta == ("delta", (6, 5, 4, 8, 16), 1, "float32")
    assert conv == ("conv", (6, 5, 3, 4 * (8 + 8 + 16)), 1, "float32")


def test_the_published_model_at_its_widths():
    """The constructor of the published sizes, built symbolically (no
    weight is made): 16 layers as one pipeline stage declare the tables
    the issue's arithmetic counts."""
    stage = olmo_hybrid_7b(num_layers=16)
    assert stage.layer_types == tuple(PERIOD * 4)
    kv, delta, conv = stage.state_spec(31, 2304, kv_dtype="bfloat16")
    assert kv[1] == (4, 2, 31, 30, 2304, 128)
    assert delta[:2] == ("delta", (12, 31, 30, 96, 192))
    assert conv[1] == (12, 31, 3, 11520)
    shapes = {k: tuple(p.shape) for k, p in stage.named_leaves().items()}
    assert sum(int(np.prod(s)) for s in shapes.values()) == 4_100_788_944
    assert shapes["head"] == shapes["embed"] == (100352, 3840)


@pytest.mark.parametrize("bad,names", [
    ({"model_type": "llama"}, "llama"),
    ({"layer_types": ["linear_attention", "mamba"]}, "mamba"),
    ({"rope_parameters": {"rope_theta": 10000.0}}, "rope_theta")])
def test_from_config_names_what_it_cannot_build(bad, names):
    with pytest.raises(mx.base.MXNetError, match=names):
        HybridDecoderModel.from_config(dict(CFG, **bad))


def test_granite_configs_say_which_type_they_speak_of():
    granite = {"model_type": "granitemoehybrid", "num_local_experts": 4}
    with pytest.raises(mx.base.MXNetError, match="granitemoehybrid"):
        HybridDecoderModel.from_config(granite)
    with pytest.raises(mx.base.MXNetError, match="linear_attention"):
        HybridDecoderModel.from_config(
            {"model_type": "granitemoehybrid",
             "layer_types": ["mamba", "linear_attention"]})
    with pytest.raises(mx.base.MXNetError, match="one recurrent kind"):
        HybridDecoderModel(97, 64, 128, ["mamba", "linear_attention"], 4, 4)


def test_the_depth_cut_builds_the_models_first_layers(weights):
    """``from_config`` with the first 4 of the 8 kinds: the leaves are
    the whole model's first layers' (same names, same values from the
    same seed) and the logits are the reference's, cut the same way."""
    cut = dict(CFG, layer_types=CFG["layer_types"][:4], num_hidden_layers=4)
    w_cut = weights_olmo_hybrid.make(cut, SEED)
    assert set(w_cut) < set(weights)
    for name, leaf in w_cut.items():
        assert (np.asarray(leaf) == np.asarray(weights[name])).all(), name
    stage = _built(cut, w_cut)
    assert len(stage.layers) == 4
    assert [s[1][0] for s in stage.state_spec(1, 8)] == [1, 3, 3]
    seq = _prompt(13, salt=3)
    want = _logits(w_cut, seq, cut)
    np.testing.assert_allclose(_eager_last(stage, seq, 13), want[12],
                               atol=TOL, rtol=0)
    # and it is a cut: the whole model says something else
    assert np.abs(_logits(weights, seq)[12] - want[12]).max() > 1e-2


def test_the_block_layout_is_read_from_the_configuration(net, weights):
    """``post``: the norm on the sublayer's output.  The same leaves in
    a model built ``pre`` give other logits, and this model's are the
    reference's (which norms outputs)."""
    layer = net.layers[0]
    assert layer._post and isinstance(layer.mixer, GatedDeltaNetMixer)
    kinds = CFG["layer_types"]
    pre = HybridDecoderModel(
        97, 64, 128, kinds, 4, 4, delta_heads=4, delta_key_dim=8,
        delta_value_dim=16, delta_neg_eigval=True, chunk=8, eps=1e-6,
        layout="pre", qk_norm=True, tie_embeddings=False)
    pre.initialize()
    for name, p in pre.named_leaves().items():
        p.set_data(nd.array(np.asarray(weights[name].astype(jnp.float32))))
    seq = _prompt(9, salt=4)
    want = _logits(weights, seq)[8]
    assert np.abs(_eager_last(pre, seq, 9) - want).max() > 1e-2
    np.testing.assert_allclose(_eager_last(net, seq, 9), want, atol=TOL,
                               rtol=0)
    with pytest.raises(mx.base.MXNetError, match="'mid'"):
        HybridDecoderModel(97, 64, 128, kinds, 4, 4, layout="mid")


def test_qk_norm_is_over_all_heads_and_under_its_scope(net, weights):
    """A weight of the query norm scaled by 3 moves the logits in the
    program as in the reference (the norm is there, over the whole
    projection), and the decode program's text carries the scope."""
    scaled = dict(weights)
    gamma = np.ones(64, np.float32)
    gamma[::2] = 3.0
    scaled["l3.q_norm"] = jnp.asarray(gamma, jnp.bfloat16)
    other = _built(CFG, scaled)
    seq = _prompt(11, salt=5)
    want = _logits(scaled, seq)[10]
    assert np.abs(want - _logits(weights, seq)[10]).max() > 1e-3
    np.testing.assert_allclose(_eager_last(other, seq, 11), want, atol=TOL,
                               rtol=0)
    symbol, _ = _export(net)
    assert '"scope": "qk_norm"' in symbol.tojson()


# ----------------------------------------------------------- the runner
def test_tables_follow_the_spec(net):
    symbol, params = _export(net)
    r = GenerateRunner(symbol, params,
                       net.state_spec(LANES, CAP, kv_dtype="bfloat16"),
                       prompt_buckets=BUCKETS, cache=None)
    kv, delta, conv = tables = r.new_cache()
    assert kv.dtype == jnp.bfloat16 and delta.dtype == conv.dtype == jnp.float32
    assert delta.shape == (6, LANES + 1, 4, 8, 16)
    assert [t.name for t in r.state_spec] == ["kv", "delta", "conv"]
    series = obs.snapshot()["mxtpu_gen_state_bytes"]["series"]
    got = {v["labels"]["table"]: int(v["value"]) for v in series}
    assert got["delta"] == r.held_bytes(tables)["delta"] == delta.nbytes
    (first,), tables = _prefill_rows(r, tables, [(0, _prompt(5))], 8)
    assert tables[0].dtype == jnp.bfloat16 and np.isfinite(first).all()


@pytest.mark.parametrize("plen", [1, 5, 8])
def test_prefill_then_decode_equals_the_full_forward(runner, weights, plen):
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
    positions must not decay or correct it."""
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
    cut = [3, 14, 8]
    seqs = [_prompt(n + 2, salt=n) for n in cut]
    firsts, kv = _prefill_rows(
        runner, runner.new_cache(),
        [(lane, s[:n]) for lane, (s, n) in enumerate(zip(seqs, cut))], 8)
    wants = [_logits(weights, s) for s in seqs]
    for got, want, n in zip(firsts, wants, cut):
        np.testing.assert_allclose(got, want[n - 1], atol=TOL, rtol=0)
    for k in range(2):
        got, kv = _decode(runner, kv, {lane: (s[n + k], n + k) for lane,
                                       (s, n) in enumerate(zip(seqs, cut))})
        for lane, (want, n) in enumerate(zip(wants, cut)):
            np.testing.assert_allclose(got[lane], want[n + k], atol=TOL,
                                       rtol=0)


def test_a_reused_lane_starts_from_zero_state(runner, weights):
    """A long request, then a short one in the same lane: neither the
    delta-rule state nor the keys of the first may reach the second."""
    long_seq, short = _prompt(20, salt=1), _prompt(6, salt=2)
    _, kv = _prefill_rows(runner, runner.new_cache(), [(2, long_seq[:16])],
                          8)
    for at in range(16, 20):
        _, kv = _decode(runner, kv, {2: (long_seq[at], at)})
    assert np.abs(np.asarray(kv[1][:, 2])).max() > 0
    want = _logits(weights, short)
    (first,), kv = _prefill_rows(runner, kv, [(2, short[:4])], 4)
    np.testing.assert_allclose(first, want[3], atol=TOL, rtol=0)
    for at in (4, 5):
        got, kv = _decode(runner, kv, {2: (short[at], at)})
        np.testing.assert_allclose(got[2], want[at], atol=TOL, rtol=0)


def test_an_idle_lanes_state_is_untouched_by_decode(runner):
    """A row of length 0 in the decode program (a free lane) leaves its
    lane's planes of ``delta`` and ``conv`` bit for bit as they were."""
    (_,), kv = _prefill_rows(runner, runner.new_cache(),
                             [(0, _prompt(8, salt=3))], 8)
    other = _prompt(5, salt=4)
    (_,), kv = _prefill_rows(runner, kv, [(1, other[:4])], 4)
    before = [np.asarray(t[:, 0]) for t in kv[1:]]
    assert all(np.abs(t).max() > 0 for t in before)
    for _ in range(3):
        _, kv = _decode(runner, kv, {1: (other[4], 4)})
    for was, table in zip(before, kv[1:]):
        assert (np.asarray(table[:, 0]) == was).all()


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
    """A second attempt is given the prompt and the tokens already
    streamed, rebuilds the lane's delta-rule state from them (19 + 4
    tokens: three chunks), and continues the uninterrupted stream."""
    prompt = _prompt(19, salt=11)
    whole = _greedy(weights, prompt, 9)
    b = GenerateBatcher(runner, clock=_Clock(), max_lanes=LANES)
    first = b.submit(prompt, max_tokens=9)
    _drive(b, [first])
    assert first.result() == whole
    again = b.submit(prompt, max_tokens=9, prefix=whole[:4])
    _drive(b, [again])
    assert again.result() == whole


def test_a_prefill_call_counts_the_lanes_it_gathers(runner):
    """``gen/prefill/call`` carries ``lane_bytes``: rows x one lane of
    every table, what a paged or in-place prefill would bring down."""
    lane = sum(runner.state_bytes().values()) // (LANES + 1)
    assert lane == 4 * (2 * 2 * 4 * CAP * 16 + 6 * 4 * 8 * 16
                        + 6 * 3 * 128)
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
    assert [(c["rows"], c["tokens"], c["lane_bytes"]) for c in calls] == \
        [(2, 16, 2 * lane), (2, 4, 2 * lane)]


# -------------------------------------------------------------- the ops
def _delta_inputs(rng, B, T, H, dk, dv, beta_lo=0.0, beta_hi=2.0,
                  g_lo=-0.5, g_hi=-1e-3):
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    unit = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)
    q, k = unit(arr(B, T, H, dk)) / np.sqrt(dk), unit(arr(B, T, H, dk))
    beta = jnp.asarray(rng.uniform(beta_lo, beta_hi, (B, T, H)), jnp.float32)
    g = jnp.asarray(rng.uniform(g_lo, g_hi, (B, T, H)), jnp.float32)
    return q, k, arr(B, T, H, dv), g, beta


@pytest.mark.parametrize("case,kw,tol", [
    ("beta below and above one", {}, 1e-4),
    ("beta forced above one", {"beta_lo": 1.2, "beta_hi": 1.99}, 1e-4),
    # e^-12 a step: over a chunk of 8 a product of decays is e^-96, under
    # float32's least number, so a quotient of products would be 0 / 0
    ("decays a quotient would underflow on", {"g_lo": -14.0, "g_hi": -10.0},
     1e-5)])
def test_chunked_delta_rule_equals_the_recurrence(case, kw, tol):
    """``_delta_chunked`` (chunk 8, 29 positions: three whole chunks
    and a padded one) against the reference's per-position scan, from
    zero state and, cut in two, from a carried state."""
    rng = np.random.default_rng(0)
    B, T, H, dk, dv = 2, 29, 4, 8, 16
    q, k, v, g, beta = _delta_inputs(rng, B, T, H, dk, dv, **kw)
    want = np.asarray(ref.delta_rule(None, q, k, v, g, beta))
    assert np.isfinite(want).all() and np.abs(want).max() > 1e-2
    zero = jnp.zeros((B, H, dk, dv), jnp.float32)
    o, s_end = rnn_impl._delta_chunked(q, k, v, g, beta, zero, 8)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), want, atol=tol, rtol=tol)
    cut = 13
    part = lambda lo, hi: [z[:, lo:hi] for z in (q, k, v, g, beta)]
    o1, s_mid = rnn_impl._delta_chunked(*part(0, cut), zero, 8)
    o2, s_two = rnn_impl._delta_chunked(*part(cut, T), s_mid, 8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               want, atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(s_two), np.asarray(s_end),
                               atol=tol, rtol=tol)
    # one position at a time through the one-step form: the same again
    s, outs = zero, []
    for t in range(T):
        o_t, s = rnn_impl._delta_step(s, q[:, t], k[:, t], v[:, t], g[:, t],
                                      beta[:, t])
        outs.append(o_t)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)), want,
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_end), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("rows", [1, 4, 8, 29, 64])
@pytest.mark.parametrize("alike", [False, True])
def test_unit_lower_inverse_by_products(rows, alike):
    """``_unit_lower_inverse`` against NumPy's float64 inverse: a size
    under its 4-row blocks, one block, two, sizes that are grown to a power
    of two, and the published chunk of 64 (four joins of pairs); and
    where every key of the chunk is nearly the same and ``beta`` is
    1.9 — ``A`` close to 1.9 below the diagonal, on which the whole
    Neumann series over 64 rows overflows."""
    rng = np.random.default_rng(rows)
    if alike:
        k = rng.normal(size=(1, 3, 1, 8)) + 0.01 * rng.normal(
            size=(1, 3, rows, 8))
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        A = np.tril(1.9 * k @ np.swapaxes(k, -1, -2), -1)
    else:
        A = np.tril(rng.normal(0, 0.5, (2, 3, rows, rows)), -1)
    mm = lambda spec, a, b: jnp.einsum(spec, a, b, precision="highest")
    got = np.asarray(rnn_impl._unit_lower_inverse(
        jnp.asarray(A, jnp.float32), mm))
    want = np.linalg.inv(np.eye(rows) + A)
    assert got.shape == want.shape
    # alike keys: 3e-5 read (forward substitution in float32: 3e-6)
    np.testing.assert_allclose(
        got, want, rtol=0, atol=(1e-4 if alike else 2e-5) * np.abs(want).max())


def test_chunked_delta_rule_at_the_published_chunk():
    """Chunks of 64, as the configuration has them (the inverse joins
    its 4-row blocks four times): 150 positions, two whole chunks and
    a padded one, against the reference's per-position scan."""
    rng = np.random.default_rng(1)
    B, T, H, dk, dv = 1, 150, 2, 8, 16
    q, k, v, g, beta = _delta_inputs(rng, B, T, H, dk, dv)
    want = np.asarray(ref.delta_rule(None, q, k, v, g, beta))
    o, _ = rnn_impl._delta_chunked(
        q, k, v, g, beta, jnp.zeros((B, H, dk, dv), jnp.float32), 64)
    np.testing.assert_allclose(np.asarray(o), want, atol=1e-4, rtol=1e-4)


def test_padded_positions_and_step_zero_in_delta_rule():
    """Positions from ``length`` on change nothing; ``step`` 0 starts
    from zeros whatever the table held; T = 1 equals the chunked form's
    first position; the other planes are handed on untouched."""
    rng = np.random.default_rng(1)
    B, T, H, dk, dv = 2, 8, 4, 8, 16
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    table = arr(3, B, H, dk, dv)
    q, k, v = arr(B, T, H * dk), arr(B, T, H * dk), arr(B, T, H * dv)
    g = -jnp.abs(arr(B, T, H)) * 0.3
    beta = jnp.asarray(rng.uniform(0, 2, (B, T, H)), jnp.float32)
    step = jnp.asarray([0.0, 5.0])
    length = jnp.asarray([3.0, 8.0])
    rule = lambda tb, n, ln: rnn_impl._delta_rule_op(
        tb, q[:, :n], k[:, :n], v[:, :n], g[:, :n], beta[:, :n], step, ln,
        layer=1, chunk=4)
    o, out = rule(table, T, length)
    o3, out3 = rule(table, 3, jnp.asarray([3.0, 3.0]))
    np.testing.assert_allclose(np.asarray(o[0, :3]), np.asarray(o3[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1, 0]), np.asarray(out3[1, 0]),
                               atol=1e-5)
    assert (np.asarray(out[0]) == np.asarray(table[0])).all()
    assert (np.asarray(out[2]) == np.asarray(table[2])).all()
    o_z, _ = rule(table.at[1, 0].set(0.0), T, length)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_z), atol=1e-6)
    o1, out1 = rule(table, 1, jnp.ones(2))
    np.testing.assert_allclose(np.asarray(o1[:, 0]), np.asarray(o[:, 0]),
                               atol=1e-5)
    # a row with nothing valid keeps its state bit for bit (lane 1)
    _, keep = rnn_impl._delta_rule_op(
        table, q[:, :1], k[:, :1], v[:, :1], g[:, :1], beta[:, :1], step,
        jnp.asarray([1.0, 0.0]), layer=1)
    assert (np.asarray(keep[1, 1]) == np.asarray(table[1, 1])).all()


def test_gated_rms_norm_per_head():
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.normal(size=(2, 3, 4 * 16)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(2, 3, 4 * 16)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=16), jnp.float32)
    got = rnn_impl._gated_rms_norm_op(y, z, w, eps=1e-6, group=16,
                                      norm_before_gate=True)
    heads = np.asarray(y, np.float64).reshape(2, 3, 4, 16)
    want = heads / np.sqrt((heads ** 2).mean(-1, keepdims=True) + 1e-6) \
        * np.asarray(w, np.float64)
    gate = np.asarray(z, np.float64)
    want = want.reshape(2, 3, 64) * gate / (1 + np.exp(-gate))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    # the whole-width, gate-first form is what it was
    whole = rnn_impl._gated_rms_norm_op(y, z, jnp.ones(64), eps=1e-5)
    gated = np.asarray(y, np.float64) * gate / (1 + np.exp(-gate))
    np.testing.assert_allclose(
        np.asarray(whole),
        gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5),
        atol=1e-5)


def test_ssm_conv_without_a_bias():
    rng = np.random.default_rng(3)
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    table, x, w = arr(2, 2, 3, 12), arr(2, 5, 12), arr(12, 4)
    step, length = jnp.asarray([0.0, 4.0]), jnp.asarray([5.0, 2.0])
    y, out = rnn_impl._ssm_conv_op(table, x, w, step, length, layer=1,
                                   no_bias=True)
    y0, out0 = rnn_impl._ssm_conv_op(table, x, w, jnp.zeros(12), step, length,
                                     layer=1)
    assert (np.asarray(y) == np.asarray(y0)).all()
    assert (np.asarray(out) == np.asarray(out0)).all()
    want = np.asarray(jax.nn.silu(ref.causal_conv(x[:1], w)))
    np.testing.assert_allclose(np.asarray(y[:1]), want, atol=1e-6)


def test_new_ops_ride_the_symbol_json(net):
    symbol, _ = _export(net)
    text = symbol.tojson()
    for op in ("delta_rule", "ssm_conv", "rms_norm", "gated_rms_norm",
               "cached_attention", "kv_cache_write"):
        assert f'"op": "{op}"' in text
    assert sym_mod.load_json(text).tojson() == text
    assert '"chunk": "8"' in text and '"no_bias": "True"' in text
    assert '"group": "16"' in text and '"norm_before_gate": "True"' in text


def test_the_memory_oracle_reads_every_declared_table(runner):
    """``mxmem``'s KV oracle on this model's three-table spec: ``kv``,
    ``delta`` and ``conv`` are declared to it, allocated bytes equal the
    declared geometry plus the scratch slot, and no hazard fires."""
    from mxtpu.analysis import memflow
    record = memflow.generate_record(
        runner, buckets=[runner.default_bucket("decode")])
    kv = record["kv"]
    assert [t["name"] for t in kv["tables"]] == ["kv", "delta", "conv"]
    assert kv["table_bytes"] == kv["expected_bytes"] == \
        sum(runner.state_bytes().values())
    assert memflow.kv_hazards(record) == []
    assert record["programs"]["decode_step"]["donation"]["declared"] == [3]


def test_a_decode_step_brings_back_token_ids_not_logits(runner, weights):
    """``decode`` leaves its logits on the device: each slot's first
    maximum is what the host's argmax would find, a greedy draw takes
    it without the numbers, and the numbers come over (whole, once)
    only when asked for — a top-k draw, or a caller that converts.  The
    decode region counts the logits made and the bytes that crossed.
    A batcher's streams, greedy and top-k lanes side by side, are what
    they were (and the greedy ones are held against the reference
    above)."""
    from mxtpu.serving.generate import sample_token
    seq = _prompt(9, salt=8)
    (_,), kv = _prefill_rows(runner, runner.new_cache(), [(1, seq[:8])], 8)
    slots = runner.max_lanes + 1
    tok = np.zeros((slots, 1), np.float32)
    step, length = np.zeros(slots, np.float32), np.zeros(slots, np.float32)
    tok[1, 0], step[1], length[1] = seq[8], 8, 1
    kept, _ = runner.decode(tok, step, kv, length)
    assert isinstance(kept, DeviceLogits) and kept._host is None
    row = kept[1, 0]
    first = sample_token(row, position=9)
    assert kept._host is None          # a greedy draw fetched nothing
    host = np.asarray(kept)
    assert host.shape == (slots, 1, 97) and np.asarray(kept) is host
    assert (kept.first_maximum == host[:, 0].argmax(-1)).all()
    assert first == int(host[1, 0].argmax())
    assert (np.asarray(row) == host[1, 0]).all()
    assert sample_token(row, position=9, seed=3, top_k=4) == \
        sample_token(host[1, 0], position=9, seed=3, top_k=4)
    want = _logits(weights, seq)[8]
    np.testing.assert_allclose(np.asarray(row), want, atol=TOL, rtol=0)

    b = GenerateBatcher(runner, clock=_Clock(), max_lanes=LANES)
    greedy = b.submit(_prompt(5, salt=9), max_tokens=3)
    _drive(b, [greedy])
    mixed = [b.submit(_prompt(4, salt=10), max_tokens=4, top_k=4, seed=2),
             b.submit(_prompt(5, salt=9), max_tokens=3)]
    _drive(b, mixed)
    assert mixed[1].result() == greedy.result()
    again = b.submit(_prompt(4, salt=10), max_tokens=4, top_k=4, seed=2)
    _drive(b, [again])
    assert again.result() == mixed[0].result()
