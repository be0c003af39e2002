"""The routed-experts / sliding-window decoder (``mellum``) on the
serving path.

A tiny model (one period of ``[sliding, sliding, sliding, full]``,
hidden 64, 4 query heads over 2 key/value heads of 16, per-head QK-norm,
8 experts of 32 of which a token takes 2, window 8 on a ring of 12, a
plain and a YaRN rotary table, vocabulary 97, untied head) is held
against the plain reference (``benchmark/reference_mellum2.py``:
float32, whole masks, every expert over every token, no cache, no ring)
on seeded weights, at every place where a ring of columns or a routed
token can go wrong that a flat table and a dense MLP forgive: a prompt
that wraps the ring, a chunk that straddles the wrap, a decode step past
a wrap, a lane a longer request used, padded rows, every token on one
expert, a stream replayed from its prompt and prefix.  Logits are
compared, not tokens; on the CPU in float32 the program and the
reference differ by rounding only, so every tolerance on logits of size
0.4 is 3e-5 (a path that dropped a term would miss by 1e-3 and more).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, obs, profiler
from mxtpu import symbol as sym_mod
from mxtpu.models.hybrid import (GatedMLP, GroupedQueryAttention,
                                 HybridDecoderModel, SparseMLP)
from mxtpu.ndarray import rnn_impl
from mxtpu.parallel import moe
from mxtpu.serving import DeviceLogits, GenerateBatcher, GenerateRunner

from benchmark import reference_mellum2 as ref
from benchmark import weights_mellum2

YARN = {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
        "original_max_position_embeddings": 16, "beta_fast": 4,
        "beta_slow": 1, "attention_factor": 1.1386}
PLAIN = {"rope_type": "default", "rope_theta": 10000}
CFG = {"model_type": "mellum", "vocab_size": 97, "hidden_size": 64,
       "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
       "num_hidden_layers": 4,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
       "mlp_layer_types": ["sparse"] * 4, "moe_intermediate_size": 32,
       "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
       "sliding_window": 8, "use_sliding_window": True,
       "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
       "attention_bias": False,
       "rope_parameters": {"full_attention": YARN,
                           "sliding_attention": PLAIN}}
LANES, CAP, BUCKET, RING = 3, 48, 4, 12
TOL = 3e-5
SEED = 2 ** 31 + 7


def _built(cfg, weights):
    n = HybridDecoderModel.from_config(cfg)
    n.initialize()
    leaves = n.named_leaves()
    assert {k: tuple(p.shape) for k, p in leaves.items()} == \
        weights_mellum2.leaf_shapes(cfg)
    for name, p in leaves.items():
        p.set_data(nd.array(np.asarray(weights[name].astype(jnp.float32))))
    return n


@pytest.fixture(scope="module")
def weights():
    return weights_mellum2.make(CFG, SEED)


@pytest.fixture(scope="module")
def net(weights):
    return _built(CFG, weights)


def _export(net):
    out = net(*[sym_mod.var(f"data{i}") for i in range(5)])
    params = {p.name: p.data() for p in net.collect_params().values()}
    return sym_mod.Group(list(out)), params


@pytest.fixture(scope="module")
def runner(net):
    symbol, params = _export(net)
    return GenerateRunner(
        symbol, params, net.state_spec(LANES, CAP, max_chunk=BUCKET),
        prompt_buckets=(BUCKET,), cache=None, counters=net.counter_spec())


def _logits(weights, tokens, cfg=CFG, cast=None):
    return np.asarray(ref.forward(cfg, weights, np.asarray(tokens)[None],
                                  cast=cast))[0]


def _prompt(n, salt=0):
    return np.random.default_rng(300 + salt).integers(1, 97, n).tolist()


def _eager(net, seq, n):
    """The model's outputs for ``seq`` from fresh tables wide enough
    for it, ``n`` of the tokens valid: (logits at n - 1, experts
    touched)."""
    tables = [nd.array(np.zeros(s, np.float32)) for _, s, _, _ in
              net.state_spec(1, 64, max_chunk=len(seq))]
    out = net(nd.array(np.asarray(seq, np.float32)[None]),
              nd.array(np.zeros(1)), nd.array(np.array([float(n)])), *tables)
    assert len(out) == 4
    return out[0].asnumpy()[0, 0], int(out[3].asnumpy()[0])


def _prefill_rows(runner, kv, rows, bucket=BUCKET):
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
@pytest.mark.parametrize("n", [1, 5, 8, 9, 30])
def test_full_forward_matches_the_reference(net, weights, n):
    """Each length's last position: 1 is the one-step form, 8 fills the
    window, 9 is the first query that must not see position 0, 30 is
    past three windows."""
    seq = _prompt(30)
    want = _logits(weights, seq)
    assert np.abs(want).max() > 0.1
    got, touched = _eager(net, seq, n)
    np.testing.assert_allclose(got, want[n - 1], atol=TOL, rtol=0)
    assert 4 * min(2, n) <= touched <= 4 * min(8, 2 * n)
    # the window is there: without it the logits past it are others
    if n > 8:
        assert np.abs(_logits(weights, seq, cast="window_off")[n - 1]
                      - want[n - 1]).max() > 1e-3


def test_state_spec_declares_two_position_tables_and_no_other(net):
    kv, ring = net.state_spec(5, 40, kv_dtype="bfloat16", max_chunk=4)
    assert kv == ("kv", (1, 2, 5, 2, 40, 16), 2, "bfloat16")
    assert ring == ("kv_win", (3, 2, 5, 2, 12, 16), 2, "bfloat16")
    with pytest.raises(mx.base.MXNetError, match="max_chunk"):
        net.state_spec(5, 40)
    assert net.counter_spec() == {"device": ("moe_experts_touched",),
                                  "per_token": {"moe_assignments": 2}}
    with pytest.raises(mx.base.MXNetError, match="state tables"):
        net(nd.array(np.ones((1, 2))), nd.array(np.zeros(1)),
            nd.array(np.ones(1)), nd.array(np.zeros((1, 2, 1, 2, 8, 16))))


def test_the_published_model_at_its_widths():
    """The published configuration's 12-layer stage, built symbolically
    (no weight is made), declares the tables the issue's arithmetic
    counts: a ring of 1,280 columns for the nine sliding layers."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "mellum2_12b_a2_5b.json")) as f:
        cfg = json.load(f)
    stage = HybridDecoderModel.from_config(cfg)
    assert stage.layer_types == tuple(
        (["sliding_attention"] * 3 + ["full_attention"]) * 3)
    kv, ring = stage.state_spec(23, 8448, kv_dtype="bfloat16", max_chunk=256)
    assert kv[1] == (3, 2, 23, 4, 8448, 128)
    assert ring[:2] == ("kv_win", (9, 2, 23, 4, 1280, 128))
    shapes = {k: tuple(p.shape) for k, p in stage.named_leaves().items()}
    assert shapes == weights_mellum2.leaf_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 5_465_959_680
    assert shapes["l11.w_in"] == (64, 2304, 1792)
    assert stage.counter_spec()["per_token"] == {"moe_assignments": 8}
    sliding, full = stage.layers[0].mixer, stage.layers[3].mixer
    assert sliding._read == {"window": 1024}
    assert sliding._write == {"ring": True}
    assert full._read == {} and full._write == {}
    assert sliding._rope["scale"] == 1.0
    assert full._rope["scale"] == 1.2772588722239782
    assert len(full._rope["inv_freq"]) == 64


@pytest.mark.parametrize("bad,names", [
    ({"layer_types": ["sliding_attention", "mamba"]}, "mamba"),
    ({"mlp_layer_types": ["sparse", "dense"]}, "dense feed-forward"),
    ({"attention_bias": True}, "attention biases"),
    ({"use_sliding_window": False}, "switched off"),
    ({"rope_parameters": {"full_attention": {"rope_type": "llama3",
                                             "rope_theta": 1e4}}}, "llama3")])
def test_from_config_names_what_it_cannot_build(bad, names):
    with pytest.raises(mx.base.MXNetError, match=names):
        HybridDecoderModel.from_config(dict(CFG, **bad))


def test_a_model_of_other_kinds_keeps_its_tables_and_its_mlp():
    """The options default off: a model with no sliding layer and no
    expert declares ``kv`` beside its recurrent tables, builds the dense
    MLP, and gives its attention ops no new attribute."""
    plain = HybridDecoderModel(97, 64, 128, ["mamba", "attention"], 4, 2,
                               ssm_heads=4, ssm_head_dim=16, ssm_state=8)
    assert [t[0] for t in plain.state_spec(2, 16)] == ["kv", "ssm", "conv"]
    assert plain.counter_spec() == {"device": (), "per_token": {}}
    assert isinstance(plain.layers[1].mlp, GatedMLP)
    mixer = plain.layers[1].mixer
    assert isinstance(mixer, GroupedQueryAttention)
    assert (mixer._rope, mixer._read, mixer._write) == (None, {}, {})
    out = plain(*[sym_mod.var(f"data{i}") for i in range(6)])
    assert len(out) == 4
    text = sym_mod.Group(list(out)).tojson()
    assert "rope" not in text and "routed_experts" not in text
    with pytest.raises(mx.base.MXNetError, match="sliding_window"):
        HybridDecoderModel(97, 64, 32, ["sliding_attention"], 4, 2)
    with pytest.raises(mx.base.MXNetError, match="QK-norm"):
        GroupedQueryAttention(64, 4, 2, 16, -1.0, qk_norm="pairs")


def test_qk_norm_is_over_each_head_and_under_its_scope(net, weights):
    """A weight of the key norm scaled by 3 moves the logits in the
    program as in the reference (the norm is there, one weight of 16
    for every head), and the graph carries the scope."""
    scaled = dict(weights)
    gamma = np.ones(16, np.float32)
    gamma[::2] = 3.0
    scaled["l3.k_norm"] = jnp.asarray(gamma, jnp.bfloat16)
    other = _built(CFG, scaled)
    seq = _prompt(11, salt=5)
    want = _logits(scaled, seq)[10]
    assert np.abs(want - _logits(weights, seq)[10]).max() > 1e-3
    np.testing.assert_allclose(_eager(other, seq, 11)[0], want, atol=TOL,
                               rtol=0)
    symbol, _ = _export(net)
    assert '"scope": "qk_norm"' in symbol.tojson()


# ----------------------------------------------------------- the runner
def test_tables_follow_the_spec(net):
    symbol, params = _export(net)
    r = GenerateRunner(
        symbol, params,
        net.state_spec(LANES, CAP, kv_dtype="bfloat16", max_chunk=BUCKET),
        prompt_buckets=(BUCKET,), cache=None, counters=net.counter_spec())
    kv, ring = tables = r.new_cache()
    assert kv.dtype == ring.dtype == jnp.bfloat16
    assert ring.shape == (3, 2, LANES + 1, 2, RING, 16)
    assert [t.name for t in r.state_spec] == ["kv", "kv_win"]
    # the full table's capacity is the bound the batcher evicts on
    assert r.max_len == CAP
    series = obs.snapshot()["mxtpu_gen_state_bytes"]["series"]
    got = {v["labels"]["table"]: int(v["value"]) for v in series}
    assert got["kv_win"] == r.held_bytes(tables)["kv_win"] == ring.nbytes
    (first,), tables = _prefill_rows(r, tables, [(0, _prompt(5))])
    assert tables[1].dtype == jnp.bfloat16 and np.isfinite(first).all()


@pytest.mark.parametrize("plen,steps", [
    (1, 6),      # the one-step form from a fresh lane
    (7, 8),      # decode crosses the window and the ring's first wrap
    (10, 4),     # the third chunk (positions 8-11, 2 valid) ends at the wrap
    (22, 5),     # chunk 3 (12-15) starts the second turn; decode wraps again
    (31, 9)])    # two wraps in the prompt, a third in decode
def test_prefill_then_decode_equals_the_full_forward(runner, weights, plen,
                                                     steps):
    seq = _prompt(plen + steps, salt=plen)
    want = _logits(weights, seq)
    (first,), kv = _prefill_rows(runner, runner.new_cache(),
                                 [(1, seq[:plen])])
    np.testing.assert_allclose(first, want[plen - 1], atol=TOL, rtol=0)
    for at in range(plen, len(seq)):
        got, kv = _decode(runner, kv, {1: (seq[at], at)})
        np.testing.assert_allclose(got[1], want[at], atol=TOL, rtol=0)


def test_a_chunk_that_straddles_the_wrap(net, weights):
    """Chunks of 8 on a ring of 16 from position 0 never straddle; a
    replayed stream does: 5 tokens, then 8 from position 5 (columns
    5-12), then 8 from 13 (columns 13, 14, 15, 0, ... 4: over the
    wrap), each in one call."""
    symbol, params = _export(net)
    r = GenerateRunner(symbol, params, net.state_spec(2, CAP, max_chunk=8),
                       prompt_buckets=(8,), cache=None,
                       counters=net.counter_spec())
    assert r.state_spec[1].shape[4] == 16
    seq = _prompt(24, salt=9)
    want = _logits(weights, seq)
    kv = r.new_cache()
    for base, valid in ((0, 5), (5, 8), (13, 8)):
        tok = np.zeros((1, 8), np.float32)
        tok[0, :valid] = seq[base:base + valid]
        logits, kv = r.prefill(tok, np.array([base], np.float32),
                               np.array([0], np.float32), kv,
                               np.array([valid], np.float32))
        np.testing.assert_allclose(np.asarray(logits)[0, 0],
                                   want[base + valid - 1], atol=TOL, rtol=0)
    for at in range(21, 24):
        got, kv = _decode(r, kv, {0: (seq[at], at)})
        np.testing.assert_allclose(got[0], want[at], atol=TOL, rtol=0)


def test_a_rung_with_padding_rows_and_unequal_prompts(runner, weights):
    """Three prompts on the rung of four: one padding row (scratch slot,
    length 0), and the short rows finish chunks before the long one,
    whose ring wraps while theirs do not."""
    cut = [3, 26, 9]
    seqs = [_prompt(n + 2, salt=n) for n in cut]
    firsts, kv = _prefill_rows(
        runner, runner.new_cache(),
        [(lane, s[:n]) for lane, (s, n) in enumerate(zip(seqs, cut))])
    wants = [_logits(weights, s) for s in seqs]
    for got, want, n in zip(firsts, wants, cut):
        np.testing.assert_allclose(got, want[n - 1], atol=TOL, rtol=0)
    for k in range(2):
        got, kv = _decode(runner, kv, {lane: (s[n + k], n + k) for lane,
                                       (s, n) in enumerate(zip(seqs, cut))})
        for lane, (want, n) in enumerate(zip(wants, cut)):
            np.testing.assert_allclose(got[lane], want[n + k], atol=TOL,
                                       rtol=0)


def test_a_reused_lane_reads_nothing_stale_in_the_ring(runner, weights):
    """A request that filled the ring twice over, then a short one in
    the same lane: every column holds the first one's keys, and none of
    them may reach the second, before its first wrap or after."""
    long_seq, short = _prompt(30, salt=1), _prompt(16, salt=2)
    _, kv = _prefill_rows(runner, runner.new_cache(), [(2, long_seq[:26])])
    for at in range(26, 30):
        _, kv = _decode(runner, kv, {2: (long_seq[at], at)})
    assert (np.abs(np.asarray(kv[1][:, :, 2])).max(axis=(0, 1, 2, 4))
            > 0).all()
    want = _logits(weights, short)
    (first,), kv = _prefill_rows(runner, kv, [(2, short[:6])])
    np.testing.assert_allclose(first, want[5], atol=TOL, rtol=0)
    for at in range(6, 16):
        got, kv = _decode(runner, kv, {2: (short[at], at)})
        np.testing.assert_allclose(got[2], want[at], atol=TOL, rtol=0)


def test_a_call_counts_its_assignments_and_the_experts_it_touched(runner):
    """``gen/prefill/call`` and ``gen/decode`` carry ``moe_assignments``
    (valid tokens x 2, known on the host) and ``moe_experts_touched``
    (counted on the device, summed over the four layers, brought over
    behind the token ids), and both add to the program's counters."""
    def total(name):
        series = obs.snapshot().get(name, {}).get("series", [])
        return sum(int(v["value"]) for v in series)

    before = {n: total(n) for n in ("mxtpu_moe_assignments_total",
                                    "mxtpu_moe_experts_touched_total")}
    profiler.set_state("run")
    try:
        seq = _prompt(9, salt=6)
        (_,), kv = _prefill_rows(runner, runner.new_cache(), [(0, seq[:7])])
        _, kv = _decode(runner, kv, {0: (seq[7], 7)})
        # nothing valid: nothing routed, nothing touched
        _, kv = _decode(runner, kv, {})
        events = profiler.events()
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
    calls = [e["args"] for e in events
             if e["name"] in (obs.SPAN_PREFILL_CALL, obs.SPAN_DECODE)]
    assert [c["moe_assignments"] for c in calls] == [8, 6, 2, 0]
    touched = [c["moe_experts_touched"] for c in calls]
    assert touched[3] == 0 and touched[2] == 4 * 2
    assert 4 * 2 <= touched[0] <= 4 * 8 and 4 * 2 <= touched[1] <= 4 * 6
    assert total("mxtpu_moe_assignments_total") \
        - before["mxtpu_moe_assignments_total"] == 16
    assert total("mxtpu_moe_experts_touched_total") \
        - before["mxtpu_moe_experts_touched_total"] == sum(touched)


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


def _drive(b, reqs, n=300):
    for _ in range(n):
        b.step()
        if all(r.done() for r in reqs):
            return
    raise AssertionError("requests not done")


def test_batcher_streams_equal_the_references_greedy_streams(runner,
                                                             weights):
    """Five requests over three lanes: lanes are reused, a prompt of 27
    prefills in seven chunks and wraps its ring twice while its
    neighbours wait, and every stream is the reference's greedy stream
    token for token."""
    b = GenerateBatcher(runner, clock=_Clock(), max_lanes=LANES)
    prompts = [_prompt(n, salt=n) for n in (3, 27, 6, 13, 2)]
    lens = [5, 4, 7, 3, 6]
    reqs = [b.submit(p, max_tokens=n) for p, n in zip(prompts, lens)]
    _drive(b, reqs)
    assert b.joins == 5
    for r, p, n in zip(reqs, prompts, lens):
        assert r.result() == _greedy(weights, p, n)


def test_replay_after_a_steal_is_token_for_token(runner, weights):
    """A second attempt is given the prompt and the tokens already
    streamed, rebuilds both tables from them (19 + 4 tokens: six chunks,
    the ring wrapped once), and continues the uninterrupted stream."""
    prompt = _prompt(19, salt=11)
    whole = _greedy(weights, prompt, 9)
    b = GenerateBatcher(runner, clock=_Clock(), max_lanes=LANES)
    first = b.submit(prompt, max_tokens=9)
    _drive(b, [first])
    assert first.result() == whole
    again = b.submit(prompt, max_tokens=9, prefix=whole[:4])
    _drive(b, [again])
    assert again.result() == whole


def test_the_full_tables_capacity_alone_ends_a_stream(runner):
    """A stream runs far past the ring (12 columns) and ends where the
    full-attention table does (48 positions)."""
    b = GenerateBatcher(runner, clock=_Clock(), max_lanes=LANES)
    req = b.submit(_prompt(5, salt=12), max_tokens=100)
    _drive(b, [req])
    assert req.finish_reason == "length"
    assert 5 + len(req.result()) == CAP + 1 and CAP > 3 * RING


# -------------------------------------------------------------- the ops
def _yarn_by_hand():
    """dim(r) = 16 ln(16 / (2 pi r)) / (2 ln 10000): dim(4) < 0 so lo =
    0, dim(1) = 0.81 so hi = 1: the ramp is 0 at j = 0 and 1 after."""
    e = 10000.0 ** (-2.0 * np.arange(8) / 16)
    return np.concatenate([e[:1], e[1:] / 4.0])


def test_rope_frequencies_against_numbers_worked_out_here():
    np.testing.assert_allclose(rnn_impl.rope_frequencies(16, 10000),
                               10000.0 ** (-np.arange(8) / 8.0), rtol=1e-12)
    np.testing.assert_allclose(rnn_impl.rope_frequencies(16, 10000, YARN),
                               _yarn_by_hand(), rtol=1e-12)
    # the published full-attention table: theta 500000, heads of 128,
    # factor 16 over 8192: dim(32) = 18.08, dim(1) = 34.98
    published = {"factor": 16, "original_max_position_embeddings": 8192,
                 "beta_fast": 32, "beta_slow": 1}
    f = rnn_impl.rope_frequencies(128, 500000, published)
    e = 500000.0 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(f[:19], e[:19], rtol=1e-12)
    np.testing.assert_allclose(f[35:], e[35:] / 16.0, rtol=1e-12)
    mid = (30 - 18) / (35 - 18)
    np.testing.assert_allclose(f[30], e[30] / 16 * mid + e[30] * (1 - mid),
                               rtol=1e-12)
    # the reference's own table, written apart, is the same
    np.testing.assert_allclose(
        f, ref.rotary_table({"head_dim": 128, "rope_parameters": {
            "full_attention": dict(published, rope_type="yarn",
                                   rope_theta=500000,
                                   attention_factor=1.0)}},
            "full_attention")[0], rtol=1e-12)


@pytest.mark.parametrize("table,scale", [(None, 1.0), (YARN, 1.1386)],
                         ids=["plain", "yarn"])
def test_rotation_at_a_step_equals_rotation_of_the_whole(table, scale):
    """Rows rotated in pieces at their own ``step`` are the rows of the
    whole sequence rotated at once, and both are the pairwise rotation
    written out here."""
    freq = rnn_impl.rope_frequencies(16, 10000, table)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 3, 11, 16)),
                    jnp.float32)
    kw = {"inv_freq": tuple(freq), "scale": scale}
    whole = np.asarray(rnn_impl._rope_op(x, jnp.zeros(2), **kw))
    at = jnp.asarray([4.0, 7.0])
    parts = np.asarray(rnn_impl._rope_op(
        jnp.stack([x[0, :, 4:8], x[1, :, 7:11]]), at, **kw))
    np.testing.assert_allclose(parts[0], whole[0, :, 4:8], atol=1e-6)
    np.testing.assert_allclose(parts[1], whole[1, :, 7:11], atol=1e-6)
    want = np.zeros_like(whole)
    xs = np.asarray(x, np.float64)
    for p in range(11):
        c, s = np.cos(p * freq) * scale, np.sin(p * freq) * scale
        want[:, :, p, :8] = xs[:, :, p, :8] * c - xs[:, :, p, 8:] * s
        want[:, :, p, 8:] = xs[:, :, p, 8:] * c + xs[:, :, p, :8] * s
    np.testing.assert_allclose(whole, want, atol=1e-5)
    with pytest.raises(mx.base.MXNetError, match="frequencies"):
        rnn_impl._rope_op(x, at, inv_freq=(1.0, 0.5))


def _flat_attention(q, k, v, window):
    """Causal attention over whole sequences, a window's worth a query:
    loops, float64."""
    B, H, T, D = q.shape
    g = H // k.shape[1]
    out = np.zeros((B, H, T, D))
    for b in range(B):
        for h in range(H):
            for t in range(T):
                lo = max(0, t - window + 1) if window else 0
                s = k[b, h // g, lo:t + 1] @ q[b, h, t] / np.sqrt(D)
                p = np.exp(s - s.max())
                out[b, h, t] = p / p.sum() @ v[b, h // g, lo:t + 1]
    return out


@pytest.mark.parametrize("ring", [False, True], ids=["flat", "ring"])
def test_windowed_attention_over_a_table_and_over_a_ring(ring):
    """37 positions in chunks of 1 to 5 through ``kv_cache_write`` and
    ``cached_attention`` with a window of 8: on a flat table of 40 and
    on a ring of 12, which wraps three times and is straddled by
    several chunks, against the loops above.  The ring starts full of
    another request's numbers."""
    rng = np.random.default_rng(2)
    B, H, Hk, T, D, W = 2, 4, 2, 37, 8, 8
    q = rng.normal(size=(B, H, T, D))
    k, v = rng.normal(size=(2, B, Hk, T, D))
    want = _flat_attention(q, k, v, W)
    L = 12 if ring else 40
    table = jnp.asarray(rng.normal(size=(1, 2, B, Hk, L, D)) * 9, jnp.float32)
    at, got = 0, []
    for n in [1, 5, 4, 5, 3, 5, 5, 2, 1, 1, 5]:
        step = jnp.full((B,), float(at))
        cut = lambda z: jnp.asarray(z[:, :, at:at + n], jnp.float32)
        for plane, new in enumerate((k, v)):
            table = rnn_impl._kv_cache_write_op(table, cut(new), step,
                                                layer=0, plane=plane,
                                                ring=ring)
        got.append(np.asarray(rnn_impl._cached_attention_op(
            cut(q), table[0, 0], table[0, 1], step, window=W)))
        at += n
    assert at == T
    np.testing.assert_allclose(np.concatenate(got, axis=2), want, atol=1e-5)


def test_the_ring_refuses_what_it_cannot_hold():
    q = jnp.zeros((1, 2, 5, 8))
    ring = jnp.zeros((1, 2, 12, 8))
    step = jnp.zeros(1)
    with pytest.raises(mx.base.MXNetError, match="ring of 12"):
        rnn_impl._cached_attention_op(q, ring, ring, step, window=9)
    rnn_impl._cached_attention_op(q, ring, ring, step, window=8)
    with pytest.raises(mx.base.MXNetError, match="ring"):
        rnn_impl._kv_cache_write_op(jnp.zeros((1, 2, 1, 2, 12, 8)),
                                    jnp.zeros((1, 2, 13, 8)), step,
                                    ring=True)


def _old_cached_attention(q, k_cache, v_cache, step):
    """``cached_attention`` as it was before it took a window (grouped
    form), kept as the reference of its default path."""
    B, H, T, D = q.shape
    Hk, L = k_cache.shape[1], k_cache.shape[2]
    s = jnp.asarray(step).astype(jnp.int32)
    pos_q = s[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    mask = jnp.arange(L, dtype=jnp.int32)[None, None, :] <= pos_q[:, :, None]
    qg = q.astype(jnp.float32).reshape(B, Hk, H // Hk, T, D)
    scores = jnp.einsum("bkgtd,bkld->bkgtl", qg, k_cache.astype(jnp.float32),
                        preferred_element_type=jnp.float32) \
        * (1.0 / float(np.sqrt(D)))
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    out = jnp.einsum("bkgtl,bkld->bkgtd", jax.nn.softmax(scores, axis=-1),
                     v_cache.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, T, D).astype(q.dtype)


@pytest.mark.parametrize("window", [0, 14],
                         ids=["default", "widest-the-table-holds"])
def test_the_default_path_is_the_op_it_was(window):
    """With no window (or the widest a table of 16 holds for 3 new
    tokens, which no query here can feel: a table that holds every
    position is a ring no wrap has reached) the op's numbers are, bit
    for bit, those of the formulation it had, and the default path's
    program names no new scope."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 4, 3, 8)), jnp.float32)
    k, v = jnp.asarray(rng.normal(size=(2, 2, 2, 16, 8)), jnp.float32)
    step = jnp.asarray([5.0, 9.0])
    got = rnn_impl._cached_attention_op(q, k, v, step, window=window)
    assert (np.asarray(got) == np.asarray(
        _old_cached_attention(q, k, v, step))).all()
    from mxtpu import analysis
    text = analysis.lowered_text(rnn_impl._cached_attention_op, q, k, v, step)
    assert "cached_attention" in text and "window_attention" not in text
    table = jnp.asarray(rng.normal(size=(1, 2, 2, 2, 16, 8)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(2, 2, 3, 8)), jnp.float32)
    flat = rnn_impl._kv_cache_write_op(table, new, step, 0, 1)
    assert (np.asarray(flat[0, 1, 1, :, 9:12]) == np.asarray(new[1])).all()
    # a ring no wrap has reached is the flat table
    assert (np.asarray(rnn_impl._kv_cache_write_op(
        table, new, step, 0, 1, ring=True)) == np.asarray(flat)).all()


# ------------------------------------------------------ the routed layer
def _dense_experts(x, router, w_in, w_out, top_k, renormalise=True):
    """Every expert over every token, in float64, weighted by the
    router's renormalised top-k probabilities (0 elsewhere)."""
    x, router, w_in, w_out = (np.asarray(a, np.float64)
                              for a in (x, router, w_in, w_out))
    logits = x @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        chosen = np.argsort(-probs[t], kind="stable")[:top_k]
        total = probs[t, chosen].sum() if renormalise else 1.0
        for e in chosen:
            gate, up = np.split(x[t] @ w_in[e], 2)
            out[t] += probs[t, e] / total \
                * ((gate / (1 + np.exp(-gate)) * up) @ w_out[e])
    return out


def _experts(rng, T=24, D=16, E=8, F=12):
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return arr(T, D), arr(D, E), arr(E, D, 2 * F) * 0.3, arr(E, F, D) * 0.3


@pytest.mark.parametrize("case", ["uniform", "one-expert", "top-1",
                                  "not-renormalised"])
def test_routed_experts_equal_the_dense_sum_and_drop_nothing(case):
    """The grouped products give what every expert over every token
    gives, weighted by the router: under routing as it falls, with
    every token on the same two experts (their groups hold all 24
    tokens, the other six none: nothing is dropped), with one expert a
    token, and with the weights left as the softmax gave them."""
    x, router, w_in, w_out = _experts(np.random.default_rng(4))
    top_k, renorm = 2, True
    if case == "one-expert":
        # experts 5 and 2 win every token by a mile
        router = router.at[:, 5].set(0).at[:, 2].set(0)
        x = x.at[:, 0].set(9.0)
        router = router.at[0, 5].set(3.0).at[0, 2].set(2.0)
    elif case == "top-1":
        top_k = 1
    elif case == "not-renormalised":
        renorm = False
    y, touched = moe.routed_experts(x, router, w_in, w_out, top_k=top_k,
                                    renormalise=renorm)
    want = _dense_experts(x, router, w_in, w_out, top_k, renorm)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    weights, experts = moe.topk_router(x, router, top_k, renormalise=renorm)
    if case == "one-expert":
        assert int(touched) == 2
        assert (np.asarray(experts) == np.array([5, 2])).all()
    else:
        assert int(touched) == len(np.unique(np.asarray(experts)))
    sums = np.asarray(weights).sum(-1)
    if renorm:
        np.testing.assert_allclose(sums, 1.0, atol=1e-6)
    else:
        assert (sums < 0.999).all()


@pytest.mark.parametrize("tokens,only", [(24, None), (5, None), (40, 3)],
                         ids=["24-tokens", "5-tokens", "40-on-one-expert"])
def test_the_chips_grouped_kernel_is_the_grouped_product(monkeypatch, tokens,
                                                         only):
    """On the TPU the experts' products are the megablox grouped-matmul
    kernel over tiles of 128 rows (here in the interpreter): the same
    numbers as ``ragged_dot`` gives, for row counts that are no whole
    tile, and with every row in one group."""
    from mxtpu import kernels
    x, router, w_in, w_out = _experts(np.random.default_rng(8), T=tokens)
    if only is not None:
        router = jnp.zeros_like(router).at[:, only].set(1.0)
        x = jnp.abs(x)
    want, touched = moe.routed_experts(x, router, w_in, w_out, top_k=2)
    monkeypatch.setattr(kernels, "pallas_enabled", lambda: True)
    got, again = moe.routed_experts(x, router, w_in, w_out, top_k=2)
    assert int(again) == int(touched) and np.abs(np.asarray(want)).max() > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    from mxtpu import analysis
    text = analysis.lowered_text(
        lambda *a: moe.routed_experts(*a, top_k=2)[0], x, router, w_in, w_out)
    assert "ragged" not in text


def test_padded_rows_change_nothing_and_touch_nothing():
    """``routed_experts`` as the graph calls it: rows (B, T, D) with
    ``length``; positions past a row's length come out zero, the valid
    ones are what they are alone, a call with nothing valid touches no
    expert, and a padded token whose expert no valid token chose does
    not touch it."""
    rng = np.random.default_rng(5)
    x, router, w_in, w_out = _experts(rng, T=12)
    op = mx.ndarray.nn_extra._routed_experts_op
    rows = x.reshape(2, 6, 16)
    y, touched = op(rows, router, w_in, w_out, jnp.asarray([6.0, 2.0]),
                    top_k=2)
    assert y.shape == (2, 6, 16) and touched.shape == (1,)
    alone, t_alone = moe.routed_experts(
        jnp.concatenate([x[:6], x[6:8]]), router, w_in, w_out, top_k=2)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(alone[:6]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(y[1, :2]), np.asarray(alone[6:]),
                               atol=1e-6)
    assert (np.asarray(y[1, 2:]) == 0).all()
    assert int(touched[0]) == int(t_alone)
    y, touched = op(rows, router, w_in, w_out, jnp.zeros(2), top_k=2)
    assert (np.asarray(y) == 0).all() and int(touched[0]) == 0
    # garbage in the padded positions is garbage nobody reads
    wild = rows.at[1, 2:].set(jnp.nan)
    y2, _ = op(wild, router, w_in, w_out, jnp.asarray([6.0, 2.0]), top_k=2)
    assert np.isfinite(np.asarray(y2)).all()


def test_the_sparse_block_is_the_references_layer(net, weights):
    """One ``SparseMLP`` with the model's own leaves against the
    reference's experts over every token."""
    block = net.layers[1].mlp
    assert isinstance(block, SparseMLP)
    x = np.random.default_rng(6).normal(size=(2, 7, 64)).astype(np.float32)
    y, touched = block(nd.array(x), nd.array(np.array([7.0, 7.0])))
    lw = {k: weights["l1." + k].astype(jnp.float32)
          for k in ("router", "w_in", "w_out")}
    want = np.asarray(ref.experts(None, jnp.asarray(x), lw, 2))
    np.testing.assert_allclose(y.asnumpy(), want, atol=1e-5)
    assert 2 <= int(touched.asnumpy()[0]) <= 8
