"""One device's share of an expert-parallel decoder (``afmoe``, arcee's
Trinity) on the serving path.

A tiny model (published layers' pattern cut to ``[sliding, sliding,
full, sliding]``, the first dense, hidden 64, 4 query heads over 2
key/value heads of 16, per-head QK-norm, a rotary table for the sliding
layers alone, gated attention, four norms a layer, 16 experts of 32
routed over by a sigmoid with a selection bias, 4 a token, of which
this device holds experts 4-7, a shared expert, window 8 on a ring of
12, vocabulary 97, untied head, the embedding times sqrt(64)) is held
against the plain reference (``benchmark/reference_trinity.py``:
float32, whole masks, every held expert over every token, no cache, no
ring) on seeded weights: a prompt fed in chunks, then decoding through
the cache and across a wrap of the ring.  The routed layer is held to
the equations alone: the shares of a layer add up to the whole layer,
the bias chooses and never weighs, the weights are renormalised over
all chosen experts before the share is taken, and a token whose chosen
experts are all held elsewhere gets the shared expert alone.  Logits
are compared, not tokens; on the CPU in float32 the program and the
reference differ by rounding only, so every tolerance on logits of size
0.5 is 3e-5 (a path that dropped a term would miss by 1e-3 and more).

The tiny Mellum and SDAR programs' lowered text is pinned to the
programs they were before the expert share was added: their routed
layers take none of its options, and their executables (and compile
cache keys) must not move.
"""
import hashlib
import re

import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu import nd, obs, profiler
from mxtpu import symbol as sym_mod
from mxtpu.base import MXNetError
from mxtpu.models.hybrid import (GatedMLP, GroupedQueryAttention,
                                 HybridDecoderModel, SparseMLP)
from mxtpu.parallel import moe
from mxtpu.serving import GenerateRunner

from benchmark import reference_trinity as ref
from benchmark import weights_trinity

CFG = {"model_type": "afmoe", "vocab_size": 97, "hidden_size": 64,
       "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
       "num_hidden_layers": 4,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention"],
       "num_dense_layers": 1, "intermediate_size": 96,
       "moe_intermediate_size": 32, "num_experts": 4,
       "expert_share": {"of": 16, "first": 4}, "num_experts_per_tok": 4,
       "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
       "route_scale": 2.448, "n_group": 1, "topk_group": 1,
       "sliding_window": 8, "rope_theta": 10000, "rope_scaling": None,
       "rms_norm_eps": 1e-5, "mup_enabled": True,
       "tie_word_embeddings": False}
LANES, CAP, BUCKET, RING = 3, 48, 4, 12
TOL = 3e-5
SEED = 2 ** 31 + 41


def _built(cfg, weights):
    n = HybridDecoderModel.from_config(cfg)
    n.initialize()
    leaves = n.named_leaves()
    assert {k: tuple(p.shape) for k, p in leaves.items()} == \
        weights_trinity.leaf_shapes(cfg)
    for name, p in leaves.items():
        p.set_data(nd.array(np.asarray(weights[name].astype(jnp.float32))))
    return n


@pytest.fixture(scope="module")
def weights():
    return weights_trinity.make(CFG, SEED)


@pytest.fixture(scope="module")
def net(weights):
    return _built(CFG, weights)


@pytest.fixture(scope="module")
def runner(net):
    out = net(*[sym_mod.var(f"data{i}") for i in range(5)])
    params = {p.name: p.data() for p in net.collect_params().values()}
    return GenerateRunner(
        sym_mod.Group(list(out)), params,
        net.state_spec(LANES, CAP, max_chunk=BUCKET),
        prompt_buckets=(BUCKET,), cache=None, counters=net.counter_spec())


def _logits(weights, tokens, cast=None):
    t = np.asarray(tokens)
    padded = np.zeros(-(-len(t) // 64) * 64, np.int64)
    padded[:len(t)] = t
    return np.asarray(ref.forward(CFG, weights, padded[None],
                                  cast=cast))[0, :len(t)]


def _prompt(n, salt=0):
    return np.random.default_rng(500 + salt).integers(1, 97, n).tolist()


# -- the model as built --------------------------------------------------
def test_the_model_is_built_as_the_configuration_says(net):
    layers = list(net.layers)
    assert isinstance(layers[0].mlp, GatedMLP)
    assert all(isinstance(layer.mlp, SparseMLP) for layer in layers[1:])
    kinds = [layer.mixer for layer in layers]
    assert all(isinstance(m, GroupedQueryAttention) and m._gated
               for m in kinds)
    # NoPE on the full layer: only the sliding ones rotate
    assert [m._rope is not None for m in kinds] == [True, True, False, True]
    assert [m._read.get("window", 0) for m in kinds] == [8, 8, 0, 8]
    assert net._embed_scale == pytest.approx(8.0)
    mlp = layers[1].mlp
    assert mlp.router.shape == (64, 16) and mlp.bias.shape == (16,)
    assert mlp.w_in.shape == (4, 64, 64) and mlp.w_out.shape == (4, 32, 64)
    assert mlp.shared_in.shape == (64, 64)
    assert mlp._attrs == {"top_k": 4, "norm_topk": True, "score": "sigmoid",
                          "route_scale": 2.448, "held_first": 4,
                          "held_count": 4, "bias": True, "shared": True}
    assert net.counter_spec() == {
        "device": ("moe_experts_touched", "moe_assignments_held"),
        "per_token": {"moe_assignments": 4}}
    spec = {n: s for n, s, _, _ in net.state_spec(LANES, CAP,
                                                  max_chunk=BUCKET)}
    assert spec == {"kv": (1, 2, LANES, 2, CAP, 16),
                    "kv_win": (3, 2, LANES, 2, RING, 16)}


@pytest.mark.parametrize("bad", [{"n_group": 2}, {"score_func": "softmax2"},
                                 {"expert_share": {"of": 16, "first": 13}}])
def test_from_config_refuses_what_it_cannot_serve(bad):
    with pytest.raises(MXNetError):
        HybridDecoderModel.from_config(dict(CFG, **bad))


# -- (a) the served path against the reference -------------------------
def test_prefill_then_decode_across_a_ring_wrap_matches_the_reference(
        runner, weights):
    """A prompt of 14 in chunks of 4 (it wraps the ring of 12), then 10
    decode steps on lane 1 beside an idle lane: every step's logits are
    the reference's at that position of the whole sequence."""
    prompt = _prompt(14)
    kv = runner.new_cache()
    lane = 1
    for base in range(0, len(prompt), BUCKET):
        chunk = prompt[base:base + BUCKET]
        tok = np.zeros((1, BUCKET), np.float32)
        tok[0, :len(chunk)] = chunk
        logits, kv = runner.prefill(tok, np.array([base], np.float32),
                                    np.array([lane], np.float32), kv,
                                    np.array([len(chunk)], np.float32))
    seq = list(prompt)
    got = [np.asarray(logits)[0, 0]]
    for _ in range(10):
        seq.append(int(np.argmax(got[-1])))
        tok = np.zeros((runner.max_lanes + 1, 1), np.float32)
        step = np.zeros(runner.max_lanes + 1, np.float32)
        on = np.zeros(runner.max_lanes + 1, np.float32)
        tok[lane, 0], step[lane], on[lane] = seq[-1], len(seq) - 1, 1
        logits, kv = runner.decode(tok, step, kv, on)
        got.append(np.asarray(logits)[lane, 0])
    want = _logits(weights, seq)[len(prompt) - 1:]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.stack(got), want, atol=TOL, rtol=TOL)


def test_the_eager_graph_counts_held_experts_and_their_rows(net, weights):
    """One forward of 20 tokens: the graph's counts are the held experts
    given a token and the assignments that landed on them, summed over
    the three routed layers, as the reference's routing says."""
    seq = _prompt(20, salt=1)
    tables = [nd.array(np.zeros(s, np.float32)) for _, s, _, _ in
              net.state_spec(1, 32, max_chunk=20)]
    out = net(nd.array(np.asarray(seq, np.float32)[None]),
              nd.array(np.zeros(1)), nd.array(np.array([20.0])), *tables)
    touched, held = (int(c) for c in out[-1].asnumpy())
    x = ref._embed(weights["embed"], jnp.asarray([seq]), scale=8.0)
    static = ref._static(CFG)
    want_touched = want_held = 0
    for i, kind in enumerate(CFG["layer_types"]):
        lw = ref._layer_leaves(weights, i)
        if "router" in lw:
            lw32 = {k: v.astype(jnp.float32) for k, v in lw.items()}
            eps = CFG["rms_norm_eps"]
            a = ref.attention(None, ref.rms_norm(x, lw32["norm1"], eps),
                              lw32, CFG, kind)
            h = ref.rms_norm(x + ref.rms_norm(a, lw32["post_norm1"], eps),
                             lw32["norm2"], eps)
            chosen = np.asarray(ref.routing(None, h, lw32, CFG)[1])
            mine = chosen[(chosen >= 4) & (chosen < 8)]
            want_held += mine.size
            want_touched += len(set(mine.tolist()))
        x = ref._layer(x, lw, kind=kind, cfg_json=static, cast=None)
    assert (touched, held) == (want_touched, want_held)
    assert 0 < held < 20 * 4 * 3


# -- (b) the shares add up to the whole layer ----------------------------
def _layer_leaves(seed=0, d=48, f=24, e=16, shared=40):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(0, 0.3, s), jnp.float32)
    return {"x": n(37, d) * 3, "router": n(d, e), "bias": n(e) * 0.2,
            "w_in": n(e, d, 2 * f), "w_out": n(e, f, d),
            "shared": (n(d, 2 * shared), n(shared, d))}


def test_the_shares_routed_parts_and_the_shared_expert_add_up_to_the_layer():
    """Eight devices of two experts each: their routed parts, plus the
    shared expert counted once, are the uncut layer's output; their
    counts add up to its assignments and its touched experts."""
    L = _layer_leaves()
    valid = jnp.arange(37) < 33
    kw = dict(top_k=4, score="sigmoid", bias=L["bias"], scale=2.448)
    whole, touched = moe.routed_experts(L["x"], L["router"], L["w_in"],
                                        L["w_out"], valid,
                                        shared=L["shared"], **kw)
    parts, counts = [], []
    for r in range(8):
        y, c = moe.routed_experts(
            L["x"], L["router"], L["w_in"][2 * r:2 * r + 2],
            L["w_out"][2 * r:2 * r + 2], valid, held=(2 * r, 2), **kw)
        parts.append(y)
        counts.append(np.asarray(c))
    shared = jnp.where(valid[:, None], moe._swiglu(L["x"], *L["shared"]), 0)
    np.testing.assert_allclose(
        np.asarray(sum(parts) + moe._swiglu(L["x"], *L["shared"])),
        np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(shared)).max() > 0.1
    counts = np.stack(counts)
    assert counts[:, 1].sum() == 33 * 4
    assert counts[:, 0].sum() == int(touched)


def test_a_share_of_the_whole_layer_is_the_whole_layer():
    """``held`` over every expert is the layer without a share, counts
    beside."""
    L = _layer_leaves(1)
    kw = dict(top_k=4, score="sigmoid", bias=L["bias"], scale=2.448,
              shared=L["shared"])
    a, t = moe.routed_experts(L["x"], L["router"], L["w_in"], L["w_out"],
                              **kw)
    b, c = moe.routed_experts(L["x"], L["router"], L["w_in"], L["w_out"],
                              held=(0, 16), **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(c).tolist() == [int(t), 37 * 4]


# -- (c) the router -------------------------------------------------------
def test_the_bias_chooses_and_never_weighs_and_the_norm_runs_over_all_k():
    """Against the equations written out: ``s = sigmoid(x W)``, the four
    largest of ``s + b`` chosen, each weighing ``2.448 s / (sum of the
    chosen s)`` — and a bias that puts expert 3 first for every token
    gives it its own score's weight, not its biased one."""
    L = _layer_leaves(2)
    x, router = np.asarray(L["x"], np.float64), np.asarray(L["router"])
    s = 1.0 / (1.0 + np.exp(-(x @ router)))
    bias = np.zeros(16, np.float32)
    bias[3] = 10.0
    w, e = moe.topk_router(L["x"], L["router"], 4, score="sigmoid",
                           bias=jnp.asarray(bias), scale=2.448)
    w, e = np.asarray(w), np.asarray(e)
    assert (e[:, 0] == 3).all()
    order = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :4]
    np.testing.assert_array_equal(e, order)
    chosen = np.take_along_axis(s, order, axis=-1)
    np.testing.assert_allclose(w, 2.448 * chosen / chosen.sum(-1,
                                                              keepdims=True),
                               rtol=1e-5)
    assert w.sum(-1) == pytest.approx(2.448, rel=1e-5)
    # without renormalising: the scores themselves, scaled
    w2, _ = moe.topk_router(L["x"], L["router"], 4, score="sigmoid",
                            bias=jnp.asarray(bias), scale=2.0,
                            renormalise=False)
    np.testing.assert_allclose(np.asarray(w2), 2.0 * chosen, rtol=1e-5)


def test_a_token_whose_experts_are_all_held_elsewhere_gets_the_shared_part():
    """A bias that sends every token to experts 12-15 leaves a device
    that holds 0-3 nothing to route: its output is the shared expert's
    alone, it touches no expert and no assignment lands on it; the
    device that holds 12-15 weighs its four by the whole norm."""
    L = _layer_leaves(3)
    bias = jnp.asarray(np.r_[np.zeros(12), np.full(4, 50.0)], jnp.float32)
    kw = dict(top_k=4, score="sigmoid", bias=bias, scale=2.448,
              shared=L["shared"])
    y, c = moe.routed_experts(L["x"], L["router"], L["w_in"][:4],
                              L["w_out"][:4], held=(0, 4), **kw)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(moe._swiglu(L["x"], *L["shared"])),
                               rtol=1e-6, atol=1e-6)
    assert np.asarray(c).tolist() == [0, 0]
    y, c = moe.routed_experts(L["x"], L["router"], L["w_in"][12:],
                              L["w_out"][12:], held=(12, 4), **kw)
    whole, _ = moe.routed_experts(L["x"], L["router"], L["w_in"],
                                  L["w_out"], **kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(whole), rtol=1e-5,
                               atol=1e-5)
    assert np.asarray(c).tolist() == [4, 37 * 4]


def test_every_share_draws_its_selection_bias_from_the_same_strata(weights):
    """The seeded selection bias is the 16 mid-point quantiles of
    normal(0, BIAS_STD), dealt so that each share of 4 experts holds one
    value of every run of 4 neighbouring quantiles: no seed piles the
    bias, and with it the load, on one share."""
    from jax.scipy.special import ndtri
    q = np.asarray((weights_trinity.BIAS_STD * ndtri(
        (jnp.arange(16, dtype=jnp.float32) + 0.5) / 16)).astype(
            jnp.bfloat16).astype(jnp.float32))
    for i in (1, 2, 3):
        b = np.asarray(weights[f"l{i}.bias"].astype(jnp.float32))
        assert sorted(b.tolist()) == sorted(q.tolist())
        for share in b.reshape(4, 4):
            assert (np.searchsorted(q, np.sort(share)) // 4).tolist() \
                == [0, 1, 2, 3]
    other = weights_trinity.make(CFG, SEED + 1)
    assert not np.array_equal(np.asarray(other["l1.bias"]),
                              np.asarray(weights["l1.bias"]))


def test_the_reference_s_bias_weights_control_differs_from_it(weights):
    seq = _prompt(24, salt=2)
    exact = _logits(weights, seq)
    for cast in ("fp8", "bias_weights"):
        off = _logits(weights, seq, cast=cast)
        assert np.abs(off - exact).max() > 1e-4, cast


# -- (d) NoPE on the full layer, and the gate -----------------------------
def _attention_by_hand(x, lw, rotate, window):
    """One attention layer from the equations: per-head QK-norm, a
    rotation or none, causal softmax (within ``window``), the gate."""
    t = x.shape[0]
    heads = lambda z, n: z.reshape(t, n, 16).transpose(1, 0, 2)
    norm = lambda z, g: z / np.sqrt((z * z).mean(-1, keepdims=True)
                                    + 1e-5) * g
    q = norm(heads(x @ lw["q"].T, 4), lw["q_norm"])
    k = norm(heads(x @ lw["k"].T, 2), lw["k_norm"])
    v = heads(x @ lw["v"].T, 2)
    if rotate:
        f = 10000.0 ** (-np.arange(8) * 2 / 16)
        ang = np.arange(t)[:, None] * f[None, :]
        cos, sin = np.cos(np.c_[ang, ang]), np.sin(np.c_[ang, ang])
        turn = lambda z: np.concatenate([-z[..., 8:], z[..., :8]], -1)
        q, k = q * cos + turn(q) * sin, k * cos + turn(k) * sin
    k, v = np.repeat(k, 2, 0), np.repeat(v, 2, 0)
    s = q @ k.transpose(0, 2, 1) / 4.0
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    s = np.where((back >= 0) & (back < window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    o = (p / p.sum(-1, keepdims=True)) @ v
    o = o.transpose(1, 0, 2).reshape(t, 64)
    gate = 1.0 / (1.0 + np.exp(-(x @ lw["gate"].T)))
    return (o * gate) @ lw["o"].T


@pytest.mark.parametrize("layer", [0, 2])
def test_attention_layers_against_hand_written_equations(net, weights, layer):
    """The sliding layer 0 rotates, the full layer 2 takes no position;
    both gate their heads' outputs by ``sigmoid(x W_gate)`` before
    ``o``."""
    m = net.layers[layer].mixer
    lw = {k[len(f"l{layer}."):]: np.asarray(v.astype(jnp.float32), np.float64)
          for k, v in weights.items() if k.startswith(f"l{layer}.")}
    x = np.random.default_rng(7).normal(size=(12, 64))
    name, shape, _, _ = net.state_spec(1, 32, max_chunk=12)[
        0 if layer == 2 else 1]
    kv = nd.array(np.zeros((1,) + shape[1:], np.float32))
    got, _ = m(nd.array(x[None].astype(np.float32)), nd.array(np.zeros(1)),
               kv)
    want = _attention_by_hand(x, lw, rotate=layer != 2,
                              window=8 if layer != 2 else 12)
    np.testing.assert_allclose(got.asnumpy()[0], want, rtol=1e-4, atol=1e-5)


# -- tracing: the counts on the regions ----------------------------------
def test_regions_carry_the_held_assignments_and_the_counter_adds_them(
        runner):
    """``gen/prefill/call`` and ``gen/decode`` carry
    ``moe_assignments_held`` beside ``moe_experts_touched``, brought
    over in the fetch; ``mxtpu_moe_assignments_held_total`` sums them."""
    def total(name):
        return sum(int(v["value"]) for v in
                   obs.snapshot().get(name, {}).get("series", []))
    before = total("mxtpu_moe_assignments_held_total")
    profiler.set_state("run")
    try:
        kv = runner.new_cache()
        tok = np.asarray([_prompt(4, salt=3)], np.float32)
        _, kv = runner.prefill(tok, np.zeros(1, np.float32),
                               np.ones(1, np.float32), kv,
                               np.full(1, 4, np.float32))
        on = np.array([0, 1, 0, 0], np.float32)
        _, kv = runner.decode(np.full((4, 1), 5, np.float32),
                              np.array([0, 4, 0, 0], np.float32), kv, on)
        events = profiler.events()
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
    calls = [e["args"] for e in events
             if e["name"] in (obs.SPAN_PREFILL_CALL, obs.SPAN_DECODE)]
    assert [c["moe_assignments"] for c in calls] == [16, 4]
    held = [c["moe_assignments_held"] for c in calls]
    assert all(0 <= h <= a * 3 for h, a in zip(held, (16, 4)))
    assert all(c["moe_experts_touched"] <= c["moe_assignments_held"]
               for c in calls)
    assert total("mxtpu_moe_assignments_held_total") - before == sum(held)


# -- the programs that take none of the share's options ------------------
# sha256 of the lowered text (source files and lines stripped, scopes
# kept) of the tiny Mellum and SDAR decode and prefill programs before
# the expert share was added
PINNED = {
    ("mellum", None, "decode"):
        "16a35f3e4a802a14e7252eaed1bab502306337454463cf3cdb6007428faec99e",
    ("mellum", None, "prefill"):
        "2d40731e991cdc84f6a79c6bb3347fdfa59abd452ab6db0f9e8156e7192a893e",
    ("mellum", "bfloat16", "decode"):
        "bb65b5e1d1471fd7a527ec1195e11005acc4989d02c750fcf8425074822c0456",
    ("mellum", "bfloat16", "prefill"):
        "d3795b890c96e76fddb439771e81527d67c5365538935b321d7bb0ba1baf2871",
    ("sdar", None, "decode"):
        "992f728c62a31b86adb0eaa22053f0ee8df7d5643da2389ef2cd879d26dbd5ba",
    ("sdar", None, "prefill"):
        "3ce46f9bdd72ab2ca8966b57228fa0e232f05daddd162ffa1dab929b8a1dac64",
}


def _lowered_digest(family, amp, kind):
    from mxtpu import analysis
    from test_mellum2 import CFG as MELLUM
    from test_sdar import CFG as SDAR
    cfg, n_in, spec_kw = {"mellum": (MELLUM, 5, {"max_chunk": 8}),
                          "sdar": (SDAR, 4, {})}[family]
    net = HybridDecoderModel.from_config(cfg)
    net.initialize()
    out = net(*[sym_mod.var(f"data{i}") for i in range(n_in)])
    params = {p.name: p.data() for p in net.collect_params().values()}
    r = GenerateRunner(sym_mod.Group(list(out)), params,
                       net.state_spec(3, 48 if family == "mellum" else 64,
                                      **spec_kw),
                       prompt_buckets=(8,), cache=None, amp=amp,
                       counters=net.counter_spec(),
                       diffusion=net.diffusion_spec())
    bucket = ("decode", (4,)) if kind == "decode" else ("prefill", (1, 8))
    fn = r._decode_pure() if kind == "decode" else r._prefill_pure()
    text = analysis.lowered_text(fn, *r._structs(bucket), r._param_structs)
    text = re.sub(r' source_file="[^"]*" source_line=\d+', "", text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("family,amp,kind", sorted(
    PINNED, key=lambda k: (k[0], str(k[1]), k[2])))
def test_mellum_and_sdar_programs_are_the_ones_they_were(family, amp, kind):
    assert _lowered_digest(family, amp, kind) == PINNED[(family, amp, kind)]
