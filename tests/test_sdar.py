"""The block-diffusion decoder (``sdar_moe``) on the serving path.

A tiny model (two layers of full attention under the block-causal mask
with blocks of 4, hidden 64, 4 query heads over 2 key/value heads of 16,
per-head QK-norm, plain rotation, 8 experts of 32 of which a token takes
2, vocabulary 512 with the mask id 500, untied head) is served through
``GenerateRunner`` and ``GenerateBatcher`` and held against the plain
reference (``benchmark/reference_sdar.py``: float32, whole masks, every
expert over every token, no cache) on seeded weights: every pass of
several requests, in the same step at different phases of their blocks,
a prompt whose tail shares its first block, and the folded passes that
store a clean block in front of the next one.  Logits are compared, not
tokens; on the CPU in float32 the program and the reference differ by
rounding only, so every tolerance on logits of size 0.1-1 is 3e-5 (a
pass that saw one key too many or too few misses by 1e-3 and more).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, obs, profiler
from mxtpu import symbol as sym_mod
from mxtpu.models.hybrid import GroupedQueryAttention, HybridDecoderModel
from mxtpu.ndarray import rnn_impl
from mxtpu.serving import DeviceLogits, GenerateBatcher, GenerateRunner

from benchmark import reference_sdar as ref
from benchmark import weights_sdar

B, MASK, V = 4, 500, 512
CFG = {"model_type": "sdar_moe", "vocab_size": V, "hidden_size": 64,
       "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
       "num_hidden_layers": 2, "moe_intermediate_size": 32,
       "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
       "decoder_sparse_step": 1, "mlp_only_layers": [],
       "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": None,
       "tie_word_embeddings": False, "attention_bias": False,
       "use_sliding_window": False, "block_length": B,
       "mask_token_id": MASK, "denoising_steps": 4,
       "confidence_threshold": 0.9}
LANES, CAP, BUCKET = 3, 64, 8
TOL = 3e-5
SEED = 2 ** 31 + 11


def _built(cfg, weights):
    n = HybridDecoderModel.from_config(cfg)
    n.initialize()
    leaves = n.named_leaves()
    assert {k: tuple(p.shape) for k, p in leaves.items()} == \
        weights_sdar.leaf_shapes(cfg)
    for name, p in leaves.items():
        p.set_data(nd.array(np.asarray(weights[name].astype(jnp.float32))))
    return n


@pytest.fixture(scope="module")
def weights():
    return weights_sdar.make(CFG, SEED)


@pytest.fixture(scope="module")
def net(weights):
    return _built(CFG, weights)


def _runner(net, cap=CAP):
    out = net(*[sym_mod.var(f"data{i}") for i in range(4)])
    params = {p.name: p.data() for p in net.collect_params().values()}
    return GenerateRunner(
        sym_mod.Group(list(out)), params, net.state_spec(LANES, cap),
        prompt_buckets=(BUCKET,), cache=None, counters=net.counter_spec(),
        diffusion=net.diffusion_spec())


@pytest.fixture(scope="module")
def runner(net):
    return _runner(net)


def _logits(weights, tokens, cast=None):
    return np.asarray(ref.forward(CFG, weights, np.asarray(tokens)[None],
                                  cast=cast))[0]


def _prompt(n, salt=0):
    return np.random.default_rng(700 + salt).integers(1, MASK, n).tolist()


class _Clock:
    t = 100.0

    def __call__(self):
        return self.t


def _drive(b, reqs, n=400):
    for _ in range(n):
        b.step()
        if all(r.done() for r in reqs):
            return
    raise AssertionError("requests not done")


def _recording(runner, batcher):
    """Every decode call's host rows, logits and the request each active
    slot held, kept as the batcher makes them."""
    calls, decode = [], runner.decode

    def kept(tokens, step, kv, length=None, **kw):
        held = {i: lane.req for i, lane in enumerate(batcher._lanes)
                if lane is not None}
        out, kv = decode(tokens, step, kv, length, **kw)
        calls.append((np.array(tokens), np.array(step), np.array(length),
                      np.asarray(out), out.first_maximum.copy(),
                      out.confidence.copy(), held))
        return out, kv

    runner.decode = kept
    return calls


# ------------------------------------------------------------ the model
def test_the_model_declares_a_block_and_its_mask(net):
    assert net.diffusion_spec() == {"block": B, "mask_id": MASK,
                                    "steps": 4, "threshold": 0.9}
    assert [t[0] for t in net.state_spec(2, 16)] == ["kv"]
    for layer in net.layers:
        assert isinstance(layer.mixer, GroupedQueryAttention)
        assert layer.mixer._read == {"block": B} and layer.sparse
    text = sym_mod.Group(list(net(*[sym_mod.var(f"data{i}")
                                    for i in range(4)]))).tojson()
    assert '"block": "4"' in text or '"block": 4' in text


@pytest.mark.parametrize("n", [4, 8, 12])
def test_full_forward_matches_the_reference(net, weights, n):
    """A row's last block of logits, from fresh tables, against the
    reference's whole forward under the block mask: rows of one, two and
    three whole blocks (a caller feeds whole blocks: a position reads
    every column of its block), the rest of the row padding."""
    seq = _prompt(12)
    want = _logits(weights, seq[:n])
    assert np.abs(want).max() > 0.1
    tables = [nd.array(np.zeros(s, np.float32))
              for _, s, _, _ in net.state_spec(1, 16)]
    out = net(nd.array(np.asarray(seq, np.float32)[None]),
              nd.array(np.zeros(1)), nd.array(np.array([float(n)])), *tables)
    got = out[0].asnumpy()[0]
    assert got.shape == (B, V)
    np.testing.assert_allclose(got, want[n - B:n], atol=TOL, rtol=0)
    # the mask is the block one: causal attention gives other logits at
    # every position of a block but its last
    causal = _logits(weights, seq[:n], cast="causal_block")
    gap = np.abs(causal[n - B:n] - want[n - B:n]).max(-1)
    assert (gap[:-1] > 1e-3).all()


@pytest.mark.parametrize("bad,names", [
    ({"mlp_only_layers": [1]}, "dense feed-forward"),
    ({"attention_bias": True}, "attention biases"),
    ({"use_sliding_window": True}, "sliding window"),
    ({"block_length": 1}, "block_length")])
def test_from_config_names_what_it_cannot_build(bad, names):
    with pytest.raises(mx.base.MXNetError, match=names):
        HybridDecoderModel.from_config(dict(CFG, **bad))


def test_the_published_stage_at_its_widths():
    """The configuration's 6-layer stage, built symbolically (no weight
    is made), holds the leaves the cut counts: 8.72 GB in
    bfloat16 with every expert and the whole vocabulary."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "sdar_30b_a3b_chat.json")) as f:
        cfg = json.load(f)
    stage = HybridDecoderModel.from_config(cfg)
    assert stage.layer_types == ("full_attention",) * 6
    assert stage.diffusion_spec() == {"block": 4, "mask_id": 151669,
                                      "steps": 4, "threshold": 0.9}
    (kv,) = stage.state_spec(49, 2560, kv_dtype="bfloat16")
    assert kv[1] == (6, 2, 49, 4, 2560, 128)
    shapes = {k: tuple(p.shape) for k, p in stage.named_leaves().items()}
    assert shapes == weights_sdar.leaf_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 4_361_055_744
    assert shapes["l5.w_in"] == (128, 2048, 1536)
    mixer = stage.layers[0].mixer
    assert mixer._read == {"block": 4} and mixer._rope["scale"] == 1.0
    np.testing.assert_allclose(
        mixer._rope["inv_freq"],
        1e6 ** (-np.arange(64) / 64.0), rtol=1e-12)


# ------------------------------------------------------- the reference
def test_a_pass_against_the_stored_prefix_is_the_whole_forward(weights):
    """``pass_logits`` — one forward of the final sequence for the keys
    and values, then each pass alone against the columns before it — is
    the whole forward of ``[prefix ; the pass's tokens]``: a pass of one
    block with masked positions, a folded pass of two, a pass from 0."""
    seq = _prompt(20, salt=1)
    passes = [(12, [seq[12], MASK, seq[14], MASK]),
              (8, seq[8:12] + [MASK] * 4),
              (0, [MASK] * 4)]
    cols = np.arange(0, V, 3)
    got = ref.pass_logits(CFG, weights, seq, passes, cols, chunk=2)
    for (start, fed), g in zip(passes, got):
        whole = _logits(weights, seq[:start] + fed)
        np.testing.assert_allclose(g, whole[-B:][:, cols], atol=TOL, rtol=0)
    off = ref.pass_logits(CFG, weights, seq, passes, cols,
                          cast="causal_block")
    assert np.abs(off - got).max() > 1e-3


# ---------------------------------------------------------- the batcher
def test_every_pass_of_the_batcher_is_the_references_forward(net, runner,
                                                             weights):
    """Four requests over three lanes, admitted at different steps: a
    prompt of 6 (its tail of 2 shares its first block, which two passes
    denoise), prompts of 8 and 13, and one of 3 (no whole block: no
    prefill call).  Every pass of every lane — folded (2 blocks) and not,
    the lanes at different passes of their blocks in one step — gives
    the logits of the reference's whole forward of what that row fed
    after what its request holds before it."""
    b = GenerateBatcher(runner, clock=_Clock(), max_lanes=LANES)
    calls = _recording(runner, b)
    try:
        prompts = [_prompt(n, salt=n) for n in (6, 8, 13, 3)]
        lens = [10, 8, 12, 7]
        reqs = [b.submit(prompts[0], max_tokens=lens[0])]
        b.step()
        reqs += [b.submit(p, max_tokens=n)
                 for p, n in zip(prompts[1:], lens[1:])]
        _drive(b, reqs)
    finally:
        runner.decode = GenerateRunner.decode.__get__(runner)
    assert [len(r.result()) for r in reqs] == lens
    folded = phases = 0
    for tokens, step, length, logits, first, conf, held in calls:
        rows = np.flatnonzero(length > 0)
        assert set(rows) == set(held)
        phases += len({int(length[i]) for i in rows}) > 1
        for i in rows:
            r = held[i]
            whole = r.prompt + r.result()
            s, n = int(step[i]), int(length[i])
            assert s % B == 0 and n in (B, 2 * B)
            folded += n == 2 * B
            fed = [int(t) for t in tokens[i, :n]]
            want = _logits(weights, whole[:s] + fed)[-B:]
            np.testing.assert_allclose(logits[i], want, atol=TOL, rtol=0)
            # what crossed to the host: the best token other than the
            # mask, and its probability under the whole row's softmax
            no_mask = want.copy()
            no_mask[:, MASK] = -np.inf
            np.testing.assert_array_equal(first[i], no_mask.argmax(-1))
            p = np.exp(want - want.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            np.testing.assert_allclose(
                conf[i], p[np.arange(B), first[i]], rtol=1e-4)
    assert folded >= 8 and phases >= 1
    assert all(MASK not in r.result() for r in reqs)


def test_emissions_come_in_whole_blocks_in_order(runner):
    """A step streams 0 or a block's tokens a lane (the first block: the
    positions after the prompt's tail; the last: cut at max_tokens), in
    order, and never one past ``max_tokens``; prefill streams nothing."""
    b = GenerateBatcher(runner, clock=_Clock(), max_lanes=LANES)
    got = {}
    sizes = [(9, 11), (4, 8), (14, 5)]
    reqs = [b.submit(_prompt(n, salt=30 + n), max_tokens=m,
                     on_token=lambda t, i, k=k: got.setdefault(k, []).append(
                         (b.steps, i, t)))
            for k, (n, m) in enumerate(sizes)]
    _drive(b, reqs)
    for k, (r, (n, m)) in enumerate(zip(reqs, sizes)):
        stream = got[k]
        assert [i for _, i, _ in stream] == list(range(m))
        assert [t for _, _, t in stream] == r.result() and r.finish_reason \
            == "length"
        by_step = {}
        for step, _, _ in stream:
            by_step[step] = by_step.get(step, 0) + 1
        counts = [by_step[s] for s in sorted(by_step)]
        first = B - n % B if n % B else B
        assert counts[0] == min(first, m)
        assert all(c == B for c in counts[1:-1])
        assert 0 < counts[-1] <= B


def _biased(weights, towards):
    """Weights whose head puts ``towards`` first at every position by a
    wide margin: every embedding carries a large first coordinate, which
    survives the layers and the final norm, and only the head's row of
    ``towards`` reads it."""
    w = dict(weights)
    w["embed"] = weights["embed"].at[:, 0].set(60.0)
    head = jnp.zeros_like(weights["head"]).at[:, 1:].set(
        weights["head"][:, 1:])
    w["head"] = head.at[towards, 0].set(3.0)
    return w


@pytest.mark.parametrize("threshold,mask_first,passes", [
    (0.9, False, 1), (1.01, False, 4), (0.9, True, 4)],
    ids=["passes", "unreachable", "mask-first"])
def test_a_confident_pass_decides_more_than_its_share(weights, threshold,
                                                      mask_first, passes):
    """With confidences near 1 a pass decides every masked position
    whose probability passes the threshold (a block in one pass); with a
    threshold nothing passes it decides the static count, one of four a
    pass.  A head that puts the mask id first leaves its runner-up little
    probability, so the static count again, and the mask id is never
    decided nor streamed."""
    w = _biased(weights, 7)
    if mask_first:
        w["head"] = w["head"].at[MASK, 0].set(4.0)
    net = _built(dict(CFG, confidence_threshold=threshold), w)
    r = _runner(net)
    b = GenerateBatcher(r, clock=_Clock(), max_lanes=LANES)
    calls = _recording(r, b)
    req = b.submit(_prompt(8, salt=50), max_tokens=12)
    _drive(b, [req])
    assert req.result() == [7] * 12
    assert len(calls) == 3 * passes
    conf = min(c[5][0].min() for c in calls)
    assert (conf < 0.01) if mask_first else (conf > 0.99)
    profiler.set_state("run")
    try:
        b2 = GenerateBatcher(r, clock=_Clock(), max_lanes=LANES)
        req = b2.submit(_prompt(8, salt=51), max_tokens=8)
        _drive(b2, [req])
        events = profiler.events()
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
    decodes = [e["args"] for e in events if e["name"] == obs.SPAN_DECODE]
    assert [d["decided"] for d in decodes] == [4 // passes] * (2 * passes)
    assert [d["positions"] for d in decodes] == \
        [B] * passes + [2 * B] + [B] * (passes - 1)
    assert [d["committed"] for d in decodes] == \
        [0] * passes + [1] + [0] * (passes - 1)
    assert sum(d["emitted_blocks"] for d in decodes) == 2
    steps = [e["args"] for e in events if e["name"] == obs.SPAN_GEN_STEP]
    assert sum(s["emitted"] for s in steps) == 8


def test_submit_refuses_what_a_block_cannot_hold(net):
    r = _runner(net, cap=16)
    b = GenerateBatcher(r, clock=_Clock(), max_lanes=LANES)
    with pytest.raises(mx.base.MXNetError, match="no room for a block"):
        b.submit(_prompt(13), max_tokens=4)
    b.submit(_prompt(11), max_tokens=4)
    with pytest.raises(mx.base.MXNetError, match="greedily"):
        b.submit(_prompt(4), max_tokens=4, top_k=5)
    # a stream ends where the table does: the last block's passes write
    # two blocks' columns from its start
    req = b.submit(_prompt(2, salt=3), max_tokens=40)
    _drive(b, [req])
    assert req.finish_reason == "length" and len(req.result()) == 12 - 2


# -------------------------------------------------------------- the ops
def _block_oracle(q, k, v, block):
    """Attention over whole sequences under the block-causal mask:
    loops, float64."""
    Bn, H, T, D = q.shape
    g = H // k.shape[1]
    out = np.zeros((Bn, H, T, D))
    for b in range(Bn):
        for h in range(H):
            for t in range(T):
                end = (t // block + 1) * block
                s = k[b, h // g, :end] @ q[b, h, t] / np.sqrt(D)
                p = np.exp(s - s.max())
                out[b, h, t] = p / p.sum() @ v[b, h // g, :end]
    return out


def test_block_masked_attention_over_a_table_equals_the_oracle():
    """24 positions written and read in chunks of whole blocks (1, 2 and
    3 blocks of 4) through ``kv_cache_write`` and ``cached_attention``
    with ``block=4``, into a table that starts full of another request's
    numbers, against the loops above."""
    rng = np.random.default_rng(9)
    Bn, H, Hk, T, D = 2, 4, 2, 24, 8
    q = rng.normal(size=(Bn, H, T, D))
    k, v = rng.normal(size=(2, Bn, Hk, T, D))
    want = _block_oracle(q, k, v, B)
    table = jnp.asarray(rng.normal(size=(1, 2, Bn, Hk, 32, D)) * 9,
                        jnp.float32)
    at, got = 0, []
    for n in (4, 8, 4, 8):
        step = jnp.full((Bn,), float(at))
        cut = lambda z: jnp.asarray(z[:, :, at:at + n], jnp.float32)
        for plane, new in enumerate((k, v)):
            table = rnn_impl._kv_cache_write_op(table, cut(new), step,
                                                layer=0, plane=plane)
        got.append(np.asarray(rnn_impl._cached_attention_op(
            cut(q), table, table, step, layer=0, block=B)))
        at += n
    np.testing.assert_allclose(np.concatenate(got, axis=2), want, atol=1e-5)
    with pytest.raises(mx.base.MXNetError, match="window and a block"):
        rnn_impl._cached_attention_op(cut(q), table[0, 0], table[0, 1], step,
                                      window=4, block=B)


def test_a_block_masked_chunk_never_reaches_the_kernel(monkeypatch):
    """The blockwise kernel stops at each row's frontier and knows no
    block mask: a chunk under ``block`` > 1 keeps XLA's form where the
    same chunk under the causal mask takes the kernel."""
    from mxtpu.kernels.flash_attention import cached_call_sites
    monkeypatch.setenv("MXTPU_PALLAS", "interpret")
    monkeypatch.setattr(rnn_impl, "_SCORES_FOR_KERNEL", 0)
    q = jax.ShapeDtypeStruct((1, 4, 16, 128), jnp.bfloat16)
    keys = jax.ShapeDtypeStruct((1, 2, 256, 128), jnp.bfloat16)
    assert rnn_impl._attends_by_kernel(q, keys)
    assert not rnn_impl._attends_by_kernel(q, keys, block=B)
    table = jax.ShapeDtypeStruct((1, 2, 1, 2, 256, 128), jnp.bfloat16)
    step = jax.ShapeDtypeStruct((1,), jnp.float32)
    from mxtpu import analysis
    for block, sites in ((1, 1), (B, 0)):
        with cached_call_sites() as seen:
            analysis.lowered_text(lambda a, t, s: rnn_impl._cached_attention_op(
                a, t, t, s, layer=0, block=block), q, table, step)
        assert seen[0] == sites


# the tiny models of test_mellum2.py and test_hybrid_generate.py
MELLUM = {"model_type": "mellum", "vocab_size": 97, "hidden_size": 64,
          "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
          "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
          "mlp_layer_types": ["sparse"] * 4, "moe_intermediate_size": 32,
          "num_experts": 8, "num_experts_per_tok": 2, "sliding_window": 8,
          "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
          "rope_parameters": {
              "full_attention": {"rope_type": "yarn", "rope_theta": 10000,
                                 "factor": 4,
                                 "original_max_position_embeddings": 16,
                                 "beta_fast": 4, "beta_slow": 1,
                                 "attention_factor": 1.1386},
              "sliding_attention": {"rope_type": "default",
                                    "rope_theta": 10000}}}
GRANITE = {"vocab_size": 97, "hidden_size": 64,
           "shared_intermediate_size": 128,
           "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
           "mamba_d_conv": 4, "mamba_chunk_size": 8, "mamba_n_groups": 1,
           "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
           "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
           "logits_scaling": 8}


def _programs(cfg, told=False):
    """The lowered text of a tiny model's decode and prefill programs;
    with ``told`` every attention op of its graph is given ``block=1``."""
    net = HybridDecoderModel.from_config(cfg)
    net.initialize()
    if told:
        for layer in net.layers:
            if isinstance(layer.mixer, GroupedQueryAttention):
                layer.mixer._read["block"] = 1
    spec = net.state_spec(2, 32, max_chunk=8)
    out = net(*[sym_mod.var(f"data{i}") for i in range(3 + len(spec))])
    symbol = sym_mod.Group(list(out))
    params = {p.name: p.data() for p in net.collect_params().values()}
    r = GenerateRunner(symbol, params, spec, prompt_buckets=(8,), cache=None,
                       counters=net.counter_spec())
    return symbol.tojson(), [r.lowered_program_text(bk) for bk in
                             (("decode", (3,)), ("prefill", (2, 8)))]


@pytest.mark.parametrize("cfg", [MELLUM, GRANITE], ids=["mellum", "granite"])
def test_block_one_is_the_program_it_was(cfg):
    """Models that generate a token a step give their attention no block
    attribute, and a graph whose attention is told ``block=1`` lowers
    its decode and prefill programs to the same text as one told
    nothing: the causal mask is the block mask of blocks of one."""
    graph, texts = _programs(cfg)
    assert '"block"' not in graph
    told_graph, told = _programs(cfg, told=True)
    assert '"block"' in told_graph
    assert told == texts


def test_the_runner_hands_back_a_block_a_row(runner):
    """A prefill and a pass of the diffusion runner hand back (rows, 4,
    V) logits, with each position's best id other than the mask and its
    probability; ``gen/decode`` counts the positions fed and the keys
    they read."""
    kv = runner.new_cache()
    tok = np.zeros((1, BUCKET), np.float32)
    tok[0] = _prompt(8, salt=60)
    out, kv = runner.prefill(tok, np.zeros(1, np.float32),
                             np.zeros(1, np.float32), kv,
                             np.array([8.0], np.float32))
    assert isinstance(out, DeviceLogits) and out.shape == (1, B, V)
    assert out.first_maximum.shape == out.confidence.shape == (1, B)
    slots = LANES + 1
    rows = np.zeros((slots, 2 * B), np.float32)
    rows[0, :B] = MASK
    profiler.set_state("run")
    try:
        out, kv = runner.decode(rows, np.array([8, 0, 0, 0], np.float32),
                                kv, np.array([B, 0, 0, 0], np.float32))
        events = profiler.events()
    finally:
        profiler.set_state("stop")
        profiler.dumps(reset=True)
    assert out.shape == (slots, B, V) and (out.first_maximum != MASK).all()
    (d,) = [e["args"] for e in events if e["name"] == obs.SPAN_DECODE]
    assert d["positions"] == B and d["attended"] == B * 12
    assert d["moe_assignments"] == 2 * B
