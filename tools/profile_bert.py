"""Ablation profile of the BERT-Large training step on the real chip
(VERDICT r4 item 3 — the profile_resnet.py treatment for BERT).

Decomposes fwd+bwd time at b32 s128 (and b8 s512) by knocking out one
component at a time and re-measuring the sustained chained step
(tools/microbench.py methodology: a real data dependence threads the
iterations, so nothing is DCE'd).  Components are ablated by
monkeypatching the model module's class names before construction —
the blocks resolve them at call time.

r6 additions, covering the hot-path work this profile motivated:
- ``epilogue_lax``     — MXTPU_FUSED_LN_EPILOGUE=0: the fused
  bias+dropout+add+LN Pallas epilogue replaced by the lax composite
  (same numerics, unfused memory traffic).
- ``loop_floor``       — the chained loop on an identity-cost body:
  dispatch + loop overhead that no model change can remove; subtract
  from every other row before computing component shares.
- ``step``             — the FULL TrainStep (fwd+bwd+optimizer) via
  build_train_step, as the benchmark's train cell runs it (one
  parameter an update, nothing stacked).  ``step`` minus ``full`` is
  the whole optimizer+writeback share.
- ``step_zero``        — the FULL TrainStep on a dp mesh over every
  local device (dp = min(8, devices)) with ZeRO-1 sharded optimizer
  states; vs ``step`` this prices the reduce-scatter/all-gather
  exchange (and ZeRO's stacked buckets) against the dp× opt-state HBM
  saving.  Skipped on a single-device host.
- ``--cost``           — also print TrainStep.cost_analysis() FLOPs /
  bytes for the step program (on TPU the Pallas custom calls hide
  their FLOPs; the CPU lowering counts everything — see
  bench.py _TRAIN_FLOPS provenance notes).

Usage: python tools/profile_bert.py [batch] [seqlen] [only,csv] [--cost]
(``--help`` prints this text and measures nothing.)
(MXTPU_PROFILE_BERT_MODEL=tiny|base|large swaps the model so the
harness itself can be smoke-tested on a CPU box.)
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools.microbench import sustained


def sustained_ms(fn, x0, n=10, repeats=3):
    return sustained(fn, x0, n=n, repeats=repeats) * 1e3


def _build_bert(seqlen, dropout=0.1):
    """bert_large unless MXTPU_PROFILE_BERT_MODEL overrides — the
    tiny/base tiers exist so the harness itself can be smoke-tested on
    a CPU box where a Large compile takes minutes."""
    import mxtpu.models.transformer as tr
    from mxtpu import knobs
    kind = knobs.get("MXTPU_PROFILE_BERT_MODEL")
    if kind == "tiny":
        return tr.BERTModel(30522, 128, 512, 2, 2, max_length=seqlen,
                            dropout=dropout)
    if kind == "base":
        return tr.bert_base(vocab_size=30522, max_length=seqlen,
                            dropout=dropout)
    return tr.bert_large(vocab_size=30522, max_length=seqlen,
                         dropout=dropout)


def build_loss_fn(batch, seqlen, variant, dropout=0.1):
    """Returns (loss_of(x_tokens_f32) -> scalar, token array)."""
    import mxtpu.models.transformer as tr
    from mxtpu import nd
    from mxtpu.gluon import loss as gloss
    from mxtpu.gluon import nn
    from mxtpu.gluon.block import HybridBlock, _traced_forward
    from mxtpu.ndarray.ndarray import NDArray
    from mxtpu.symbol import _is_aux_name

    saved = {}

    def patch(name, cls):
        saved[name] = getattr(tr, name)
        setattr(tr, name, cls)

    class AttnCoreOnlyV(tr.MultiHeadAttention):
        # flash-attention core replaced by the value passthrough:
        # QKV/proj GEMMs stay (isolates the attention-core cost)
        def hybrid_forward(self, F, x):
            u, h = self._units, self._heads
            qkv = self.qkv(x)
            v = F.slice_axis(qkv, axis=-1, begin=2 * u, end=3 * u)
            out = self.proj(v)
            if self.drop is not None:
                out = self.drop(out)
            return out

    class AttnIdentity(HybridBlock):
        def __init__(self, *a, **k):
            super().__init__()

        def hybrid_forward(self, F, x):
            return x

    class FFNIdentity(HybridBlock):
        def __init__(self, *a, **k):
            super().__init__()

        def hybrid_forward(self, F, x):
            return x

    class LNIdentity(HybridBlock):
        def __init__(self, *a, **k):
            super().__init__()

        def hybrid_forward(self, F, x):
            return x

    if variant == "attn_core_ablated":
        patch("MultiHeadAttention", AttnCoreOnlyV)
    elif variant == "attn_ablated":
        patch("MultiHeadAttention", AttnIdentity)
    elif variant == "ffn_ablated":
        patch("PositionwiseFFN", FFNIdentity)
    elif variant == "ln_ablated":
        saved["LayerNorm"] = nn.LayerNorm
        nn.LayerNorm = LNIdentity

    if variant == "no_dropout":
        dropout = 0.0

    try:
        net = _build_bert(seqlen, dropout)
        if variant == "mlm_ablated":
            net.mlm = nn.Dense(1024, flatten=False)
            net.register_child(net.mlm)
        net.initialize(init="xavier")
    finally:
        for k, v in saved.items():
            if k == "LayerNorm":
                nn.LayerNorm = v
            else:
                setattr(tr, k, v)

    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 30522, (batch, seqlen))
                       .astype(np.float32))

    # collect params once (eager)
    x_nd = NDArray(toks, None, _placed=True)
    from mxtpu import autograd
    with autograd.record():
        net(x_nd)
    params = net.collect_params()
    plist = list(params.values())
    pvals0 = [p.data().data for p in plist]
    cdt = jnp.bfloat16

    lfn = gloss.SoftmaxCrossEntropyLoss()
    V = net.mlm._units if hasattr(net.mlm, "_units") else 30522

    def loss_of(tv, xx):
        pvals = [v.astype(cdt)
                 if not _is_aux_name(plist[i].name)
                 and jnp.issubdtype(v.dtype, jnp.floating) else v
                 for i, v in enumerate(tv)]
        raw_outs, _, _, _ = _traced_forward(
            net, {p.name: p for p in plist}, pvals,
            [NDArray(xx, None, _placed=True)], True,
            jax.random.PRNGKey(0))
        pred = NDArray(raw_outs[0], None, _placed=True)
        l = lfn(pred.reshape((-1, pred.shape[-1])),
                NDArray(xx.reshape(-1), None, _placed=True))
        return jnp.mean(l.data.astype(jnp.float32))

    return loss_of, toks, tuple(pvals0), plist


class _env:
    """Set env overrides for the duration of one variant build+measure
    (the kill switches are read at trace time, and every measurement
    jits afresh)."""

    def __init__(self, **kv):
        self._kv = kv

    def __enter__(self):
        self._old = {k: os.environ.get(k) for k in self._kv}
        os.environ.update(self._kv)

    def __exit__(self, *a):
        for k, v in self._old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def measure_train_step(batch, seqlen, zero=None):
    """Full compiled TrainStep (fwd+bwd+optimizer+writeback) per-step
    ms — the number bench.py's BERT row is made of.  ``zero=1`` runs
    it on a dp mesh over every local device with ZeRO-1 sharded
    optimizer states (bench.py's bert_zero row)."""
    from mxtpu import nd, parallel
    from mxtpu.gluon import loss as gloss

    mesh = None
    if zero:
        dp = min(8, jax.device_count())
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:dp]), ("dp",))
    net = _build_bert(seqlen)
    net.initialize(init="xavier")

    def mlm_loss(pred, y):
        return gloss.SoftmaxCrossEntropyLoss()(
            pred.reshape((-1, pred.shape[-1])), y.reshape((-1,)))

    step = parallel.build_train_step(
        net, mlm_loss, "adam", {"learning_rate": 1e-4},
        compute_dtype="bfloat16", cast_batch=False,
        mesh=mesh, zero=zero)
    rng = np.random.RandomState(0)
    toks = nd.array(rng.randint(0, 30522, (batch, seqlen))
                    .astype(np.float32))
    last = step.run_steps(toks, toks, 2, reuse_batch=True)
    float(last.asnumpy()[-1])  # compile + drain
    n, best = 8, float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        last = step.run_steps(toks, toks, n, reuse_batch=True)
        float(last.asnumpy()[-1])
        best = min(best, (time.perf_counter() - t0) / n)
    return best * 1e3, step, toks


def measure_variant(batch, seqlen, variant):
    if variant == "step_zero":
        dp = min(8, jax.device_count())
        if dp <= 1 or batch % dp:
            return None  # needs a >1 dp mesh that divides the batch
        t, _, _ = measure_train_step(batch, seqlen, zero=1)
        return t
    if variant == "step":
        t, _, _ = measure_train_step(batch, seqlen)
        return t
    if variant == "loop_floor":
        rng = np.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, 30522, (batch, seqlen))
                           .astype(np.float32))
        # identity-cost body: what remains is the chained-loop +
        # dispatch floor every other row also pays
        return sustained_ms(
            lambda xx: jnp.clip(xx + jnp.sum(xx) * 0.0 + 1e-12,
                                0, 30521),
            toks, n=8, repeats=3)

    env = {"epilogue_lax": {"MXTPU_FUSED_LN_EPILOGUE": "0"}} \
        .get(variant, {})
    with _env(**env):
        loss_of, toks, pvals, plist = build_loss_fn(
            batch, seqlen, variant)

        grad_fn = jax.grad(lambda tv, xx: loss_of(tv, xx))

        def chain(xx):
            g = grad_fn(pvals, xx)
            s = sum(jnp.sum(gi.astype(jnp.float32))
                    for gi in jax.tree_util.tree_leaves(g))
            # fold the grad signal back into the token ids (kept valid
            # by a tiny scale + floor) so iterations are data-dependent
            return jnp.clip(xx + s * 1e-12, 0, 30521)

        return sustained_ms(chain, toks, n=8, repeats=3)


VARIANTS = ["full", "attn_core_ablated", "attn_ablated", "ffn_ablated",
            "mlm_ablated", "ln_ablated", "no_dropout", "epilogue_lax",
            "loop_floor", "step", "step_zero"]


def main():
    if "--help" in sys.argv[1:] or "-h" in sys.argv[1:]:
        print(__doc__)
        return
    argv = [a for a in sys.argv[1:] if not a.startswith("--")]
    want_cost = "--cost" in sys.argv[1:]
    batch = int(argv[0]) if len(argv) > 0 else 32
    seqlen = int(argv[1]) if len(argv) > 1 else 128
    only = argv[2].split(",") if len(argv) > 2 else None
    print(f"device={jax.devices()[0]} b{batch} s{seqlen} bf16 "
          f"(fwd+bwd, chained; step rows add the optimizer)")
    base = None
    for v in VARIANTS:
        if only and v not in only:
            continue
        t = measure_variant(batch, seqlen, v)
        if t is None:
            print(f"{v:>18}: skipped (needs a >1-device dp mesh that "
                  f"divides the batch)", flush=True)
            continue
        tok_s = batch * seqlen / t * 1e3
        delta = f"  (component ~{base - t:6.1f} ms)" \
            if base is not None and not v.startswith("step") \
            and v != "loop_floor" else ""
        if v == "full":
            base = t
        print(f"{v:>18}: {t:7.1f} ms/step  {tok_s:9.0f} tok/s{delta}",
              flush=True)
    if want_cost:
        from mxtpu import nd, parallel
        from mxtpu.gluon import loss as gloss
        net = _build_bert(seqlen)
        net.initialize(init="xavier")

        def mlm_loss(pred, y):
            return gloss.SoftmaxCrossEntropyLoss()(
                pred.reshape((-1, pred.shape[-1])), y.reshape((-1,)))

        step = parallel.build_train_step(
            net, mlm_loss, "adam", {"learning_rate": 1e-4},
            compute_dtype="bfloat16", cast_batch=False)
        rng = np.random.RandomState(0)
        toks = nd.array(rng.randint(0, 30522, (batch, seqlen))
                        .astype(np.float32))
        ca = step.cost_analysis(toks, toks)
        flops = ca.get("flops")
        toks_n = batch * seqlen
        print(f"cost_analysis: flops={flops:.3e} "
              f"({flops / toks_n:.3e}/token)  "
              f"bytes={ca.get('bytes accessed', float('nan')):.3e}  "
              f"(Pallas custom calls hide their FLOPs on TPU; the CPU "
              f"lowering counts everything)", flush=True)


if __name__ == "__main__":
    main()
