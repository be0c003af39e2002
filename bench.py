"""Benchmark: compiled training-step throughput on the real chip.

Prints ONE JSON line whose primary metric is the **ResNet-50 ImageNet
training throughput** (north-star #1, BASELINE.md); the BERT-Large
(north-star #2) and LeNet numbers ride along in ``extras`` so every
round's ``BENCH_r{N}.json`` captures the full picture.  Set
MXTPU_BENCH_MODEL=lenet|resnet50|resnet50_pipeline|bert|bert_s512|
transformer|moe_ffn|ssd|bert_zero|serving_bert|serving_fleet|
serving_autoscale|serving_coldstart|serving_bert_int8|
serving_generate to run a single
workload (moe_ffn, ssd, bert_zero and the serving_* rows are
on-demand only — not part of the default ``all`` sweep, which is
sized to the wall budget).  ``--amp`` (or MXTPU_BENCH_MODEL=resnet50_amp|bert_amp|
transformer_amp|bert_zero_amp) runs the ``mxtpu.amp`` pair rows: the
base workload measured AMP-off and AMP-on, rate + MFU + (for the
ZeRO pair) contract-pinned comm bytes side by side.  Every row's ``details``
carries ``hbm_peak`` — the per-device resident high-water
(temp + argument bytes) of the compiled program, from XLA's
memory_analysis.  ``bench.py --preflight`` prints the per-row wall
estimates for the selected sweep and exits non-zero if it would not
fit MXTPU_BENCH_WALL_BUDGET — check this BEFORE burning a TPU run.

The measured unit is the full compiled training step — forward,
backward, fused optimizer (+BN aux writeback) — via
``mxtpu.parallel.build_train_step``, i.e. the samples/sec a
Speedometer would report (SURVEY.md §5.5).

``mfu`` is model-FLOPs utilisation: training FLOPs/sample as counted
by XLA's cost_analysis of the compiled fwd+bwd program (see
_TRAIN_FLOPS) divided by the chip's peak bf16 FLOP/s.  Each row's
``band`` records the run-to-run spread of its repeats.

Where it runs: on a TPU, in this one process.  JAX picks the platform
(no default is set here); with no TPU the run is an error, not a CPU
run, and a ``device_kind`` missing from ``_PEAK_BF16`` raises instead
of reporting ``mfu: null``.  The JSON names the device it ran on.  The
``--contracts`` gates are CPU-pinned child processes and run before
this process first touches the chip.

Wall budget: the run carries a global deadline
(``MXTPU_BENCH_WALL_BUDGET`` seconds, default 780).
When the selected sweep's TOTAL estimate already exceeds the budget,
the sweep is auto-trimmed UP FRONT: rows that don't fit the cumulative
estimate are recorded as ``{"skipped": "budget"}`` before anything
runs — the same arithmetic ``--preflight`` prints, applied instead of
merely warned about.  Before each remaining workload the leftover time
is re-checked against that row's conservative estimate as a backstop;
a row that does not fit is likewise recorded as
``{"skipped": "budget"}`` instead of running.  The pipeline row
additionally self-limits: repeats stop when its own slice of the
budget is spent.  Stale ``mxtpu_bench_rec_*`` temp dirs from killed
runs are swept at startup.  A ``SIGALRM`` at the wall budget flushes
the partial record (never-ran rows as ``{"skipped": "budget"}``) if a
single row hangs straight through its estimate.

Exit code: the JSON always prints; the process then exits non-zero if
any row raised (its ``error`` is on the record) or the wall alarm
tripped.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

from mxtpu import guards, knobs, obs

# MXTPU_GUARDS must never change bench semantics: self_check asserts
# the disabled scope is the shared no-op object (zero per-call
# overhead when guards are off) and, when enabled, that a jitted
# probe returns bit-identical results inside the guard scope.
guards.self_check()
# Same contract for MXTPU_OBS: disabled instruments are the shared
# no-op singletons, and the exposition formats round-trip losslessly.
obs.self_check()

# Peak dense bf16 FLOP/s per chip, by jax device_kind prefix.
# v5 lite (v5e) 197 TFLOP/s; v5p 459; v4 275; v3 123 (bf16).
_PEAK_BF16 = (("TPU v5 lite", 197e12), ("TPU v5p", 459e12),
              ("TPU v5", 459e12), ("TPU v4", 275e12), ("TPU v3", 123e12),
              ("TPU v2", 45e12))

# single source for each workload's metric name (success AND error
# paths report the same key)
_METRIC_NAMES = {
    "resnet50": "resnet50_imagenet_train_throughput",
    "resnet50_pipeline": "resnet50_pipeline_fed_train_throughput",
    "bert": "bert_large_pretrain_throughput",
    "bert_s512": "bert_large_s512_pretrain_throughput",
    "transformer": "transformer_big_wmt_train_throughput",
    "moe_ffn": "moe_ffn_microbench_throughput",
    "ssd": "ssd300_voc_train_throughput",
    "bert_zero": "bert_large_zero1_train_throughput",
    "serving_bert": "serving_bert_sustained_throughput",
    "serving_fleet": "serving_fleet_soak_throughput",
    "serving_autoscale": "serving_autoscale_burst_absorb_throughput",
    "serving_coldstart": "serving_coldstart_disk_warm_speedup",
    "serving_bert_int8": "serving_bert_int8_raw_throughput",
    "serving_generate": "serving_generate_decode_throughput",
    "lenet": "lenet_mnist_train_throughput",
    # --amp pairs: each row runs its base workload twice (AMP off /
    # AMP on via mxtpu.amp) and reports rate + MFU + comm side by side
    "resnet50_amp": "resnet50_imagenet_amp_train_throughput",
    "bert_amp": "bert_large_amp_pretrain_throughput",
    "transformer_amp": "transformer_big_wmt_amp_train_throughput",
    "bert_zero_amp": "bert_large_zero1_amp_train_throughput",
}

# Training FLOPs per unit (sample or token), from XLA's own
# cost_analysis() of the compiled fwd+bwd program (r4: the widely
# quoted "4.1 GFLOP" for ResNet-50 is multiply-ACCUMULATES; XLA counts
# 7.54 GFLOP fwd / 22.49 GFLOP fwd+bwd per sample at 224x224, so r1-r3
# under-reported ResNet MFU by 1.83x.  BERT's 6N estimate was within
# 3% of XLA's 2.063 GFLOP/token and is replaced by the measured value.)
_TRAIN_FLOPS = {
    "resnet50": 22.49e9,      # XLA cost_analysis, fwd+bwd, b256
    "resnet50_pipeline": 22.49e9,  # same model, pipeline-fed
    "bert": 2.063e9,          # XLA cost_analysis, fwd+bwd, b32 s128
    # s512: s128 measurement + analytic attention delta (4*T*d*L fwd,
    # x3 fwd+bwd; the flash-attention custom call hides its FLOPs from
    # cost_analysis, so the analytic form is the honest one here)
    "bert_s512": 2.18e9,
    # TrainStep.cost_analysis on the CPU lowering (r6), fwd+bwd+adam,
    # transformer_big b16 s64+s64, per src+tgt token.  The CPU count
    # INCLUDES the attention einsums the TPU Pallas custom call hides,
    # so it is the complete denominator (1.489e12 FLOPs / 2048 tokens).
    "transformer": 0.727e9,
    "moe_ffn": None,          # microbench reports its own details
    "bert_zero": None,        # ablation row — the throughput delta and
                              # opt-state bytes are the result, not MFU
    "ssd": None,              # anchor machinery dominates op count,
                              # MFU would flatter the conv backbone
    "serving_bert": None,     # latency/throughput row — the served/raw
                              # ratio is the result, not MFU
    "serving_fleet": None,    # robustness row — zero in-deadline drops
                              # through a kill/restart is the result
    "serving_autoscale": None,  # control-plane row — absorb time / SLO
                                # violations vs static-N are the result
    "serving_coldstart": None,  # robustness row — the cold vs
                                # disk-warmed warmup split is the result
    "serving_bert_int8": None,  # ablation row — the int8/f32 ratio,
                                # accuracy delta and s8xs8->s32 census
                                # are the result, not MFU
    "serving_generate": None,   # decode row — tokens/sec, TTFT and
                                # the kv-vs-naive-reprefill ratio are
                                # the result, not MFU
    "lenet": None,            # too small for MFU to mean anything
    # amp pairs reuse the base row's FLOP denominator: AMP changes
    # operand dtypes, not the model math being counted
    "resnet50_amp": 22.49e9,
    "bert_amp": 2.063e9,
    "transformer_amp": 0.727e9,
    "bert_zero_amp": None,
}


def _device():
    """The device this run measures, as the JSON reports it.  No TPU
    is an error: a CPU rate must never land under a device metric."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _peak_flops():
    import jax
    kind = jax.devices()[0].device_kind
    for prefix, peak in _PEAK_BF16:
        if kind.startswith(prefix):
            return peak
    raise ValueError(f"bench: no peak bf16 FLOP/s on record for "
                     f"device_kind {kind!r}; add it to _PEAK_BF16")


def _measure(step, x, y, warmup, iters, batch_size, repeats=5):
    """Timing of BULKED execution: ``iters`` steps run as one compiled
    ``lax.scan`` program (``TrainStep.run_steps``), the TPU-native
    analogue of the reference's bulked graph execution, so host
    dispatch per step is not part of the measured unit.  Returns
    {best, median, n, spread} over ``repeats`` runs — a single point
    is not a result."""
    last = step.run_steps(x, y, max(warmup, 2), reuse_batch=True)
    float(last.asnumpy()[-1])  # drain warmup incl. compile
    vals = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        last = step.run_steps(x, y, iters, reuse_batch=True)
        float(last.asnumpy()[-1])  # sync
        dt = time.perf_counter() - t0
        vals.append(batch_size * iters / dt)
    vals.sort()
    median = vals[len(vals) // 2] if len(vals) % 2 else \
        0.5 * (vals[len(vals) // 2 - 1] + vals[len(vals) // 2])
    # spread = (max-min)/median over the runs minus the single worst
    # (one stalled run would otherwise swamp the band)
    core = vals[1:] if len(vals) >= 4 else vals
    # per-device resident high-water (temp + argument bytes) of the
    # compiled scan program — rides into every row's ``details``
    mem = step.last_memory_analysis()
    return {"best": max(vals), "median": median, "n": len(vals),
            "spread": round((max(core) - min(core)) / median, 4),
            "runs": [round(v, 1) for v in vals],
            "info": {"hbm_peak": mem["hbm_peak"] if mem else None}}


def bench_lenet(batch_size=512, warmup=5, iters=30):
    from mxtpu import nd
    from mxtpu import parallel
    from mxtpu.gluon import loss as gloss
    from mxtpu.models import lenet

    net = lenet()
    net.initialize(init="xavier")
    step = parallel.build_train_step(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9})
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(batch_size, 1, 28, 28).astype(np.float32))
    y = nd.array(rng.randint(0, 10, (batch_size,)).astype(np.float32))
    return _measure(step, x, y, warmup, iters, batch_size), \
        _METRIC_NAMES["lenet"], "samples/sec"


def bench_resnet50(batch_size=None, warmup=3, iters=20, amp=None):
    """ResNet-50 ImageNet-shaped training step (north-star #1).
    Defaults to the standard TPU recipe — bf16 compute over f32 master
    weights, batch 256 (MXTPU_BENCH_DTYPE= / MXTPU_BENCH_BATCH
    override; set MXTPU_BENCH_DTYPE="" for pure f32).  ``amp=True``
    switches to the policy-driven ``mxtpu.amp`` path (bf16 storage +
    f32 masters + loss scaling) instead of the blanket compute-dtype
    cast — the two are mutually exclusive."""
    from mxtpu import nd
    from mxtpu import parallel
    from mxtpu.gluon import loss as gloss
    from mxtpu.models import resnet50

    batch_size = batch_size or knobs.get("MXTPU_BENCH_BATCH")
    net = resnet50(classes=1000)
    net.initialize(init="xavier")
    step = parallel.build_train_step(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        compute_dtype=(None if amp
                       else knobs.get("MXTPU_BENCH_DTYPE") or None),
        amp=amp)
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(batch_size, 3, 224, 224).astype(np.float32))
    y = nd.array(rng.randint(0, 1000, (batch_size,)).astype(np.float32))
    return _measure(step, x, y, warmup, iters, batch_size), \
        _METRIC_NAMES["resnet50"], "samples/sec"


def bench_resnet50_pipeline(batch_size=None, warmup=4, iters=24,
                            repeats=3, row_budget=None):
    """Pipeline-fed ResNet-50 (VERDICT r5 item 2): trains from an
    ImageRecordIter over a synthetic raw-record dataset — per-step
    batches, NO reuse_batch.  The full L6 pipeline:

        disk → vectorized batch assembly (one read_batch_into +
        blockwise mirror, worker thread via PrefetchingIter)
             → double-buffered H2D (DeviceFeedIter: batch N+1's
               non-blocking device_put issued while step N runs)
             → compiled step (uint8 crosses the link; cast + mean/std
               fuse into the first conv's XLA program).

    The raw-record tier is the honest rate-proof on THIS host: the
    box has ONE CPU core (nproc=1), which caps cv2 JPEG decode at
    ~380 img/s no matter the implementation; raw records take decode
    out and measure the framework's own assembly + feed architecture
    (BASELINE.md "Input pipeline").  Reference:
    iter_image_recordio_2.cc† + iter_prefetcher.h†.

    Self-limiting (r5 post-mortem): measurement repeats stop when
    ``row_budget`` seconds have elapsed in this row — a slow pipeline
    produces a worse number, never a dead round."""
    import shutil
    import tempfile

    from mxtpu import parallel
    from mxtpu import recordio as rio
    from mxtpu.gluon import loss as gloss
    from mxtpu.gluon import nn
    from mxtpu.io import (DeviceFeedIter, ImageRecordIter,
                          PrefetchingIter)
    from mxtpu.models import resnet50

    batch_size = batch_size or knobs.get("MXTPU_BENCH_BATCH")
    row_budget = row_budget or knobs.get("MXTPU_BENCH_ROW_BUDGET")
    t_row = time.perf_counter()
    d = tempfile.mkdtemp(prefix="mxtpu_bench_rec_")
    try:
        prefix = os.path.join(d, "synth")
        rng = np.random.RandomState(0)
        n_img = 4 * batch_size
        rec = rio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                    "w")
        base = (rng.rand(3, 224, 224) * 255).astype(np.uint8)
        for i in range(n_img):
            # distinct images without n_img full RNG draws: roll+refresh
            if i % 61 == 0:
                base = (rng.rand(3, 224, 224) * 255).astype(np.uint8)
            rec.write_idx(i, rio.pack(
                rio.IRHeader(0, float(i % 1000), i, 0),
                np.roll(base, i % 224, axis=2).tobytes()))
        rec.close()

        compute_dtype = knobs.get("MXTPU_BENCH_DTYPE") or "float32"

        class _DeviceNormalize(nn.HybridBlock):
            """uint8 -> (x - mean)/std on device; XLA fuses it into the
            step (channel-mean simplification: ImageNet grand mean /
            std — the arithmetic cost is identical to per-channel).
            The 1/std lives in a frozen parameter so the layer inherits
            the compute dtype from the AMP cast machinery: eager
            shape-inference sees f32, the compiled step sees bf16 — no
            hand-managed casts."""

            def __init__(self, **kw):
                super().__init__(**kw)
                from mxtpu import initializer
                self.inv_std = self.params.get(
                    "inv_std", shape=(1,),
                    init=initializer.Constant(1.0 / 57.7),
                    grad_req="null")

            def hybrid_forward(self, F, x, inv_std):
                return (x.astype(str(inv_std.dtype)) - 114.8) * inv_std

        net = nn.HybridSequential(prefix="pipe_")
        net.add(_DeviceNormalize(), resnet50(classes=1000))
        net.initialize(init="xavier")
        step = parallel.build_train_step(
            net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
            compute_dtype=(compute_dtype if compute_dtype != "float32"
                           else None),
            cast_batch=False)

        # host_batches=True: the worker thread hands raw numpy across
        # the queue; the single device_put per array happens one batch
        # ahead in DeviceFeedIter, overlapping the running step
        it = ImageRecordIter(prefix + ".rec", (3, 224, 224), batch_size,
                             path_imgidx=prefix + ".idx", shuffle=True,
                             rand_mirror=True, raw_records=True,
                             dtype="uint8", preprocess_threads=2,
                             host_batches=True)
        feed = DeviceFeedIter(PrefetchingIter(it))

        def batches():
            while True:
                try:
                    yield feed.next()
                except StopIteration:
                    feed.reset()

        stream = batches()
        loss = None
        for _ in range(warmup):  # includes the compile
            b = next(stream)
            loss = step(b.data[0], b.label[0])
        float(loss.asnumpy().mean())
        vals = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(iters):
                b = next(stream)
                loss = step(b.data[0], b.label[0])  # async dispatch
            float(loss.asnumpy().mean())  # sync
            vals.append(batch_size * iters /
                        (time.perf_counter() - t0))
            # stop, don't die: the next repeat must fit what is left
            # of this row's budget (r5's rc=124 lesson)
            spent = time.perf_counter() - t_row
            if spent + (time.perf_counter() - t0) > row_budget:
                break
        vals.sort()
        median = vals[len(vals) // 2] if len(vals) % 2 else \
            0.5 * (vals[len(vals) // 2 - 1] + vals[len(vals) // 2])
        mem = step.last_memory_analysis()
        stats = {"best": max(vals), "median": median, "n": len(vals),
                 "spread": round((max(vals) - min(vals)) / median, 4),
                 "runs": [round(v, 1) for v in vals],
                 "info": {"hbm_peak": mem["hbm_peak"] if mem
                          else None}}
        return stats, _METRIC_NAMES["resnet50_pipeline"], "samples/sec"
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_bert(batch_size=32, seq_len=128, warmup=3, iters=20,
               metric_key="bert", amp=None):
    """BERT-Large MLM-style training step, tokens/sec (north-star #2).
    bf16 compute by default (set MXTPU_BENCH_DTYPE= to override);
    ``amp=True`` takes the ``mxtpu.amp`` path instead."""
    from mxtpu import nd
    from mxtpu import parallel
    from mxtpu.gluon import loss as gloss
    from mxtpu.models.transformer import bert_large

    net = bert_large(vocab_size=30522, max_length=seq_len, dropout=0.1)
    net.initialize(init="xavier")
    dtype = None if amp else knobs.get("MXTPU_BENCH_DTYPE") or None

    def mlm_loss(pred, y):
        V = 30522
        return gloss.SoftmaxCrossEntropyLoss()(
            pred.reshape((-1, V)), y.reshape((-1,)))

    # cast_batch=False: token ids must not be rounded through bf16
    step = parallel.build_train_step(
        net, mlm_loss, "adam", {"learning_rate": 1e-4},
        compute_dtype=dtype, cast_batch=False, amp=amp)
    rng = np.random.RandomState(0)
    toks = nd.array(rng.randint(0, 30522, (batch_size, seq_len))
                    .astype(np.float32))
    tokens_per_batch = batch_size * seq_len
    value = _measure(step, toks, toks, warmup, iters, tokens_per_batch)
    return value, _METRIC_NAMES[metric_key], "tokens/sec"


def bench_transformer(batch_size=16, src_len=64, tgt_len=64, warmup=3,
                      iters=16, amp=None):
    """Transformer-big WMT-shaped seq2seq training step, tokens/sec
    over src+tgt tokens (north-star #4 / M6 bench presence).  Sized to
    fit the wall budget: b16 s64/s64 keeps the compile + 5 measurement
    repeats inside the row estimate while every GEMM is already
    MXU-shaped (the per-token cost is sequence-length-flat until
    attention dominates)."""
    from mxtpu import nd
    from mxtpu import parallel
    from mxtpu.gluon import loss as gloss
    from mxtpu.gluon.block import HybridBlock
    from mxtpu.models.transformer import transformer_big

    V = 32768

    class _MTWrap(HybridBlock):
        """TrainStep feeds ONE batch array: src|tgt ride concatenated
        on the time axis and split here."""

        def __init__(self, split, **kw):
            super().__init__(**kw)
            self._split = split
            self.model = transformer_big(vocab_size=V, max_length=256,
                                         dropout=0.1)

        def hybrid_forward(self, F, x):
            src = F.slice_axis(x, axis=1, begin=0, end=self._split)
            tgt = F.slice_axis(x, axis=1, begin=self._split, end=None)
            return self.model(src, tgt)

    net = _MTWrap(src_len)
    net.initialize(init="xavier")
    dtype = None if amp else knobs.get("MXTPU_BENCH_DTYPE") or None

    def mt_loss(pred, y):
        return gloss.SoftmaxCrossEntropyLoss()(
            pred.reshape((-1, V)), y.reshape((-1,)))

    # cast_batch=False: token ids must not be rounded through bf16
    step = parallel.build_train_step(
        net, mt_loss, "adam", {"learning_rate": 1e-4},
        compute_dtype=dtype, cast_batch=False, amp=amp)
    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, V, (batch_size, src_len + tgt_len))
                 .astype(np.float32))
    y = nd.array(rng.randint(0, V, (batch_size, tgt_len))
                 .astype(np.float32))
    tokens_per_batch = batch_size * (src_len + tgt_len)
    value = _measure(step, x, y, warmup, iters, tokens_per_batch)
    return value, _METRIC_NAMES["transformer"], "tokens/sec"


def bench_ssd(batch_size=8, size=300, num_classes=20, warmup=3,
              iters=10):
    """SSD-300 VOC-shaped training throughput (VERDICT r5 item 5): the
    full detection step — backbone, multi-scale heads, MultiBoxTarget
    assignment, SSDLoss — compiled via build_train_step on synthetic
    VOC-shaped batches (3x300x300, up to 3 boxes/image)."""
    from mxtpu import nd
    from mxtpu import parallel
    from mxtpu.models.ssd import SSDLoss, ssd_300

    net = ssd_300(num_classes=num_classes)
    net.initialize(init="xavier")
    loss_fn = SSDLoss()

    def det_loss(pred, labels):
        anchors, cls_preds, box_preds = pred
        bt, bm, ct = nd.MultiBoxTarget(anchors, labels, cls_preds)
        return nd.mean(loss_fn(cls_preds, box_preds, ct, bt, bm))

    # cast_batch only touches x (the image) — labels reach
    # MultiBoxTarget in f32, so class ids and box coords never round
    # through bf16
    step = parallel.build_train_step(
        net, det_loss, "sgd",
        {"learning_rate": 5e-3, "momentum": 0.9, "wd": 5e-4},
        compute_dtype=knobs.get("MXTPU_BENCH_DTYPE") or None)
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(batch_size, 3, size, size)
                 .astype(np.float32))
    # VOC-shaped labels: (B, 3, 5) [cls, x0, y0, x1, y1], -1 pads
    labels = np.full((batch_size, 3, 5), -1.0, np.float32)
    for b in range(batch_size):
        for o in range(1 + b % 3):
            x0, y0 = rng.uniform(0, 0.6, 2)
            labels[b, o] = [rng.randint(num_classes), x0, y0,
                            x0 + rng.uniform(0.2, 0.4),
                            y0 + rng.uniform(0.2, 0.4)]
    y = nd.array(labels)
    value = _measure(step, x, y, warmup, iters, batch_size)
    return value, _METRIC_NAMES["ssd"], "samples/sec"


def bench_moe_ffn(T=8192, E=8, D=1024, H=4096, warmup=2, iters=8,
                  repeats=3):
    """Switch-MoE FFN microbench at the honesty point VERDICT r5
    item 6 names: T=8192 tokens, E=8 experts, D=1024, bf16, fwd+bwd.
    Reports tokens/sec plus ``details``: the dense-FFN equivalent
    (same D→H→D at the same token count), HBM high-water from XLA's
    memory_analysis, and the router+dispatch share (time not spent in
    the expert GEMMs themselves, measured by running the expert FFN on
    pre-dispatched (E, C, D) activations)."""
    import jax
    import jax.numpy as jnp

    from mxtpu.parallel.moe import MoEFFN

    layer = MoEFFN(D, H, E, capacity_factor=1.25)
    params = layer.params()
    C = int(np.ceil(T / E * 1.25))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(T, D).astype(np.float32),
                    dtype=jnp.bfloat16)

    def _chain(fn, x0, n, label):
        """Sustained fwd+bwd: each iteration's grad signal feeds the
        next input, so nothing is DCE'd or hoisted
        (tools/microbench.py methodology)."""
        grad_fn = jax.grad(
            lambda xx: fn(xx).astype(jnp.float32).sum() * 1e-3)

        @jax.jit
        def run(xx):
            return jax.lax.fori_loop(
                0, n, lambda i, v: (v + 1e-3 * grad_fn(v)
                                    .astype(v.dtype)), xx)

        out = run(x0)
        float(jnp.sum(out.astype(jnp.float32)))  # compile + drain
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = run(x0)
            float(jnp.sum(out.astype(jnp.float32)))
            best = min(best, (time.perf_counter() - t0) / n)
        # HBM high-water of the compiled loop program through the ONE
        # memflow analyzer (``hbm`` keeps the historical
        # temp+arg+output accounting; ``peak`` is the sweep-wide
        # hbm_peak convention, temp+arg only)
        from mxtpu import analysis
        try:
            mem = analysis.mem_stats(run.lower(x0).compile())
        except Exception:
            mem = None
        if mem is None:
            hbm = peak = None
        else:
            hbm = mem["hbm_peak"] + mem.get("output_size_in_bytes", 0)
            peak = mem["hbm_peak"]
        return best, hbm, peak

    def moe_out(xx):
        # aux (load-balance loss) is dropped: the router itself stays
        # live through the dispatch/combine einsums y depends on
        return layer.apply(params, xx)[0]

    t_moe, hbm_moe, peak_moe = _chain(moe_out, x, iters, "moe")

    # dense-FFN equivalent: one D→H→D over the same tokens
    k = jax.random.PRNGKey(1)
    w1 = (jax.random.normal(k, (D, H)) / np.sqrt(D)).astype(jnp.bfloat16)
    w2 = (jax.random.normal(k, (H, D)) / np.sqrt(H)).astype(jnp.bfloat16)
    t_dense, hbm_dense, _ = _chain(
        lambda xx: jax.nn.relu(xx @ w1) @ w2, x, iters, "dense")

    # experts-only: the same per-expert GEMMs on pre-dispatched
    # activations — the difference to t_moe is the router + the two
    # dispatch/combine einsums
    _, w1e, b1e, w2e, b2e = params
    w1c = w1e.astype(jnp.bfloat16)
    w2c = w2e.astype(jnp.bfloat16)
    xe = jnp.asarray(rng.randn(E, C, D).astype(np.float32),
                     dtype=jnp.bfloat16)

    def experts_only(v):
        h = jnp.einsum("ecd,edh->ech", v, w1c) \
            + b1e.astype(jnp.bfloat16)[:, None, :]
        return jnp.einsum("ech,ehd->ecd", jax.nn.relu(h), w2c) \
            + b2e.astype(jnp.bfloat16)[:, None, :]

    t_exp, _, _ = _chain(experts_only, xe, iters, "experts")

    vals = [T / t_moe]
    stats = {"best": max(vals), "median": vals[0], "n": 1,
             "spread": 0.0, "runs": [round(v, 1) for v in vals],
             "info": {
                 "hbm_peak": peak_moe,
                 "shape": {"T": T, "E": E, "D": D, "H": H,
                           "capacity": C, "dtype": "bfloat16"},
                 "dense_ffn_tokens_per_sec": round(T / t_dense, 1),
                 "vs_dense_ffn": round(t_dense / t_moe, 3),
                 "hbm_highwater_bytes": hbm_moe,
                 "dense_hbm_highwater_bytes": hbm_dense,
                 "router_dispatch_share": round(
                     max(0.0, (t_moe - t_exp)) / t_moe, 3),
             }}
    return stats, _METRIC_NAMES["moe_ffn"], "tokens/sec"


def bench_bert_zero(batch_size=32, seq_len=128, warmup=2, iters=8,
                    amp=None):
    """ZeRO-1 ablation (on-demand, MXTPU_BENCH_MODEL=bert_zero): the
    BERT-Large adam step replicated vs ZeRO-1 sharded optimizer states
    (``mxtpu.parallel`` TrainStep docs) on a dp mesh over every local
    device, dp = min(8, devices).  The primary value is the ZeRO
    variant's tokens/sec when a dp mesh exists (else the replicated
    number); ``details`` carries both variants' step rates and
    per-device optimizer-state bytes.  When fewer than 8 devices are
    attached the dp=8 footprint is additionally PLANNED from
    ``plan_zero_buckets`` geometry — pure arithmetic, the same
    provenance as BASELINE.md's optimizer-memory table."""
    import jax

    from mxtpu import nd
    from mxtpu import parallel
    from mxtpu.gluon import loss as gloss
    from mxtpu.models.transformer import bert_large

    V = 30522
    dtype = None if amp else knobs.get("MXTPU_BENCH_DTYPE") or None
    rng = np.random.RandomState(0)
    toks = nd.array(rng.randint(0, V, (batch_size, seq_len))
                    .astype(np.float32))
    tokens_per_batch = batch_size * seq_len

    def mlm_loss(pred, y):
        return gloss.SoftmaxCrossEntropyLoss()(
            pred.reshape((-1, V)), y.reshape((-1,)))

    def _variant(mesh, zero):
        net = bert_large(vocab_size=V, max_length=seq_len, dropout=0.1)
        net.initialize(init="xavier")
        step = parallel.build_train_step(
            net, mlm_loss, "adam", {"learning_rate": 1e-4}, mesh=mesh,
            compute_dtype=dtype, cast_batch=False, zero=zero, amp=amp)
        stats = _measure(step, toks, toks, warmup, iters,
                         tokens_per_batch, repeats=3)
        return stats, step

    dp = min(8, jax.device_count())
    repl, rstep = _variant(None, None)
    info = {
        "dp": dp,
        "hbm_peak": (repl.get("info") or {}).get("hbm_peak"),
        "replicated_hbm_peak": (repl.get("info") or {}).get("hbm_peak"),
        "replicated_tokens_per_sec": round(repl["best"], 1),
        "replicated_opt_state_bytes": rstep.opt_state_bytes(),
    }
    stats = repl
    if dp > 1:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:dp]), ("dp",))
        zstats, zstep = _variant(mesh, 1)
        info.update({
            # hbm_peak reports the primary (ZeRO) program
            "hbm_peak": (zstats.get("info") or {}).get("hbm_peak"),
            "zero_tokens_per_sec": round(zstats["best"], 1),
            "zero_opt_state_bytes_per_device": zstep.opt_state_bytes(),
            "zero_vs_replicated": round(zstats["best"] / repl["best"],
                                        3),
        })
        stats = zstats
    if dp < 8:
        from mxtpu.analysis import memflow
        sigs = [(tuple(rstep._params[i]._data._data.shape),
                 str(rstep._params[i]._data._data.dtype))
                for i in rstep._train_idx]
        # adam: two f32 state leaves (m, v) per bucket, dp-sharded —
        # the same plan_zero_buckets oracle the mem ledgers commit
        info["zero_dp8_planned_opt_state_bytes_per_device"] = \
            memflow.planned_shard_bytes(sigs, 8)
    stats = dict(stats)
    stats["info"] = info
    return stats, _METRIC_NAMES["bert_zero"], "tokens/sec"


def _contract_comm_bytes():
    """Reduce-scatter/all-gather byte counts from the committed
    bert_zero contracts — the f32 program's compiled collectives vs
    the AMP program's AS-WRITTEN collectives (the CPU backend's
    float-normalization pass rewrites bf16 collectives back to f32 in
    compiled text, so the as-written level is where the wire dtype
    lives; see tools/hlocheck/targets.py::bert_zero_amp).  These are
    the tiny pinned stand-in programs, not the bench model — the
    RATIO is the scale-invariant contract property being reported."""
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        with open(os.path.join(here, "contracts",
                               "bert_zero.json")) as f:
            f32 = json.load(f)["programs"]["train_step"]["collectives"]
        with open(os.path.join(here, "contracts",
                               "bert_zero_amp.json")) as f:
            amp = json.load(f)["programs"]["train_step_as_written"][
                "collectives"]
    except (OSError, KeyError, ValueError):
        return None
    rs_f, rs_a = f32["reduce-scatter"], amp["reduce-scatter"]
    return {"f32_reduce_scatter_bytes": rs_f["bytes"],
            "amp_reduce_scatter_bytes": rs_a["bytes"],
            "reduce_scatter_bytes_ratio": round(
                rs_a["bytes"] / rs_f["bytes"], 3),
            "f32_all_gather_bytes": f32["all-gather"]["bytes"],
            "amp_all_gather_bytes": amp["all-gather"]["bytes"],
            "counts_equal": rs_f["count"] == rs_a["count"]}


def bench_amp_pair(key, base_fn, **kw):
    """One --amp row: the base workload measured twice — AMP off
    (the row's existing recipe) and AMP on (``mxtpu.amp``: bf16
    storage + autocast + f32 masters + loss scaling) — reported side
    by side.  The primary value is the AMP-on rate; ``details``
    carries both variants' rate/MFU/HBM and, for the ZeRO pair, the
    contract-pinned comm-byte split."""
    off, _, unit = base_fn(amp=None, **kw)
    on, _, _ = base_fn(amp=True, **kw)
    peak = _peak_flops()
    base_key = key[: -len("_amp")]

    def _side(stats):
        return {"best": round(stats["best"], 1),
                "median": round(stats["median"], 1),
                "mfu": _mfu(base_key, stats["best"], peak),
                "hbm_peak": (stats.get("info") or {}).get("hbm_peak")}

    info = dict(on.get("info") or {})
    info.update({
        "amp_off": _side(off), "amp_on": _side(on),
        "amp_speedup": round(on["best"] / off["best"], 3),
    })
    if base_key == "bert_zero":
        comm = _contract_comm_bytes()
        if comm:
            info["comm_contract"] = comm
    stats = dict(on)
    stats["info"] = info
    return stats, _METRIC_NAMES[key], unit


def bench_serving_bert(seq_len=64, max_batch=8, repeats=3):
    """mxtpu.serving end-to-end row (on-demand,
    MXTPU_BENCH_MODEL=serving_bert): a small exported BERT behind
    ``InferenceServer`` under OPEN-LOOP arrival (requests submitted on
    a fixed schedule regardless of completions — the serving-honest
    load model; a closed loop self-throttles and hides queueing).

    The primary value is sustained served req/sec at saturation
    (offered 1.5x the raw AOT back-to-back capacity of the largest
    bucket, single-length traffic), best of ``repeats`` — the number
    the within-15%-of-raw acceptance check in BASELINE.md reads.
    ``details`` carries the raw back-to-back rate, served/raw ratio,
    and a mixed-length latency sweep at two sub-saturation arrival
    rates with p50/p95/p99, batch fill-rate and peak queue depth."""
    import tempfile
    import threading  # noqa: F401 — server worker threads

    from mxtpu import nd
    from mxtpu.models.transformer import BERTModel
    from mxtpu.serving import InferenceServer, ModelRunner, ServerBusy

    V = 8192
    net = BERTModel(V, 256, 1024, 4, 4, max_length=seq_len,
                    dropout=0.0)
    net.initialize(init="xavier")
    rng = np.random.RandomState(0)
    net(nd.array(rng.randint(0, V, (1, seq_len))
                 .astype(np.float32)))          # materialize params
    d = tempfile.mkdtemp(prefix="mxtpu_bench_rec_serving_")
    sym_file, param_file = net.export(os.path.join(d, "bert"))
    runner = ModelRunner.from_export(
        sym_file, param_file, input_specs={"data": (None,)},
        seq_buckets=[seq_len // 2, seq_len], max_batch_size=max_batch)
    t0 = time.perf_counter()
    runner.warmup()
    compile_s = time.perf_counter() - t0

    # raw AOT back-to-back capacity of the saturation bucket — the
    # denominator of the batcher-overhead acceptance check
    bucket = (max_batch, seq_len)
    full = [{"data": rng.randint(0, V, (seq_len,)).astype(np.float32)}
            for _ in range(max_batch)]
    vals = runner._pad_stack(full, bucket)
    np.asarray(runner.run_raw(vals, bucket)[0])       # settle
    raw_iters = 30
    t0 = time.perf_counter()
    for _ in range(raw_iters):
        outs = runner.run_raw(vals, bucket)
    np.asarray(outs[0])                               # sync
    raw_rps = max_batch * raw_iters / (time.perf_counter() - t0)

    def open_loop(offered_rps, lens, n_req, timeout_s=None):
        """One fresh endpoint, ``n_req`` arrivals at 1/offered_rps
        spacing; returns (served_rps, stats snapshot, rejected)."""
        payloads = [rng.randint(0, V, (lens[i % len(lens)],))
                    .astype(np.float32) for i in range(n_req)]
        interval = 1.0 / offered_rps
        with InferenceServer() as server:
            server.register("bert", runner, max_queue_delay_us=2000)
            reqs, rejected = [], 0
            t_start = time.perf_counter()
            for i, row in enumerate(payloads):
                lag = t_start + i * interval - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                try:
                    reqs.append(server.submit(
                        "bert", {"data": row}, timeout_s=timeout_s))
                except ServerBusy:
                    rejected += 1   # load shed at the edge, open loop
            done = 0
            for r in reqs:
                try:
                    r.result(timeout=60.0)
                    done += 1
                except Exception:   # noqa: BLE001 — timeouts counted
                    pass            # via the endpoint snapshot
            served = done / (time.perf_counter() - t_start)
            for _ in range(200):    # let worker counters settle
                snap = server.stats("bert")
                if snap["completed"] >= done:
                    break
                time.sleep(0.01)
        return served, snap, rejected

    # -- mixed-length latency sweep at two sub-saturation rates --------
    sweep_lens = [int(v) for v in
                  rng.randint(seq_len // 4, seq_len + 1, 64)]
    sweep = {}
    for frac in (0.25, 0.5):
        offered = max(frac * raw_rps, 10.0)
        n_req = int(min(600, max(60, offered * 2.0)))
        served, snap, rejected = open_loop(offered, sweep_lens, n_req)
        sweep[f"offered_{frac:.2f}x_raw"] = {
            "offered_rps": round(offered, 1),
            "served_rps": round(served, 1),
            "p50_ms": snap["latency_ms"]["p50"],
            "p95_ms": snap["latency_ms"]["p95"],
            "p99_ms": snap["latency_ms"]["p99"],
            "batch_fill_rate": snap["batch_fill_rate"],
            "mean_batch_size": snap["mean_batch_size"],
            "peak_queue_depth": snap["peak_queue_depth"],
            "rejected": rejected,
            "timed_out": snap["timed_out"],
        }

    # -- saturation: sustained server throughput vs raw AOT ------------
    sat_vals, sat_snap = [], None
    for _ in range(repeats):
        offered = 1.5 * raw_rps
        n_req = int(min(2000, max(120, raw_rps * 1.5)))
        served, sat_snap, _ = open_loop(offered, [seq_len], n_req)
        sat_vals.append(served)
    sat_vals.sort()
    median = sat_vals[len(sat_vals) // 2] if len(sat_vals) % 2 else \
        0.5 * (sat_vals[len(sat_vals) // 2 - 1]
               + sat_vals[len(sat_vals) // 2])
    stats = {
        "best": max(sat_vals), "median": median, "n": len(sat_vals),
        "spread": round((max(sat_vals) - min(sat_vals)) / median, 4),
        "runs": [round(v, 1) for v in sat_vals],
        "info": {
            "hbm_peak": None,   # inference path; no scan program
            "raw_back_to_back_rps": round(raw_rps, 1),
            "served_vs_raw": round(max(sat_vals) / raw_rps, 4),
            "saturated_fill_rate": sat_snap["batch_fill_rate"],
            "saturated_peak_queue_depth": sat_snap["peak_queue_depth"],
            "compile_seconds_total": round(compile_s, 2),
            "compiled_buckets": runner.num_compiled(),
            "max_batch_size": max_batch,
            "seq_buckets": list(runner.seq_buckets),
            "weight_mb": round(runner.weight_bytes() / 2 ** 20, 1),
            "arrival_sweep": sweep,
        },
    }
    return stats, _METRIC_NAMES["serving_bert"], "req/sec"


def bench_serving_fleet(n_workers=3, n_req=600, repeats=3):
    """Fault-tolerant fleet soak row (on-demand,
    MXTPU_BENCH_MODEL=serving_fleet): open-loop traffic against a
    :class:`FleetRouter` over ``n_workers`` workers while one worker
    is KILLED mid-run (preemption) and a warm replacement is attached
    from the victim's compiled-ladder handoff.

    The acceptance contract (ISSUE 7): ZERO in-deadline requests
    dropped or hanging across the kill/restart — every submitted
    request either completes with a correct result or fails its own
    deadline, none blocks forever.  The primary value is sustained
    served req/sec THROUGH the failure; ``details`` carries
    p50/p95/p99 end-to-end latency and the recovery counters
    (retries, requeues, deaths, drains) the router aggregates."""
    from mxtpu import obs
    from mxtpu import symbol as sym
    from mxtpu.serving import (FleetRouter, FleetWorker, ModelRunner,
                               RequestTimeout)

    dim, max_batch = 64, 8
    w = np.arange(1, dim + 1, dtype=np.float32)
    rng = np.random.RandomState(0)

    import jax
    devices = jax.devices()

    def make_runner(i=0):
        # one replica per chip: a runner built without device= lands
        # on chip 0 whatever the fleet size
        return ModelRunner(sym.var("data") * sym.var("w"), {"w": w},
                           {"data": (dim,)}, max_batch_size=max_batch,
                           device=devices[i % len(devices)])

    # raw capacity of one worker's saturation bucket: sets the offered
    # rate so the fleet runs loaded but not in permanent shed
    probe = make_runner()
    bucket = (max_batch, None)
    rows = [{"data": rng.rand(dim).astype(np.float32)}
            for _ in range(max_batch)]
    vals = probe._pad_stack(rows, bucket)
    np.asarray(probe.run_raw(vals, bucket)[0])        # compile+settle
    t0 = time.perf_counter()
    raw_iters = 50
    for _ in range(raw_iters):
        outs = probe.run_raw(vals, bucket)
    np.asarray(outs[0])
    raw_rps = max_batch * raw_iters / (time.perf_counter() - t0)

    def soak():
        canary = {"data": np.ones(dim, np.float32)}
        router = FleetRouter(threaded=True, tick_s=0.002,
                             canary=canary, canary_expect=[w.copy()],
                             canary_interval_s=0.25,
                             canary_timeout_s=2.0)
        offered = min(0.5 * n_workers * raw_rps, 4000.0)
        interval = 1.0 / offered
        kill_at, replace_at = n_req // 3, n_req // 2
        # sampler-overhead row (ISSUE 14): when obs is on the soak
        # runs with the full operator stack live — 100 Hz sampler +
        # availability SLO ticking inside the router loop.  Under
        # MXTPU_OBS=0 both factories hand back the shared no-ops and
        # attach_slo refuses them, so that run is the control.
        eng = obs.slo_engine(
            [obs.AvailabilitySLO("fleet_avail", objective=0.999)],
            obs.sampler(period_us=10_000.0))
        router.attach_slo(eng)
        with router:
            for i in range(n_workers):
                router.add_worker(FleetWorker(
                    make_runner(i), f"w{i}",
                    max_queue_delay_us=2000.0))
            handoff = router._workers["w0"].handoff()
            reqs, vecs = [], []
            t_start = time.perf_counter()
            for i in range(n_req):
                lag = t_start + i * interval - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                if i == kill_at:
                    router.kill("w0")                 # preemption
                if i == replace_at:
                    router.add_worker(FleetWorker(
                        make_runner(), "wR",
                        max_queue_delay_us=2000.0), warm_from=handoff)
                vec = rng.rand(dim).astype(np.float32)
                vecs.append(vec)
                reqs.append(router.submit({"data": vec},
                                          timeout_s=30.0))
            done, dropped, hung, wrong = 0, 0, 0, 0
            for vec, r in zip(vecs, reqs):
                try:
                    out = r.result(timeout=30.0)[0]
                    done += 1
                    if not np.allclose(out, vec * w, rtol=1e-5):
                        wrong += 1
                except RequestTimeout:
                    hung += 1      # result() wait expired = a hang
                except Exception:  # noqa: BLE001 — anything terminal
                    dropped += 1   # inside the 30s deadline = a drop
            served = done / (time.perf_counter() - t_start)
            snap = router.fleet_stats()
        return served, snap, dropped, hung, wrong

    vals_run, last = [], None
    dropped = hung = wrong = 0
    for _ in range(repeats):
        served, last, d, h, wr = soak()
        vals_run.append(served)
        dropped += d
        hung += h
        wrong += wr
    vals_run.sort()
    median = vals_run[len(vals_run) // 2] if len(vals_run) % 2 else \
        0.5 * (vals_run[len(vals_run) // 2 - 1]
               + vals_run[len(vals_run) // 2])
    ex = last["extras"]
    stats = {
        "best": max(vals_run), "median": median, "n": len(vals_run),
        "spread": round((max(vals_run) - min(vals_run)) / median, 4),
        "runs": [round(v, 1) for v in vals_run],
        "info": {
            "hbm_peak": None,      # inference path; no scan program
            "in_deadline_dropped": dropped,   # the contract: all zero
            "in_deadline_hung": hung,
            "wrong_results": wrong,
            "p50_ms": last["latency_ms"]["p50"],
            "p95_ms": last["latency_ms"]["p95"],
            "p99_ms": last["latency_ms"]["p99"],
            "retries": ex.get("retries", 0),
            "requeues": ex.get("requeues", 0),
            "deaths": ex.get("deaths", 0),
            "hedges_won": ex.get("hedges_won", 0),
            "timed_out": last["timed_out"],
            "workers": {n: s["state"]
                        for n, s in last["workers"].items()},
            "raw_back_to_back_rps": round(raw_rps, 1),
            "n_workers": n_workers,
            "n_req_per_run": n_req,
            "obs_live": bool(obs.enabled()),   # sampler+SLO attached?
        },
    }
    return stats, _METRIC_NAMES["serving_fleet"], "req/sec"


def bench_serving_autoscale(n_burst=480, repeats=3):
    """Fleet control-plane row (on-demand,
    MXTPU_BENCH_MODEL=serving_autoscale): a traffic burst against
    (a) a STATIC single-worker fleet and (b) the same fleet with an
    :class:`Autoscaler` (min=1, max=3) driven by the router tick, both
    with predictive admission control on.  The contract (ISSUE 11):
    the autoscaled fleet absorbs the burst inside the SLO that the
    static fleet provably cannot meet, sheds nothing, and every
    replica comes up warm from the donor's compiled-ladder handoff.

    Vehicle: per-batch service time is scripted through the fault
    harness (``SlowExec(service_s, time.sleep)`` — the same injector
    tier-1 recovery tests use) because worker replicas only buy wall
    time when service parallelizes, and on a 1-core CPU box real
    compute cannot.  Sleeps do.  Everything else is real: the
    measured absorb time includes genuine scale-up reaction latency,
    replica ladder compiles, dispatch, retry and admission decisions.
    The primary value is the autoscaled fleet's burst absorb rate
    (served req/sec over the time for ALL submitted requests to reach
    a terminal state); ``details`` carries the static-N comparison —
    absorb seconds, SLO violation rate (timeouts), admission-shed
    counts — plus the scale-up count and warm-compile evidence."""
    from mxtpu import symbol as sym
    from mxtpu.serving import (Autoscaler, FaultPlan, FleetRouter,
                               FleetWorker, ModelRunner, ServerBusy,
                               SlowExec)

    dim, max_batch = 64, 8
    service_s = 0.02           # scripted per-batch service time
    w = np.arange(1, dim + 1, dtype=np.float32)
    rng = np.random.RandomState(0)

    # the burst floor: a single worker needs at least this long
    static_floor = (n_burst + max_batch - 1) // max_batch * service_s
    slo_s = 0.6 * static_floor      # feasible only by scaling out
    submit_window = 0.25 * static_floor   # paced, not instantaneous —
    # later submissions see a live ETA, so admission has signal

    import itertools
    import jax
    devices = jax.devices()
    born = itertools.count()

    def make_worker(name):
        # replicas spread over the chips in birth order: a runner
        # built without device= lands on chip 0 whatever the fleet size
        runner = ModelRunner(sym.var("data") * sym.var("w"), {"w": w},
                             {"data": (dim,)},
                             max_batch_size=max_batch,
                             device=devices[next(born) % len(devices)])
        return FleetWorker(runner, name, max_queue_delay_us=2000.0,
                           faults=FaultPlan(
                               SlowExec(service_s, time.sleep)))

    def run(autoscale):
        router = FleetRouter(threaded=True, tick_s=0.002, canary=None,
                             admission=True, admission_margin=1.0)
        shed = 0
        with router:
            w0 = make_worker("w0")
            router.add_worker(w0)
            w0.runner.warmup()
            scaler = None
            if autoscale:
                scaler = Autoscaler(
                    router, make_worker, min_workers=1, max_workers=3,
                    up_depth=2.0 * max_batch, down_depth=0.5,
                    breach_ticks=2, cooldown_s=0.05)
                router.add_controller(scaler.tick)
            interval = submit_window / n_burst
            reqs = []
            t0 = time.perf_counter()
            for i in range(n_burst):
                lag = t0 + i * interval - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                try:
                    reqs.append(router.submit(
                        {"data": rng.rand(dim).astype(np.float32)},
                        timeout_s=slo_s))
                except ServerBusy:
                    shed += 1
            served, violated = 0, 0
            for r in reqs:
                try:
                    r.result(timeout=slo_s + 10.0)
                    served += 1
                except Exception:  # noqa: BLE001 — timeout = SLO miss
                    violated += 1
            absorb = time.perf_counter() - t0
            members = router.members()
            cold = sum(1 for m in members
                       if m.runner.num_compiled()
                       < w0.runner.num_compiled())
            snap = router.fleet_stats()
        ex = snap["extras"]
        return {
            "absorb_s": round(absorb, 3),
            "served": served,
            "slo_violations": violated,
            "shed_admission": shed + ex.get("shed_admission", 0),
            "shed_backlog": ex.get("shed_backlog", 0),
            "n_workers_final": len(members),
            "cold_replicas": cold,
            "scale_ups": scaler.snapshot()["scale_ups"]
            if scaler else 0,
        }

    vals_run, statics, autos = [], [], []
    for _ in range(repeats):
        statics.append(run(autoscale=False))
        a = run(autoscale=True)
        autos.append(a)
        vals_run.append(a["served"] / a["absorb_s"])
    vals_run.sort()
    median = vals_run[len(vals_run) // 2] if len(vals_run) % 2 else \
        0.5 * (vals_run[len(vals_run) // 2 - 1]
               + vals_run[len(vals_run) // 2])
    mid_s = sorted(statics, key=lambda d: d["absorb_s"])[len(statics)
                                                        // 2]
    mid_a = sorted(autos, key=lambda d: d["absorb_s"])[len(autos) // 2]
    stats = {
        "best": max(vals_run), "median": median, "n": len(vals_run),
        "spread": round((max(vals_run) - min(vals_run)) / median, 4),
        "runs": [round(v, 1) for v in vals_run],
        "info": {
            "hbm_peak": None,       # inference path; no scan program
            "n_burst": n_burst,
            "service_s_per_batch": service_s,
            "slo_s": round(slo_s, 3),
            "static_floor_s": round(static_floor, 3),
            "static": mid_s,        # median-absorb static run
            "autoscaled": mid_a,    # median-absorb autoscaled run
            "absorb_speedup": round(
                mid_s["absorb_s"] / mid_a["absorb_s"], 2),
            "static_slo_violation_rate": round(
                (mid_s["slo_violations"] + mid_s["shed_admission"])
                / n_burst, 4),
            "auto_slo_violation_rate": round(
                (mid_a["slo_violations"] + mid_a["shed_admission"])
                / n_burst, 4),
        },
    }
    return stats, _METRIC_NAMES["serving_autoscale"], "req/sec"


def bench_serving_coldstart(seq_len=64, max_batch=8, repeats=2):
    """Persistent compile-cache row (on-demand,
    MXTPU_BENCH_MODEL=serving_coldstart): the cold vs disk-warmed
    cold-start split (ISSUE 13).  A small exported BERT's full bucket
    ladder is warmed twice — once against an empty cache root (every
    bucket is an XLA compile + a store) and once as a fresh runner
    against the now-populated root (every bucket is a verified disk
    load, ``num_compiled`` asserted zero-compile) — plus the
    operator-facing number: time-to-first-served-request for a fresh
    process in each mode.

    The primary value is the full-ladder warmup speedup (cold seconds
    / disk-warmed seconds, best of ``repeats``); ``details`` carries
    the four raw timings BASELINE.md splits out."""
    import shutil
    import tempfile

    from mxtpu import nd
    from mxtpu.cache import ExecutableCache
    from mxtpu.models.transformer import BERTModel
    from mxtpu.serving import ModelRunner

    V = 8192
    net = BERTModel(V, 128, 512, 2, 2, max_length=seq_len,
                    dropout=0.0)
    net.initialize(init="xavier")
    rng = np.random.RandomState(0)
    net(nd.array(rng.randint(0, V, (1, seq_len))
                 .astype(np.float32)))          # materialize params
    d = tempfile.mkdtemp(prefix="mxtpu_bench_rec_coldstart_")
    sym_file, param_file = net.export(os.path.join(d, "bert"))

    def make_runner(root):
        return ModelRunner.from_export(
            sym_file, param_file, input_specs={"data": (None,)},
            seq_buckets=[seq_len], max_batch_size=max_batch,
            cache=ExecutableCache(root))

    req = [{"data": rng.randint(0, V, (seq_len,))
            .astype(np.float32)}]

    def first_request_s(runner):
        bucket = runner.bucket_for(1, seq_len)
        vals = runner._pad_stack(req, bucket)
        t0 = time.perf_counter()
        np.asarray(runner.run_raw(vals, bucket)[0])
        return time.perf_counter() - t0

    runs = []
    for _ in range(repeats):
        root = os.path.join(d, f"cache{len(runs)}")
        cold = make_runner(root)
        t0 = time.perf_counter()
        cold.warmup()
        cold_warmup_s = time.perf_counter() - t0
        nbuckets = len(cold.buckets())
        assert cold.num_compiled() == nbuckets

        # a second fresh "process" against the populated root: the
        # whole ladder must come off disk with zero XLA compiles
        warm = make_runner(root)
        t0 = time.perf_counter()
        warm.warmup()
        warm_warmup_s = time.perf_counter() - t0
        assert warm.num_compiled() == nbuckets
        assert warm._cache.stats()["hit"] == nbuckets, \
            warm._cache.stats()

        # operator number: first served request, fresh runner each
        cold_first = make_runner(os.path.join(d, f"cachef{len(runs)}"))
        cold_first_s = first_request_s(cold_first)
        warm_first = make_runner(root)
        warm_first_s = first_request_s(warm_first)
        runs.append({"cold_warmup_s": round(cold_warmup_s, 3),
                     "warm_warmup_s": round(warm_warmup_s, 3),
                     "cold_first_req_s": round(cold_first_s, 3),
                     "warm_first_req_s": round(warm_first_s, 3),
                     "buckets": nbuckets})
    shutil.rmtree(d, ignore_errors=True)
    vals = sorted(r["cold_warmup_s"] / r["warm_warmup_s"]
                  for r in runs)
    median = vals[len(vals) // 2] if len(vals) % 2 else \
        0.5 * (vals[len(vals) // 2 - 1] + vals[len(vals) // 2])
    best_run = max(runs, key=lambda r: r["cold_warmup_s"]
                   / r["warm_warmup_s"])
    stats = {
        "best": max(vals), "median": median, "n": len(vals),
        "spread": round((max(vals) - min(vals)) / median, 4),
        "runs": [round(v, 2) for v in vals],
        "info": {"hbm_peak": None,  # inference path; no scan program
                 "best_run": best_run, "all_runs": runs},
    }
    return stats, _METRIC_NAMES["serving_coldstart"], "x"


def bench_serving_bert_int8(seq_len=64, max_batch=8, repeats=3,
                            iters=30):
    """INT8 serving ablation row (on-demand,
    MXTPU_BENCH_MODEL=serving_bert_int8): the serving_bert model
    exported once and served three ways over the same saturation
    bucket — f32, bf16 (mxtpu.amp) and int8 (mxtpu.quant,
    entropy-calibrated on seeded batches) — raw AOT back-to-back
    throughput and per-request p50/p95 per arm.

    The primary value is the int8 arm's raw req/sec (best of
    ``repeats``); ``details`` carries the int8-vs-f32 and
    int8-vs-bf16 speedups, each reduced-precision arm's max-|Δlogit|
    vs f32 on a fixed eval batch, and the s8×s8→s32 contraction
    census of the int8 bucket's lowering — the proof the arm actually
    quantized (on the CPU backend int8 GEMMs may not run faster, so
    the census, not the ratio, is the floor evidence; the hard
    accuracy gate on this shape lives in tests/test_quant.py)."""
    import tempfile

    from mxtpu import nd
    from mxtpu.analysis import dtypeflow
    from mxtpu.models.transformer import BERTModel
    from mxtpu.serving import ModelRunner

    V = 8192
    net = BERTModel(V, 256, 1024, 4, 4, max_length=seq_len,
                    dropout=0.0)
    net.initialize(init="xavier")
    rng = np.random.RandomState(0)
    net(nd.array(rng.randint(0, V, (1, seq_len))
                 .astype(np.float32)))          # materialize params
    d = tempfile.mkdtemp(prefix="mxtpu_bench_rec_serving_int8_")
    sym_file, param_file = net.export(os.path.join(d, "bert"))

    bucket = (max_batch, seq_len)
    calib = [{"data": rng.randint(0, V, (max_batch, seq_len))
              .astype(np.float32)} for _ in range(4)]
    eval_rows = [{"data": rng.randint(0, V, (seq_len,))
                  .astype(np.float32)} for _ in range(max_batch)]

    def make_runner(arm):
        runner = ModelRunner.from_export(
            sym_file, param_file, input_specs={"data": (None,)},
            seq_buckets=[seq_len], max_batch_size=max_batch,
            amp=(arm == "bf16") or None,
            quant=(arm == "int8") or None)
        if arm == "int8":
            runner.calibrate(calib, mode="entropy")
        return runner

    arms = {}
    f32_logits = None
    int8_census = None
    for arm in ("f32", "bf16", "int8"):
        runner = make_runner(arm)
        if arm == "int8":
            int8_census = dtypeflow.int8_contraction_census(
                runner.lowered_program_text(bucket))
        t0 = time.perf_counter()
        runner.warmup([bucket])     # one bucket per arm — cheap row
        compile_s = time.perf_counter() - t0
        vals = runner._pad_stack(eval_rows, bucket)
        logits = np.asarray(runner.run_raw(vals, bucket)[0],
                            np.float32)         # settle + eval batch
        if arm == "f32":
            f32_logits = logits
        best = 0.0
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(iters):
                outs = runner.run_raw(vals, bucket)
            np.asarray(outs[0])                 # sync
            best = max(best,
                       max_batch * iters / (time.perf_counter() - t0))
        lats = []
        for _ in range(iters):
            t0 = time.perf_counter()
            np.asarray(runner.run_raw(vals, bucket)[0])
            lats.append((time.perf_counter() - t0) * 1e3)
        lats.sort()
        arms[arm] = {
            "raw_rps": round(best, 1),
            "p50_ms": round(lats[len(lats) // 2], 3),
            "p95_ms": round(
                lats[min(len(lats) - 1,
                         int(round(0.95 * (len(lats) - 1))))], 3),
            "compile_seconds": round(compile_s, 2),
            "max_abs_logit_delta_vs_f32": None if arm == "f32" else
                round(float(np.abs(logits - f32_logits).max()), 5),
            "weight_mb": round(runner.weight_bytes() / 2 ** 20, 1),
        }
    stats = {
        "best": arms["int8"]["raw_rps"],
        "median": arms["int8"]["raw_rps"], "n": repeats,
        "spread": 0.0, "runs": [arms["int8"]["raw_rps"]],
        "info": {
            "hbm_peak": None,   # inference path; no scan program
            "arms": arms,
            "int8_vs_f32": round(
                arms["int8"]["raw_rps"] / arms["f32"]["raw_rps"], 4),
            "int8_vs_bf16": round(
                arms["int8"]["raw_rps"] / arms["bf16"]["raw_rps"], 4),
            "int8_contraction_census": int8_census,
            "f32_logit_scale": round(
                float(np.abs(f32_logits).max()), 4),
        },
    }
    return stats, _METRIC_NAMES["serving_bert_int8"], "req/sec"


def bench_serving_generate(n_req=8, max_tokens=24, repeats=3):
    """Generation serving row (on-demand,
    MXTPU_BENCH_MODEL=serving_generate): KV-cache incremental decode
    (ISSUE 19) at saturation — ``n_req`` greedy requests continuously
    batched onto the lane table of a small exported causal BERT,
    stepped until drained.

    The primary value is decode tokens/sec at saturation (best of
    ``repeats``; warm ladder — compile time is the coldstart row's
    job).  ``details`` carries p50/p95 TTFT and per-token latency
    measured at the stream callback (the timestamps an SSE client
    would see, BASELINE.md token-latency methodology), and the
    honesty denominator: a naive re-prefill-every-token baseline that
    generates the same greedy continuation by running a full prefill
    over the growing sequence for each token — the speedup over that
    is what the KV cache actually buys."""
    import tempfile

    from mxtpu import nd
    from mxtpu.models.transformer import BERTModel
    from mxtpu.serving import GenerateBatcher, GenerateRunner

    V, LANES, L = 8192, 4, 64
    prompt_len = 8
    net = BERTModel(V, 128, 512, 2, 2, max_length=L, dropout=0.0,
                    use_token_type=False, causal=True)
    net.initialize(init="xavier")
    net.hybridize()
    rng = np.random.RandomState(0)
    tokens = nd.array(rng.randint(0, V, (2, 3)).astype(np.float32))
    stepv = nd.array(np.zeros(2, np.float32))
    kv0 = nd.array(np.zeros(net.kv_cache_spec(2), np.float32))
    net(tokens, stepv, kv0)                     # incremental trace
    d = tempfile.mkdtemp(prefix="mxtpu_bench_rec_generate_")
    sym_file, param_file = net.export(os.path.join(d, "genbert"))
    runner = GenerateRunner.from_export(
        sym_file, param_file, net.kv_cache_spec(LANES, L),
        prompt_buckets=(16, 32), cache=None)
    t0 = time.perf_counter()
    runner.warmup()
    warmup_s = time.perf_counter() - t0

    prompts = [list(rng.randint(1, V, prompt_len).astype(int))
               for _ in range(n_req)]

    def saturation_run():
        """All n_req requests through one batcher; the stream
        callback records TTFT and inter-token gaps per request."""
        batcher = GenerateBatcher(runner)
        marks = [[] for _ in prompts]           # perf_counter stamps
        reqs = []
        t_submit = time.perf_counter()
        for i, p in enumerate(prompts):
            reqs.append(batcher.submit(
                p, max_tokens=max_tokens,
                on_token=lambda t, idx, m=marks[i]:
                    m.append(time.perf_counter())))
        while not batcher.drain():
            batcher.step()
        elapsed = time.perf_counter() - t_submit
        batcher.close()
        total = sum(len(r.result(0)) for r in reqs)
        ttfts = [(m[0] - t_submit) * 1e3 for m in marks if m]
        gaps = [(b - a) * 1e3 for m in marks
                for a, b in zip(m, m[1:])]
        return total / elapsed, ttfts, gaps

    def pct(vals, q):
        vals = sorted(vals)
        return round(vals[min(len(vals) - 1,
                              int(round(q * (len(vals) - 1))))], 3)

    best, ttfts, gaps = 0.0, [], []
    runs = []
    for _ in range(repeats):
        rate, t, g = saturation_run()
        runs.append(round(rate, 1))
        ttfts += t
        gaps += g
        best = max(best, rate)

    # naive denominator: the SAME greedy continuation produced by
    # re-running a full prefill over the growing sequence per token
    # (what serving without a KV cache degenerates to); single
    # request — the naive path has no lane table to batch onto.
    b = runner.batch_rung_for(1)
    kv = runner.new_cache()
    seq = list(prompts[0])
    t0 = time.perf_counter()
    while len(seq) - prompt_len < max_tokens:
        s = runner.prompt_bucket_for(len(seq))
        tok = np.zeros((b, s), np.float32)
        tok[0, :len(seq)] = seq
        length = np.zeros(b, np.float32)
        length[0] = len(seq)
        logits, kv = runner.prefill(
            tok, np.zeros(b, np.float32),
            np.full(b, LANES, np.float32), kv, length)  # scratch slot
        # the row of the sequence's last position, its maximum found
        # on the device
        seq.append(int(logits.first_maximum[0]))
    naive_rate = max_tokens / (time.perf_counter() - t0)

    stats = {
        "best": round(best, 1), "median": sorted(runs)[len(runs) // 2],
        "n": repeats, "spread": round((max(runs) - min(runs))
                                      / max(runs), 4),
        "runs": runs,
        "info": {
            "hbm_peak": None,   # inference path; no scan program
            "ttft_ms": {"p50": pct(ttfts, 0.5),
                        "p95": pct(ttfts, 0.95)},
            "per_token_ms": {"p50": pct(gaps, 0.5),
                             "p95": pct(gaps, 0.95)},
            "naive_reprefill_tok_per_sec": round(naive_rate, 1),
            "kv_vs_naive": round(best / naive_rate, 2),
            "lanes": LANES, "n_req": n_req,
            "max_tokens": max_tokens, "prompt_len": prompt_len,
            "warmup_seconds": round(warmup_s, 2),
            "ladder": [list(map(str, bkt))
                       for bkt in runner.buckets()],
        },
    }
    import shutil
    shutil.rmtree(d, ignore_errors=True)
    return stats, _METRIC_NAMES["serving_generate"], "tok/sec"


def _mfu(model, value, peak, per_unit=None):
    per_unit = per_unit or _TRAIN_FLOPS.get(model)
    if per_unit is None:
        return None
    return round(per_unit * value / peak, 4)


# Conservative per-row wall estimates (seconds, incl. compile)
# used by the pre-flight gate: a row only STARTS if this much
# time is left before the global deadline.  Overestimates drop rows
# early (recorded, recoverable one-at-a-time via MXTPU_BENCH_MODEL=…);
# underestimates risk rc=124 — err high.
_ROW_EST = {"resnet50": 150, "resnet50_pipeline": 120, "bert": 150,
            "bert_s512": 130, "lenet": 60, "transformer": 120,
            "moe_ffn": 60, "ssd": 90, "bert_zero": 150,
            # 8 bucket compiles (4-rung ladder x 2 seq buckets) of a
            # 4-layer BERT + two latency sweeps + 3 saturation runs
            "serving_bert": 180,
            # tiny model, but 3 soak runs x (n_workers + replacement)
            # ladder compiles + open-loop pacing
            "serving_fleet": 120,
            # 6 short burst runs (static + autoscaled x 3 repeats),
            # each ~2 s of scripted service + replica ladder compiles
            "serving_autoscale": 90,
            # 2 repeats x (cold ladder compile + disk-warmed reload +
            # two first-request probes) of a 2-layer BERT
            "serving_coldstart": 120,
            # 3 arms (f32/bf16/int8) x one bucket compile + timing
            # loops + one calibration pass of a 4-layer BERT
            "serving_bert_int8": 150,
            # full generate ladder compile (prefill rungs + decode
            # step) of a 2-layer causal BERT + 3 saturation drains +
            # the naive re-prefill baseline loop
            "serving_generate": 150,
            # pairs run the base workload twice (off + on)
            "resnet50_amp": 300, "bert_amp": 300,
            "transformer_amp": 240, "bert_zero_amp": 300}


def _sweep_stale_tmpdirs():
    """Remove mxtpu_bench_rec_* dirs left by killed/old runs — each
    holds a ~150 MB record set (VERDICT r5 weak #6: ~1.8 GB had
    accumulated)."""
    import glob
    import shutil
    import tempfile
    for d in glob.glob(os.path.join(tempfile.gettempdir(),
                                    "mxtpu_bench_rec_*")):
        shutil.rmtree(d, ignore_errors=True)


def _emit(results, order, budget, deadline, device):
    """The one exit path for bench JSON: primary row + extras + wall
    block + the device it ran on, printed as a single line (success,
    trim, and the SIGALRM wall backstop all come through here)."""
    primary = next((results[m] for m in order
                    if results[m].get("value") is not None),
                   results[order[0]])
    out = dict(primary)
    if len(results) > 1:
        out["extras"] = {m: results[m] for m in order
                         if results[m] is not primary}
    out["device"] = device
    out["wall"] = {"budget_seconds": round(budget, 1),
                   "elapsed_seconds": round(
                       budget - (deadline - time.monotonic()), 1),
                   "skipped": [m for m in order
                               if results[m].get("skipped")]}
    print(json.dumps(out))
    sys.stdout.flush()


def _contracts_gate():
    """``--contracts``: fail FAST if any program drifted from its
    committed lockfile — a whole bench round against a silently
    changed program (a vanished reduce-scatter, a new layout bracket,
    a drifted lock graph, dtype flow or memory ledger) records numbers
    nobody should trust.  Each gate is a child process that pins the
    CPU backend before it imports jax, so it needs no chip; main()
    still runs them FIRST, before this process touches JAX, so the
    order never depends on that."""
    here = os.path.dirname(os.path.abspath(__file__))
    for tool, what in (
            ("hlocheck", "a compiled program drifted from its "
                         "lockfile"),
            ("mxrace", "the lock-order graph drifted from "
                       "contracts/lockorder.json"),
            ("mxprec", "the dtype flow drifted from contracts/prec/"),
            ("mxmem", "the memory footprint drifted from "
                      "contracts/mem/")):
        rc = subprocess.call(
            [sys.executable, "-m", f"tools.{tool}", "--check"],
            cwd=here)
        if rc != 0:
            sys.exit(f"bench: --contracts gate failed ({tool} "
                     f"rc={rc}) — {what}; inspect `python -m "
                     f"tools.{tool}` and either fix the drift or "
                     f"regenerate with --update before benching")
    print("bench: --contracts gate passed (programs match "
          "contracts/, lock graph matches lockorder.json, "
          "dtype flow matches contracts/prec/, memory ledgers "
          "match contracts/mem/)")


def main():
    if "--contracts" in sys.argv[1:]:
        _contracts_gate()
    which = knobs.get("MXTPU_BENCH_MODEL")
    table = {"lenet": bench_lenet, "resnet50": bench_resnet50,
             "resnet50_pipeline": bench_resnet50_pipeline,
             "bert": bench_bert,
             # long-context north-star row (VERDICT r3 item 4): at
             # s512 attention is a real fraction of the FLOPs, so the
             # flash-attention kernel shows up in a recorded number
             "bert_s512": lambda: bench_bert(
                 batch_size=8, seq_len=512,
                 metric_key="bert_s512"),
             "transformer": bench_transformer,
             # on-demand rows (MXTPU_BENCH_MODEL=moe_ffn / ssd /
             # bert_zero / serving_bert / serving_fleet /
             # serving_autoscale): each fits the budget on its own but
             # the default sweep is already near the wall, so they are
             # not in "all"
             "moe_ffn": bench_moe_ffn,
             "ssd": bench_ssd,
             "bert_zero": bench_bert_zero,
             "serving_bert": bench_serving_bert,
             "serving_fleet": bench_serving_fleet,
             "serving_autoscale": bench_serving_autoscale,
             "serving_coldstart": bench_serving_coldstart,
             "serving_bert_int8": bench_serving_bert_int8,
             "serving_generate": bench_serving_generate,
             # --amp pairs (on-demand): AMP off vs on side by side
             "resnet50_amp": lambda: bench_amp_pair(
                 "resnet50_amp", bench_resnet50),
             "bert_amp": lambda: bench_amp_pair(
                 "bert_amp", bench_bert),
             "transformer_amp": lambda: bench_amp_pair(
                 "transformer_amp", bench_transformer),
             "bert_zero_amp": lambda: bench_amp_pair(
                 "bert_zero_amp", bench_bert_zero)}
    if "--amp" in sys.argv[1:]:
        # `bench.py --amp` swaps every selected row that has an AMP
        # pair for it (MXTPU_BENCH_MODEL=resnet50 --amp runs the
        # resnet50_amp pair; rows without a pair run unchanged)
        if which != "all" and f"{which}_amp" in table:
            which = f"{which}_amp"
    if which != "all" and which not in table:
        sys.exit(f"unknown MXTPU_BENCH_MODEL={which!r}; "
                 f"choices: {sorted(table) + ['all']}")
    budget = knobs.get("MXTPU_BENCH_WALL_BUDGET")
    order = [which] if which != "all" else \
        ["resnet50", "resnet50_pipeline", "bert", "bert_s512",
         "transformer", "lenet"]
    if "--amp" in sys.argv[1:] and which == "all":
        order = [f"{m}_amp" if f"{m}_amp" in table else m
                 for m in order]
    est_total = sum(_ROW_EST[m] for m in order)
    if "--preflight" in sys.argv[1:]:
        # Answer "will the selected sweep fit the wall budget?" without
        # touching the TPU.  Non-zero exit = the sweep as configured
        # would drop rows — fix the budget or the row list BEFORE
        # burning a run.
        for m in order:
            print(f"  {m:<20} est {_ROW_EST[m]:>4}s")
        verdict = "FITS" if est_total <= budget else "EXCEEDS"
        print(f"preflight: {len(order)} rows, estimated {est_total}s "
              f"{verdict} MXTPU_BENCH_WALL_BUDGET={budget:.0f}s")
        sys.exit(0 if est_total <= budget else 1)
    device = _device()
    peak = _peak_flops()
    _sweep_stale_tmpdirs()
    deadline = time.monotonic() + budget

    def skipped(m, **extra):
        return {"metric": _METRIC_NAMES[m], "value": None,
                "unit": None, "mfu": None, "skipped": "budget",
                **extra}

    results = {}
    if hasattr(signal, "SIGALRM"):
        # even if a single row blows straight through its estimate,
        # the alarm fires at the wall, the rows that never ran land
        # as {"skipped": "budget"} and the JSON still prints — but a
        # row that hung is a failure, and the exit code says so
        def _wall_trip(signum, frame):
            for m in order:
                results.setdefault(m, skipped(m))
            print(f"bench: wall budget {budget:.0f}s tripped "
                  f"mid-row; flushing partial record", file=sys.stderr)
            _emit(results, order, budget, deadline, device)
            os._exit(1)
        signal.signal(signal.SIGALRM, _wall_trip)
        signal.alarm(max(1, int(budget)))
    if est_total > budget:
        # when the sweep as configured cannot fit, trim it UP FRONT by
        # the same arithmetic --preflight prints — each row whose
        # estimate does not fit the cumulative total is dropped on
        # record before anything runs.  The per-row runtime check
        # below stays as the backstop for rows that overrun their
        # estimate.
        cum = 0.0
        for m in order:
            if cum + _ROW_EST[m] <= budget:
                cum += _ROW_EST[m]
                continue
            results[m] = skipped(
                m, est_seconds=_ROW_EST[m],
                remaining_seconds=round(budget - cum, 1))
        print(f"bench pre-flight: estimated {est_total}s for "
              f"{order} exceeds MXTPU_BENCH_WALL_BUDGET={budget:.0f}s; "
              f"auto-trimmed {sorted(results)} onto the record",
              file=sys.stderr)
    for model in order:
        if model in results:
            continue
        remaining = deadline - time.monotonic()
        if remaining < _ROW_EST[model]:
            # a row that cannot finish is DROPPED ON RECORD, never
            # allowed to run the process into the wall
            results[model] = skipped(
                model, est_seconds=_ROW_EST[model],
                remaining_seconds=round(remaining, 1))
            continue
        # one workload failing must not cost the other rows their
        # line — record the error, move on, and exit non-zero at the
        # end
        try:
            stats, metric, unit = table[model]()
        except Exception as e:
            results[model] = {"metric": _METRIC_NAMES[model],
                              "value": None, "unit": None, "mfu": None,
                              "error": f"{type(e).__name__}: "
                                       f"{str(e)[:300]}"}
            continue
        value = stats["best"]
        results[model] = {
            "metric": metric, "value": round(value, 1), "unit": unit,
            "mfu": _mfu(model, value, peak),
            "band": {"median": round(stats["median"], 1),
                     "n": stats["n"], "spread": stats["spread"]},
        }
        if stats.get("info"):
            # row-specific context (e.g. moe_ffn's dense-FFN envelope)
            results[model]["details"] = stats["info"]
        # ISSUE 8: every row carries the obs registry state as of its
        # run — compile counts, step-time histograms, serving counters
        results[model].setdefault("details", {})["obs"] = obs.summary()
    if hasattr(signal, "SIGALRM"):
        signal.alarm(0)
    _emit(results, order, budget, deadline, device)
    failed = [m for m in order if results[m].get("error")]
    if failed:
        sys.exit(f"bench: {len(failed)} row(s) failed: {failed}")


if __name__ == "__main__":
    main()
