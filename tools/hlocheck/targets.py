"""hlocheck target registry: the named model x config programs whose
compiled HLO is pinned by a lockfile in ``contracts/``.

Every target is a zero-argument builder returning
``{program_name: (hlo_text, mem_stats_dict_or_None)}``.  Builders run
on the CPU backend with the 8-virtual-device mesh the CLI pins
(``__main__`` sets ``JAX_PLATFORMS``/``XLA_FLAGS`` before jax loads),
so a lockfile regenerated on any box matches CI.

The models are *tiny stand-ins* for the bench configurations — same
code paths (ZeRO shard_map step, batched optimizer, fused epilogues,
serving bucket ladder), scaled so the whole ``--check`` sweep lowers
in a couple of minutes on CPU.  Contract properties (which
collectives, dtype policy, zero host transfers) are scale-invariant;
budget properties (fusion counts, peak bytes) pin the tiny config's
numbers, which still move when the underlying compilation strategy
changes — that is the regression-tripwire the lockfile exists for.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import numpy as np

Artifact = Tuple[str, Optional[dict]]
Builder = Callable[[], Dict[str, Artifact]]

TARGETS: Dict[str, Builder] = {}

# mxprec rides the same six targets at the PRE-optimization level:
# each prec builder returns ``{"programs": {prog: pre_opt_hlo_text},
# "optimizer": optimizer_or_None, "param_sigs": sigs_or_None}``.
# Model/step construction is shared with the hlocheck builders above
# so the two registries can never drift apart.
PrecBuilder = Callable[[], Dict]

PREC_TARGETS: Dict[str, PrecBuilder] = {}

# mxmem (ISSUE 20) rides the same fixtures a third time: each mem
# builder returns a memflow *record* (programs + byte attributions +
# the zero/kv oracles) that ``tools.mxmem`` turns into the committed
# ``contracts/mem/<target>.json`` ledger.
MemBuilder = Callable[[], Dict]

MEM_TARGETS: Dict[str, MemBuilder] = {}


def register_target(name: str):
    def deco(fn: Builder) -> Builder:
        TARGETS[name] = fn
        return fn
    return deco


def register_prec(name: str):
    def deco(fn: PrecBuilder) -> PrecBuilder:
        PREC_TARGETS[name] = fn
        return fn
    return deco


def register_mem(name: str):
    def deco(fn: MemBuilder) -> MemBuilder:
        MEM_TARGETS[name] = fn
        return fn
    return deco


def build(name: str) -> Dict[str, dict]:
    """Summaries (contract-shaped) for every program of ``name``."""
    from mxtpu.analysis import summarize
    artifacts = TARGETS[name]()
    return {prog: summarize(text, mem)
            for prog, (text, mem) in sorted(artifacts.items())}


def build_prec(name: str) -> Dict:
    """Pre-optimization dtype-flow facts for ``name`` (mxprec's
    substrate) — lowering only, never a compile, so the sweep stays
    cheap on CPU."""
    return PREC_TARGETS[name]()


def build_mem(name: str) -> Dict:
    """Memory record for ``name`` (mxmem's substrate): compiled
    ``memory_analysis()`` stats plus the byte attributions and
    geometry oracles ``mxtpu.analysis.memflow`` decomposes into the
    committed ledger."""
    return MEM_TARGETS[name]()


# ----------------------------------------------------------------------
# shared pieces
# ----------------------------------------------------------------------
_VOCAB = 512


def _mlm_loss():
    from mxtpu.gluon import loss as gloss
    ce = gloss.SoftmaxCrossEntropyLoss()

    def loss(pred, y):
        return ce(pred.reshape((-1, _VOCAB)), y.reshape((-1,)))
    return loss


def _train_step_artifact(step, x, y) -> Artifact:
    return step.hlo_text(x, y), step.memory_analysis(x, y)


def _prec_train(step, x, y) -> Dict:
    return {"programs": {"train_step": step.lowered_hlo_text(x, y)},
            "optimizer": step.optimizer,
            "param_sigs": step.param_sigs(x, y)}


def _bert_parts(zero: int, amp: bool = False):
    import jax
    from mxtpu import nd, parallel
    from mxtpu.models.transformer import BERTModel
    net = BERTModel(_VOCAB, 64, 128, 2, 2, max_length=32,
                    dropout=0.1)
    net.initialize(init="xavier")
    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, _VOCAB, (8, 16)).astype(np.float32))
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("dp",))
    step = parallel.build_train_step(
        net, _mlm_loss(), "adam", {"learning_rate": 1e-3},
        mesh=mesh, cast_batch=False, zero=zero, amp=amp or None)
    return step, x, x


@register_target("bert_replicated")
def bert_replicated() -> Dict[str, Artifact]:
    """Tiny BERT, dp8 data-parallel with replicated optimizer states
    (the pre-ZeRO path: gradient all-reduce)."""
    return {"train_step": _train_step_artifact(*_bert_parts(zero=0))}


@register_target("bert_zero")
def bert_zero() -> Dict[str, Artifact]:
    """Tiny BERT, dp8 ZeRO-1: reduce-scatter + all-gather per bucket,
    no big all-reduce — the comm signature tests/test_zero.py pins."""
    return {"train_step": _train_step_artifact(*_bert_parts(zero=1))}


@register_target("bert_zero_amp")
def bert_zero_amp() -> Dict[str, Artifact]:
    """``bert_zero`` with ``amp=True`` — pins the AMP comm payoff:
    the same reduce-scatter count as the f32 contract but the
    exchanged buckets ride bf16 (collective bytes ~ half of
    ``bert_zero``'s), upcast to f32 immediately after the exchange.

    The payoff is pinned on the ``train_step_as_written`` program
    (the pre-optimization lowering): the CPU backend's
    float-normalization pass rewrites bf16 collectives back to f32
    in the compiled text, so only the as-written level carries the
    dtype the wire sees on a real accelerator."""
    step, x, y = _bert_parts(zero=1, amp=True)
    return {"train_step": _train_step_artifact(step, x, y),
            "train_step_as_written":
                (step.lowered_hlo_text(x, y), None)}


@register_prec("bert_replicated")
def bert_replicated_prec() -> Dict:
    return _prec_train(*_bert_parts(zero=0))


@register_prec("bert_zero")
def bert_zero_prec() -> Dict:
    return _prec_train(*_bert_parts(zero=1))


@register_prec("bert_replicated_amp")
def bert_replicated_amp_prec() -> Dict:
    return _prec_train(*_bert_parts(zero=0, amp=True))


@register_prec("bert_zero_amp")
def bert_zero_amp_prec() -> Dict:
    return _prec_train(*_bert_parts(zero=1, amp=True))


def _transformer_parts(amp: bool = False):
    from mxtpu import nd, parallel
    from mxtpu.gluon.block import HybridBlock
    from mxtpu.models.transformer import TransformerModel

    class MTWrap(HybridBlock):
        def __init__(self, split, **kw):
            super().__init__(**kw)
            self._split = split
            self.model = TransformerModel(
                _VOCAB, units=64, hidden_size=128, num_layers=2,
                num_heads=2, max_length=64, dropout=0.1)

        def hybrid_forward(self, F, x):
            src = F.slice_axis(x, axis=1, begin=0, end=self._split)
            tgt = F.slice_axis(x, axis=1, begin=self._split,
                               end=None)
            return self.model(src, tgt)

    net = MTWrap(16)
    net.initialize(init="xavier")
    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, _VOCAB, (4, 32)).astype(np.float32))
    y = nd.array(rng.randint(0, _VOCAB, (4, 16)).astype(np.float32))
    step = parallel.build_train_step(
        net, _mlm_loss(), "adam", {"learning_rate": 1e-4},
        cast_batch=False, amp=amp or None)
    return step, x, y


@register_target("transformer")
def transformer() -> Dict[str, Artifact]:
    """Tiny encoder-decoder transformer (the bench `transformer` row's
    shape: src|tgt concatenated on the time axis)."""
    return {"train_step": _train_step_artifact(*_transformer_parts())}


@register_prec("transformer")
def transformer_prec() -> Dict:
    return _prec_train(*_transformer_parts())


@register_prec("transformer_amp")
def transformer_amp_prec() -> Dict:
    return _prec_train(*_transformer_parts(amp=True))


def _resnet_parts(amp: bool = False):
    from mxtpu import nd, parallel
    from mxtpu.gluon import loss as gloss
    from mxtpu.gluon.model_zoo import vision
    net = vision.get_resnet(1, 18, thumbnail=True, classes=10)
    net.initialize(init="xavier")
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(8, 3, 32, 32).astype(np.float32))
    y = nd.array(rng.randint(0, 10, (8,)).astype(np.float32))
    step = parallel.build_train_step(
        net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, amp=amp or None)
    return step, x, y


@register_target("resnet18")
def resnet18() -> Dict[str, Artifact]:
    """resnet18 thumbnail (BN-heavy conv net — the fused-BN bracket
    watchpoint of ROADMAP item 3)."""
    return {"train_step": _train_step_artifact(*_resnet_parts())}


@register_prec("resnet18")
def resnet18_prec() -> Dict:
    return _prec_train(*_resnet_parts())


@register_prec("resnet18_amp")
def resnet18_amp_prec() -> Dict:
    return _prec_train(*_resnet_parts(amp=True))


def _quant_calib_batches(n: int = 4):
    """Seeded representative batches for INT8 calibration — fixed
    token ids, so the calibrated thresholds (and therefore the
    quantized fixture's HLO, which bakes them in as constants) are
    byte-reproducible on any box."""
    rng = np.random.RandomState(0)
    return [{"data": rng.randint(0, _VOCAB, (4, 32))
             .astype(np.float32)} for _ in range(n)]


def _serving_runner(amp: bool = False, quant: bool = False):
    import os
    import tempfile
    from mxtpu import nd
    from mxtpu.models.transformer import BERTModel
    from mxtpu.serving import ModelRunner
    if quant:
        # float serving programs are weight-independent (params are
        # runtime inputs), but the quantized trace bakes the
        # CALIBRATED activation thresholds in as constants — and those
        # depend on the weights, so the int8 fixture pins the global
        # init stream
        from mxtpu.ndarray import random as _mxrnd
        _mxrnd.seed(0)
    net = BERTModel(_VOCAB, 64, 128, 2, 2, max_length=32,
                    dropout=0.0)
    net.initialize(init="xavier")
    rng = np.random.RandomState(0)
    net(nd.array(rng.randint(0, _VOCAB, (1, 32))
                 .astype(np.float32)))
    d = tempfile.mkdtemp(prefix="hlocheck_bert_")
    sym_file, param_file = net.export(os.path.join(d, "bert"))
    runner = ModelRunner.from_export(
        sym_file, param_file, input_specs={"data": (None,)},
        seq_buckets=[16, 32], max_batch_size=4,
        amp=amp or None, quant=quant or None)
    if quant:
        # explicit mode (not the env knob): the committed contracts
        # pin the entropy-calibrated thresholds
        runner.calibrate(_quant_calib_batches(), mode="entropy")
    return runner


@register_target("serving_bert")
def serving_bert() -> Dict[str, Artifact]:
    """Serving bucket ladder: tiny exported BERT through
    ModelRunner's AOT (batch, seq) executables — every bucket gets
    its own contract entry."""
    runner = _serving_runner()
    runner.warmup()
    out: Dict[str, Artifact] = {}
    for bucket in runner.buckets():
        batch, seq = bucket
        text, mem = runner.program_artifact(bucket)
        out[f"bucket_b{batch}_s{seq}"] = (text, mem)
    return out


@register_prec("serving_bert")
def serving_bert_prec() -> Dict:
    # lowering only — no warmup/compile, so the prec sweep stays fast
    runner = _serving_runner()
    programs = {}
    for bucket in runner.buckets():
        batch, seq = bucket
        programs[f"bucket_b{batch}_s{seq}"] = \
            runner.lowered_program_text(bucket)
    return {"programs": programs, "optimizer": None,
            "param_sigs": None}


@register_prec("serving_bert_amp")
def serving_bert_amp_prec() -> Dict:
    runner = _serving_runner(amp=True)
    programs = {}
    for bucket in runner.buckets():
        batch, seq = bucket
        programs[f"bucket_b{batch}_s{seq}"] = \
            runner.lowered_program_text(bucket)
    return {"programs": programs, "optimizer": None,
            "param_sigs": None}


@register_target("serving_bert_int8")
def serving_bert_int8() -> Dict[str, Artifact]:
    """The serving ladder calibrated + quantized (mxtpu.quant): every
    bucket's compiled program carries the policy's contractions as
    s8xs8 GEMMs accumulating in i32, plus one ``_as_written``
    (pre-optimization) entry — the level the prec ledger and the
    dtypeflow int8 hazard rules read, immune to any CPU-backend
    normalization of the compiled text."""
    runner = _serving_runner(quant=True)
    runner.warmup()
    out: Dict[str, Artifact] = {}
    for bucket in runner.buckets():
        batch, seq = bucket
        text, mem = runner.program_artifact(bucket)
        out[f"bucket_b{batch}_s{seq}"] = (text, mem)
    top = max(runner.buckets())
    out[f"bucket_b{top[0]}_s{top[1]}_as_written"] = \
        (runner.lowered_program_text(top), None)
    return out


@register_prec("serving_bert_int8")
def serving_bert_int8_prec() -> Dict:
    runner = _serving_runner(quant=True)
    programs = {}
    for bucket in runner.buckets():
        batch, seq = bucket
        programs[f"bucket_b{batch}_s{seq}"] = \
            runner.lowered_program_text(bucket)
    return {"programs": programs, "optimizer": None,
            "param_sigs": None}


def _generate_runner(amp: bool = False):
    """Tiny causal BERT through the incremental-decode path (ISSUE
    19): hybrid-forward with (step, cache) extra inputs exported, then
    a GenerateRunner over a 2-lane bucket-paged KV cache.  The decode
    contract this pins: the per-lane ``dynamic-update-slice`` KV
    write into the whole slot table + masked cached attention, single
    fused device program, no host round-trips inside the step."""
    import os
    import tempfile
    from mxtpu import nd
    from mxtpu.models.transformer import BERTModel
    from mxtpu.serving import GenerateRunner
    net = BERTModel(_VOCAB, 64, 128, 2, 2, max_length=32,
                    dropout=0.0, use_token_type=False, causal=True)
    net.initialize(init="xavier")
    net.hybridize()
    rng = np.random.RandomState(0)
    toks = nd.array(rng.randint(0, _VOCAB, (1, 8))
                    .astype(np.float32))
    step = nd.array(np.zeros((1,), np.float32))
    cache = nd.array(np.zeros(net.kv_cache_spec(1), np.float32))
    net(toks, step, cache)   # trace the incremental signature
    d = tempfile.mkdtemp(prefix="hlocheck_gen_")
    sym_file, param_file = net.export(os.path.join(d, "genbert"))
    return GenerateRunner.from_export(
        sym_file, param_file, net.kv_cache_spec(2, 32),
        prompt_buckets=(16, 32), cache=None, amp=amp or None)


@register_target("generate_decode")
def generate_decode() -> Dict[str, Artifact]:
    """Generation ladder: every (batch-rung x prompt-bucket) prefill
    executable plus THE decode-step executable.  The decode entry is
    the per-token serving contract — its compiled text must carry the
    slot-table ``dynamic-update-slice`` KV writes (one per layer and
    per k/v, in a loop over the slots, each on the whole table, which
    no ``copy`` or ``concatenate`` rebuilds) and no host transfer."""
    runner = _generate_runner()
    runner.warmup()
    out: Dict[str, Artifact] = {}
    for bucket in runner.buckets():
        kind, shp = bucket
        text, mem = runner.program_artifact(bucket)
        if kind == "decode":
            out["decode_step"] = (text, mem)
        else:
            out[f"prefill_b{shp[0]}_s{shp[1]}"] = (text, mem)
    # pre-optimization view of the decode step: the level the mxprec
    # ledger and dtypeflow hazard rules read (update-slice signature
    # survives backend normalization here)
    out["decode_step_as_written"] = \
        (runner.lowered_program_text(runner.default_bucket()), None)
    return out


@register_prec("generate_decode")
def generate_decode_prec() -> Dict:
    # lowering only — no compile, the sweep stays fast on CPU
    runner = _generate_runner()
    programs = {}
    for bucket in runner.buckets():
        kind, shp = bucket
        name = "decode_step" if kind == "decode" \
            else f"prefill_b{shp[0]}_s{shp[1]}"
        programs[name] = runner.lowered_program_text(bucket)
    return {"programs": programs, "optimizer": None,
            "param_sigs": None}


@register_prec("generate_decode_amp")
def generate_decode_amp_prec() -> Dict:
    """bf16 decode with f32 accumulation: the amp ledger must show
    zero hazards — attention scores and softmax stay f32 (ISSUE 16
    layout contracts) while the matmul operands ride bf16."""
    runner = _generate_runner(amp=True)
    programs = {}
    for bucket in runner.buckets():
        kind, shp = bucket
        name = "decode_step" if kind == "decode" \
            else f"prefill_b{shp[0]}_s{shp[1]}"
        programs[name] = runner.lowered_program_text(bucket)
    return {"programs": programs, "optimizer": None,
            "param_sigs": None}


class _QuantEvidenceCollector:
    """MinMax activation collector that ALSO records the per-channel
    |w| scales the quantized trace computes in-graph — the policy's
    machine evidence that every quantized weight has a usable
    per-output-channel scale (``observe_weight`` is the optional hook
    ``mxtpu.quant.wrap_op`` probes for)."""

    def __init__(self):
        from mxtpu import quant as Q
        self._inner = Q.MinMaxCollector()
        self.weights: Dict[str, list] = {}

    mode = "minmax"

    def observe(self, key, value):
        self._inner.observe(key, value)

    def observe_weight(self, key, value):
        from mxtpu import quant as Q
        arr = np.asarray(value, np.float32)
        red = tuple(range(1, arr.ndim))
        t = np.abs(arr).max(axis=red) if arr.ndim > 1 else np.abs(arr)
        self.weights.setdefault(
            key, [Q._round6(float(v))
                  for v in np.ravel(np.maximum(t, 1e-12))])

    def thresholds(self):
        return self._inner.thresholds()


def quant_calibration_evidence() -> Dict:
    """The ``calibration`` section of ``contracts/quant_policy.json``
    (written by ``python -m tools.mxprec --quant --update``):
    deterministic seeded evidence from the quantized serving fixture —
    both collectors' per-tensor activation thresholds, every quantized
    parameter's per-channel weight scales, and the s8xs8->s32
    contraction census of the quantized bucket ladder."""
    from mxtpu.analysis import dtypeflow
    batches = _quant_calib_batches()
    runner = _serving_runner(quant=True)  # entropy-calibrated
    evidence = _QuantEvidenceCollector()
    minmax = runner.calibrate(batches, collector=evidence)
    # re-arm with the entropy table LAST so the census below matches
    # the committed serving_bert_int8 contracts (also entropy)
    entropy = runner.calibrate(batches, mode="entropy")
    census = {}
    for bucket in runner.buckets():
        batch, seq = bucket
        census[f"bucket_b{batch}_s{seq}"] = \
            dtypeflow.int8_contraction_census(
                runner.lowered_program_text(bucket))
    return {
        "fixture": "serving_bert fixture, quant=True: mxtpu.random "
                   "seed 0 init, 4 seeded token batches "
                   "(RandomState(0), shape (4, 32))",
        "num_batches": len(batches),
        "activation_thresholds": {"entropy": entropy,
                                  "minmax": minmax},
        "weight_scales": evidence.weights,
        "int8_contractions": census,
    }


def _selftest_parts():
    import jax.numpy as jnp

    def f(a, b):
        w, v = jnp.linalg.eigh(a.T @ a)
        return ((v * w).sum() + (a @ b).sum())

    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(8, 8).astype(np.float32))
    b = jnp.asarray(rng.randn(8, 4).astype(np.float32))
    return f, a, b


@register_target("selftest")
def selftest() -> Dict[str, Artifact]:
    """A deliberately small program that exercises every summary
    family in milliseconds: a lapack custom call (the CPU backend's
    genuine custom-call + layout-bracket specimen), fusions, and a
    clean f32 dtype story.  Keeps one end-to-end CLI round trip
    cheap enough for tier-1."""
    from mxtpu.analysis import compiled_artifact
    f, a, b = _selftest_parts()
    text, mem = compiled_artifact(f, a, b)
    return {"eigh_matmul": (text, mem)}


@register_prec("selftest")
def selftest_prec() -> Dict:
    from mxtpu.analysis import lowered_text
    f, a, b = _selftest_parts()
    return {"programs": {"eigh_matmul": lowered_text(f, a, b)},
            "optimizer": None, "param_sigs": None}


@register_prec("selftest_amp")
def selftest_amp_prec() -> Dict:
    """The selftest math with its contraction routed through the nd
    op registry under an autocast scope — the smallest ledgered
    specimen of the policy in action (bf16 dot operands, f32
    accumulation, eigh/transcendental chain untouched)."""
    import jax.numpy as jnp
    from mxtpu import amp, nd
    from mxtpu.analysis import lowered_text
    from mxtpu.ndarray import NDArray

    def f(a, b):
        w, v = jnp.linalg.eigh(a.T @ a)
        with amp.autocast():
            prod = nd.dot(NDArray(a, None, _placed=True),
                          NDArray(b, None, _placed=True))
        return (v * w).sum() + prod._data.sum()

    _, a, b = _selftest_parts()
    return {"programs": {"eigh_matmul": lowered_text(f, a, b)},
            "optimizer": None, "param_sigs": None}


# ----------------------------------------------------------------------
# mxmem records (ISSUE 20) — same fixtures, byte-attribution view
# ----------------------------------------------------------------------
@register_mem("bert_replicated")
def bert_replicated_mem() -> Dict:
    from mxtpu.analysis import memflow
    step, x, y = _bert_parts(zero=0)
    return memflow.train_step_record(step, x, y, "bert_replicated")


@register_mem("bert_zero")
def bert_zero_mem() -> Dict:
    """The ZeRO-1 ledger: measured per-device optimizer-state bytes
    against the ``plan_zero_buckets`` shard geometry — the committed
    proof of the dp8 opt-state saving (BASELINE.md r7's 2784.6 ->
    348.1 MiB/device at bench scale)."""
    from mxtpu.analysis import memflow
    step, x, y = _bert_parts(zero=1)
    return memflow.train_step_record(step, x, y, "bert_zero",
                                     zero_expected=True)


@register_mem("bert_zero_amp")
def bert_zero_amp_mem() -> Dict:
    from mxtpu.analysis import memflow
    step, x, y = _bert_parts(zero=1, amp=True)
    return memflow.train_step_record(step, x, y, "bert_zero_amp",
                                     zero_expected=True)


@register_mem("transformer")
def transformer_mem() -> Dict:
    from mxtpu.analysis import memflow
    step, x, y = _transformer_parts()
    return memflow.train_step_record(step, x, y, "transformer")


@register_mem("resnet18")
def resnet18_mem() -> Dict:
    from mxtpu.analysis import memflow
    step, x, y = _resnet_parts()
    return memflow.train_step_record(step, x, y, "resnet18")


@register_mem("serving_bert")
def serving_bert_mem() -> Dict:
    from mxtpu.analysis import memflow
    return memflow.runner_record(_serving_runner(), "serving_bert")


@register_mem("serving_bert_int8")
def serving_bert_int8_mem() -> Dict:
    from mxtpu.analysis import memflow
    return memflow.runner_record(_serving_runner(quant=True),
                                 "serving_bert_int8")


@register_mem("generate_decode")
def generate_decode_mem() -> Dict:
    """The KV-table ledger: per-program decomposition with the slot
    table attributed, plus the kv section whose ``table_bytes ==
    expected_bytes`` equality (declared ``kv_cache_spec`` geometry +
    1 scratch slot) is the committed anti-overcommit proof."""
    from mxtpu.analysis import memflow
    return memflow.generate_record(_generate_runner(),
                                   "generate_decode")


@register_mem("selftest")
def selftest_mem() -> Dict:
    """The cheap end-to-end CLI specimen (mirrors hlocheck/mxprec):
    one compiled program, no params/opt attribution — pure
    activations+temps decomposition."""
    from mxtpu.analysis import compiled_artifact, memflow
    f, a, b = _selftest_parts()
    text, mem = compiled_artifact(f, a, b)
    return {"target": "selftest",
            "programs": {"eigh_matmul": {
                "mem": mem or {},
                "collective_scratch":
                    memflow.collective_scratch_bytes(text)}}}
