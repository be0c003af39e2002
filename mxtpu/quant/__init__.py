"""``mxtpu.quant`` — INT8 post-training quantization (calibrate →
policy → serve), the sibling of :mod:`mxtpu.amp` one dtype tier down.

Reference: ``src/operator/quantization/``† (quantize/dequantize/
requantize + quantized conv/fc) driven by ``python/mxnet/contrib/
quantization.py``†'s two calibration algorithms (minmax and
KL-entropy).  The reference rewrites the symbol graph into
``_contrib_quantized_*`` nodes; here the rewrite is a *trace-time
interposition* at the same eager/symbolic dispatch choke point AMP
uses (``ndarray._invoke_op_inner``), consuming a machine-derived
policy (``contracts/quant_policy.json``, written by ``python -m
tools.mxprec --quant``) instead of hand-curated op lists.

Two scopes share the interposition:

* :func:`calibrating` — run representative batches *eagerly* through
  the deployed graph; every candidate contraction's float input is
  observed by a collector (:class:`MinMaxCollector` or
  :class:`EntropyCollector`, the reference's two algorithms) under a
  deterministic per-dispatch key (``FullyConnected_3`` = the 4th
  candidate in topological dispatch order).  Deterministic given
  fixed batches: no RNG, no time — tools/mxlint's retrace rule scans
  this whole module for impure calls.
* :func:`quantize` — inside a trace, a candidate op whose key has a
  recorded activation threshold is replaced by the int8 form:
  quantize-on-entry (symmetric per-tensor activation scale, the
  calibrated |x| threshold), **per-channel weight scales computed
  in-graph** (abs-max over the non-output axes — weights are runtime
  inputs, so one compiled bucket serves every checkpoint), an
  **int8×int8 contraction accumulating in i32 via
  ``preferred_element_type=int32``**, and a float dequantize epilogue
  (+ float bias).  Between two adjacent quantized ops the epilogue
  and the next op's entry quantize are adjacent elementwise chains —
  XLA fuses them into the single rescale a hand-written requantize
  would be.  Anything outside the policy's allow class (or with no
  recorded scale) falls back to the bf16/f32 path untouched.

Every quantized contraction is emitted under
``jax.named_scope("q8_<key>")`` so its HLO metadata carries the scale
key; :mod:`mxtpu.analysis.dtypeflow` turns that into two machine
checks: an int8 contraction accumulating below i32 is an
``int8-accum-matmul`` hazard, and an int8 contraction with no ``q8_``
tag is a ``quant-missing-scale`` hazard (tag presence ⟺ a recorded
scale, because :func:`wrap_op` only tags ops it holds a threshold
for).  The committed ``contracts/prec/serving_bert_int8.json`` ledger
and ``contracts/serving_bert_int8.json`` hlocheck contract pin the
quantized serving ladder hazard-free with the s8×s8→s32 dot
signature inventoried.

Kill switch: ``MXTPU_QUANT=0`` forces quantization off everywhere and
the lowered programs are bit-identical to the unquantized path
(asserted by ``tests/test_quant.py``, the MXTPU_AMP=0 contract one
tier down).  ``python -m mxtpu.quant --self-check`` probes the policy
parse, a calibrate→quantize round trip on a tiny net (zero hazards,
correct scale bookkeeping) and the kill-switch precedence (wired as a
``tools/ci_static.py`` stage).
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
from typing import Any, Dict, FrozenSet, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import knobs
from ..base import MXNetError

__all__ = [
    "POLICY_PATH", "load_policy", "policy_sets", "resolve",
    "calib_config", "make_collector", "MinMaxCollector",
    "EntropyCollector", "calibrating", "quantize", "active",
    "wrap_op", "QUANT_READY", "self_check",
]

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
POLICY_PATH = os.path.join(_REPO_ROOT, "contracts", "quant_policy.json")

_F32 = jnp.float32
_I8 = jnp.int8
_I32 = jnp.int32
_QMAX = 127.0  # symmetric int8: [-127, 127], -128 unused (reference)


# ----------------------------------------------------------------------
# policy file
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def load_policy(path: Optional[str] = None) -> Dict[str, Any]:
    """Parse ``contracts/quant_policy.json`` (cached)."""
    p = path or POLICY_PATH
    try:
        with open(p, "r", encoding="utf-8") as f:
            policy = json.load(f)
    except (OSError, ValueError) as e:
        raise MXNetError(
            f"mxtpu.quant: cannot load quant policy {p!r}: {e}")
    for key in ("allow", "deny", "calibration"):
        if not isinstance(policy.get(key), dict):
            raise MXNetError(
                f"mxtpu.quant: policy {p!r} missing section {key!r} — "
                f"regenerate with `python -m tools.mxprec --quant "
                f"--update`")
    return policy


@functools.lru_cache(maxsize=None)
def policy_sets(path: Optional[str] = None
                ) -> Tuple[FrozenSet[str], FrozenSet[str]]:
    """(allow, deny) opcode sets from the policy file."""
    policy = load_policy(path)
    return frozenset(policy["allow"]), frozenset(policy["deny"])


def resolve(flag: Optional[bool] = None) -> bool:
    """Resolve the effective quantization switch: ``MXTPU_QUANT=0``
    kills it everywhere, ``MXTPU_QUANT=1`` forces it on, otherwise the
    per-call ``quant=`` argument decides (default off) — the same
    precedence ladder as ``mxtpu.amp.resolve``."""
    env = str(knobs.get("MXTPU_QUANT")).strip().lower()
    if env in ("0", "off", "false", "no"):
        return False
    if flag is not None:
        return bool(flag)
    return env in ("1", "on", "true", "yes")


def calib_config() -> Tuple[str, int]:
    """(collector mode, max batches) for calibration runs."""
    mode = str(knobs.get("MXTPU_QUANT_CALIB")).strip().lower()
    if mode not in ("minmax", "entropy"):
        raise MXNetError(
            f"mxtpu.quant: MXTPU_QUANT_CALIB={mode!r} — use "
            f"`minmax` or `entropy`")
    batches = max(1, int(knobs.get("MXTPU_QUANT_CALIB_BATCHES")))
    return mode, batches


# ----------------------------------------------------------------------
# calibration collectors (the reference's two algorithms).  Both are
# pure functions of the observed values — byte-identical thresholds
# for identical batches; mxtpu/quant/ sits in mxlint's deterministic
# scope so an RNG or clock call here is a lint failure, not a review
# comment.
# ----------------------------------------------------------------------
def _round6(x: float) -> float:
    """6-significant-figure rounding: thresholds land in committed
    JSON (quant_policy.json evidence), so pin a byte-stable decimal
    form well above f32 noise."""
    return float(f"{float(x):.6g}")


def _observed_np(value):
    import numpy as np
    try:
        # mxlint: sync-point — calibration is an offline host pass
        return np.asarray(value, np.float32)
    except Exception as e:
        raise MXNetError(
            "mxtpu.quant: calibration observed a non-concrete value "
            "(tracer?) — run calibration batches eagerly, outside "
            f"jit: {e}")


class MinMaxCollector:
    """Per-key symmetric |x| threshold = running abs-max (the
    reference's ``calib_mode='naive'``)."""

    mode = "minmax"

    def __init__(self):
        self._absmax: Dict[str, float] = {}

    def observe(self, key: str, value) -> None:
        arr = _observed_np(value)
        m = float(abs(arr).max()) if arr.size else 0.0
        prev = self._absmax.get(key, 0.0)
        if m > prev:
            self._absmax[key] = m
        else:
            self._absmax.setdefault(key, prev)

    def thresholds(self) -> Dict[str, float]:
        return {k: _round6(max(v, 1e-6))
                for k, v in sorted(self._absmax.items())}


class EntropyCollector:
    """Per-key KL-minimizing |x| threshold over every observed batch
    (the reference's ``calib_mode='entropy'``, via
    :func:`mxtpu.contrib.quantization.optimal_threshold` — a
    deterministic histogram search, no sampling)."""

    mode = "entropy"

    def __init__(self, num_bins: int = 2001,
                 num_quantized_bins: int = 255):
        self._chunks: Dict[str, List] = {}
        self._num_bins = num_bins
        self._num_quantized_bins = num_quantized_bins

    def observe(self, key: str, value) -> None:
        self._chunks.setdefault(key, []).append(
            _observed_np(value).ravel())

    def thresholds(self) -> Dict[str, float]:
        import numpy as np
        from ..contrib.quantization import optimal_threshold
        out = {}
        for key in sorted(self._chunks):
            arr = np.concatenate(self._chunks[key])
            out[key] = _round6(max(optimal_threshold(
                arr, self._num_bins, self._num_quantized_bins), 1e-6))
        return out


def make_collector(mode: Optional[str] = None):
    """Collector for ``mode`` (default: the MXTPU_QUANT_CALIB knob)."""
    if mode is None:
        mode, _ = calib_config()
    if mode == "minmax":
        return MinMaxCollector()
    if mode == "entropy":
        return EntropyCollector()
    raise MXNetError(f"mxtpu.quant: unknown collector mode {mode!r}")


# ----------------------------------------------------------------------
# calibration / quantization scopes (trace-time module globals — the
# same zero-overhead-off shape as amp._ACTIVE: one attribute read on
# the off path of _invoke_op_inner).  The per-scope dispatch counter
# gives every candidate op a stable key; eager calibration and the
# traced quantized program both interpret the SAME symbol in the same
# topological order, so key <-> op instance is a bijection across the
# two passes.
# ----------------------------------------------------------------------
_ACTIVE = False
_MODE = None        # "calib" | "quant" while a scope is live
_COLLECT = None     # live collector (calib scope)
_SCALES = None      # {key: activation |x| threshold} (quant scope)
_COUNTER = 0        # candidate ops seen since scope entry


@contextlib.contextmanager
def calibrating(collector):
    """Scope under which candidate contractions dispatched through the
    nd op registry have their float data input OBSERVED (host-side)
    by ``collector`` instead of being rewritten.  Eager-only."""
    global _ACTIVE, _MODE, _COLLECT, _COUNTER
    prev = (_ACTIVE, _MODE, _COLLECT, _COUNTER)
    _ACTIVE, _MODE, _COLLECT, _COUNTER = True, "calib", collector, 0
    try:
        yield collector
    finally:
        _ACTIVE, _MODE, _COLLECT, _COUNTER = prev


@contextlib.contextmanager
def quantize(scales: Dict[str, Any], enabled: bool = True):
    """Scope under which candidate contractions with a recorded
    activation threshold run as int8×int8 GEMMs with i32
    accumulation.  ``scales`` maps dispatch keys to thresholds (float,
    or a ``{"threshold": ...}`` dict as stored in policy evidence)."""
    norm = {}
    for k, v in (scales or {}).items():
        t = v.get("threshold") if isinstance(v, dict) else v
        if t is not None and float(t) > 0.0:
            norm[k] = float(t)
    global _ACTIVE, _MODE, _SCALES, _COUNTER
    prev = (_ACTIVE, _MODE, _SCALES, _COUNTER)
    if enabled:
        _ACTIVE, _MODE, _SCALES, _COUNTER = True, "quant", norm, 0
    try:
        yield
    finally:
        _ACTIVE, _MODE, _SCALES, _COUNTER = prev


def active() -> bool:
    return _ACTIVE


# ----------------------------------------------------------------------
# quantization decision + int8 replacements
# ----------------------------------------------------------------------
# Contraction ops with an int8 serving form (the reference quantizes
# quantized_fully_connected / quantized_conv; attention batch_dots are
# activation×activation — no weight-side per-channel scale — and stay
# on the bf16/f32 path, like the reference's FP32 fallback ops).
QUANT_READY = frozenset({
    "FullyConnected", "fully_connected",
    "Convolution", "convolution", "Convolution_v1",
})

_DECISION_CACHE: Dict[Any, bool] = {}


def _param_key(resolved: Dict[str, Any]) -> str:
    try:
        return repr(sorted(resolved.items(), key=lambda kv: kv[0]))
    except Exception:
        return "<unkeyable>"


def _quant_decision(name: str, op, arrays, resolved) -> bool:
    """The policy drives the rewrite, exactly like amp._cast_decision:
    the op's function is abstractly traced and the decision is
    ``opcodes ⊆ allow`` — a deny-class transcendental anywhere inside
    vetoes the int8 form.  Cached per (op, avals, params)."""
    from .. import amp as _amp
    key = (name,
           tuple((tuple(a.shape), str(a.dtype)) for a in arrays),
           _param_key(resolved))
    hit = _DECISION_CACHE.get(key)
    if hit is not None:
        return hit
    allow, deny = policy_sets()
    structs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]
    # no catch-all here: a trace that fails must surface, not turn
    # the int8 rewrite off for the op in silence
    closed = jax.make_jaxpr(
        lambda *xs: op.fn(*xs, **resolved))(*structs)
    opcodes: set = set()
    _amp._walk_opcodes(closed.jaxpr, opcodes)
    decision = bool(opcodes) and opcodes <= allow
    assert not (opcodes & deny) or not decision
    _DECISION_CACHE[key] = decision
    return decision


def _quantize_tensor(x, threshold: float):
    """f32 -> int8, symmetric per-tensor: round(x * 127/t) clipped to
    [-127, 127] (``detection_impl._quantize``'s math, inlined so XLA
    fuses it into the GEMM's prologue)."""
    scaled = x * jnp.float32(_QMAX / threshold)
    return jnp.clip(jnp.round(scaled), -_QMAX, _QMAX).astype(_I8)


def _channel_thresholds(w, out_axis: int = 0):
    """Per-output-channel |w| thresholds, computed IN-GRAPH: weights
    are runtime inputs to the compiled bucket, so the per-channel
    scales ride the trace and one executable serves every checkpoint
    of the architecture."""
    red = tuple(d for d in range(w.ndim) if d != out_axis)
    return jnp.maximum(jnp.max(jnp.abs(w), axis=red),
                       jnp.float32(1e-12))


def _q_fully_connected(key: str, t_act: float, resolved):
    no_bias = bool(resolved.get("no_bias", False))
    flatten = bool(resolved.get("flatten", True))

    def fn(*arrs):
        x, w = arrs[0], arrs[1]
        b = arrs[2] if len(arrs) > 2 else None
        with jax.named_scope(f"q8_{key}"):
            if flatten and x.ndim > 2:
                x = x.reshape(x.shape[0], -1)
            qx = _quantize_tensor(x, t_act)
            t_w = _channel_thresholds(w)           # (num_hidden,)
            qw = jnp.clip(jnp.round(w * (_QMAX / t_w)[:, None]),
                          -_QMAX, _QMAX).astype(_I8)
            acc = lax.dot_general(
                qx, qw, (((qx.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=_I32)
            # dequantize epilogue: t_w broadcasts on the output
            # channel (last) axis; adjacent to a downstream quantized
            # op's entry quantize this pair IS the requantize, fused
            # by XLA into one rescale
            y = acc.astype(_F32) * (jnp.float32(t_act / _QMAX)
                                    * (t_w / _QMAX))
            if b is not None and not no_bias:
                y = y + b
        return y
    return fn


def _q_convolution(key: str, t_act: float, resolved):
    kernel = tuple(resolved.get("kernel") or ())
    ndim = len(kernel)
    layout = resolved.get("layout") or \
        {1: "NCW", 2: "NCHW", 3: "NCDHW"}.get(ndim)
    if layout not in ("NCW", "NCHW", "NCDHW"):
        return None  # channels-last stays on the float path
    no_bias = bool(resolved.get("no_bias", False))
    groups = int(resolved.get("num_group") or 1)

    def fn(*arrs):
        from ..ndarray import ops_impl
        x, w = arrs[0], arrs[1]
        b = arrs[2] if len(arrs) > 2 else None
        stride = ops_impl._tuple(resolved.get("stride"), ndim)
        dilate = ops_impl._tuple(resolved.get("dilate"), ndim)
        pad = resolved.get("pad")
        pad = ops_impl._tuple(pad, ndim) if pad is not None \
            else (0,) * ndim
        with jax.named_scope(f"q8_{key}"):
            qx = _quantize_tensor(x, t_act)
            t_w = _channel_thresholds(w)           # (O,) of OI<sp>
            qw = jnp.clip(
                jnp.round(w * (_QMAX / t_w).reshape(
                    (-1,) + (1,) * (w.ndim - 1))),
                -_QMAX, _QMAX).astype(_I8)
            acc = lax.conv_general_dilated(
                qx, qw, window_strides=stride,
                padding=[(p, p) for p in pad],
                rhs_dilation=dilate,
                dimension_numbers=ops_impl._CONV_DN[layout],
                feature_group_count=groups,
                preferred_element_type=_I32)
            y = acc.astype(_F32) * (
                jnp.float32(t_act / _QMAX)
                * (t_w / _QMAX).reshape((1, -1) + (1,) * ndim))
            if b is not None and not no_bias:
                y = y + b.reshape((1, -1) + (1,) * ndim)
        return y
    return fn


def wrap_op(name: str, op, arrays, resolved):
    """Inside a quant scope, either OBSERVE a candidate op's data
    input (calibration) or return its int8 replacement (quantized
    serving) — or None to leave the op on the float path.  Called
    from ``ndarray._invoke_op_inner``; key assignment (the per-scope
    dispatch counter) is identical in both modes, so calibration keys
    line up with trace-time lookups by construction."""
    if name not in QUANT_READY or len(arrays) < 2:
        return None
    data, weight = arrays[0], arrays[1]
    if getattr(data, "dtype", None) != _F32 or \
            getattr(weight, "dtype", None) != _F32:
        return None
    global _COUNTER
    key = f"{name}_{_COUNTER}"
    _COUNTER += 1
    if _MODE == "calib":
        _COLLECT.observe(key, data)
        # evidence collectors (tools/mxprec --quant) also record the
        # per-channel weight scales the quantized trace will compute
        # in-graph; plain collectors don't implement the hook
        ow = getattr(_COLLECT, "observe_weight", None)
        if ow is not None:
            ow(key, weight)
        return None
    t_act = _SCALES.get(key) if _SCALES else None
    if t_act is None:
        return None  # no recorded scale -> bf16/f32 fallback
    if not _quant_decision(name, op, arrays, resolved):
        return None
    if name in ("Convolution", "convolution", "Convolution_v1"):
        return _q_convolution(key, t_act, resolved)
    return _q_fully_connected(key, t_act, resolved)


# ----------------------------------------------------------------------
# self-check (ci_static stage): policy parse + calibrate->quantize
# round trip on a tiny net + scale bookkeeping + kill-switch shape
# ----------------------------------------------------------------------
def _check_policy() -> None:
    policy = load_policy()
    allow, deny = policy_sets()
    if "dot" not in allow:
        raise MXNetError(
            "quant self-check: policy allow class lost `dot`")
    if not deny:
        raise MXNetError("quant self-check: policy deny class empty")
    if allow & deny:
        raise MXNetError("quant self-check: policy classes overlap")
    calib = policy.get("calibration", {})
    for key in ("activation_thresholds", "weight_scales",
                "int8_contractions"):
        if not calib.get(key):
            raise MXNetError(
                f"quant self-check: policy calibration evidence lost "
                f"{key!r} — regenerate with `python -m tools.mxprec "
                f"--quant --update`")


def _tiny_net_arrays():
    import numpy as np
    x = np.linspace(-1.5, 1.5, 48, dtype=np.float32).reshape(8, 6)
    w1 = np.linspace(1, -1, 24, dtype=np.float32).reshape(4, 6)
    b1 = np.linspace(-0.2, 0.2, 4, dtype=np.float32)
    w2 = np.linspace(-0.8, 0.8, 12, dtype=np.float32).reshape(3, 4)
    return x, w1, b1, w2


def _tiny_forward(nd, x, w1, b1, w2):
    h = nd.FullyConnected(x, w1, b1, num_hidden=4)
    h = nd.relu(h)
    return nd.FullyConnected(h, w2, num_hidden=3, no_bias=True)


def _check_roundtrip(verbose: bool = False) -> None:
    import numpy as np
    from .. import nd
    from ..analysis import dtypeflow, lowered_text
    from ..ndarray.ndarray import NDArray

    xh, w1h, b1h, w2h = _tiny_net_arrays()
    args = [nd.array(a) for a in (xh, w1h, b1h, w2h)]

    # eager calibration: both collectors see the same dispatch keys
    scales = {}
    for collector in (MinMaxCollector(), EntropyCollector()):
        with calibrating(collector):
            ref = _tiny_forward(nd, *args)
        scales[collector.mode] = collector.thresholds()
    for mode, sc in scales.items():
        if sorted(sc) != ["FullyConnected_0", "FullyConnected_1"]:
            raise MXNetError(
                f"quant self-check: {mode} collector keyed "
                f"{sorted(sc)} — expected one key per candidate "
                f"dispatch (scale bookkeeping broken)")

    # determinism: a second calibration pass is byte-identical
    again = MinMaxCollector()
    with calibrating(again):
        _tiny_forward(nd, *args)
    if again.thresholds() != scales["minmax"]:
        raise MXNetError(
            "quant self-check: calibration is not deterministic "
            "across identical passes")

    # traced quantized program: int8 dots, i32 accumulation, tagged,
    # zero hazards — and numerically close to the float reference
    table = scales["minmax"]

    def prog(x, w1, b1, w2):
        wrapped = [NDArray(a, None, _placed=True)
                   for a in (x, w1, b1, w2)]
        with quantize(table):
            return _tiny_forward(nd, *wrapped)._data

    jargs = [a._data for a in args]
    text = lowered_text(prog, *jargs)
    ledger = dtypeflow.program_ledger(text)
    if ledger["hazards"]:
        raise MXNetError(
            f"quant self-check: quantized round-trip produced "
            f"hazards: {ledger['hazards']}")
    census = dtypeflow.int8_contraction_census(text)
    if census.get("s8xs8->s32") != 2:
        raise MXNetError(
            f"quant self-check: expected 2 s8xs8->s32 contractions, "
            f"census={census}")
    if "q8_FullyConnected_0" not in text or \
            "q8_FullyConnected_1" not in text:
        raise MXNetError(
            "quant self-check: quantized dots lost their q8_<key> "
            "scale tags")
    run = jax.jit(prog)
    got = np.asarray(run(*jargs))
    want = ref.asnumpy()
    err = float(np.abs(got - want).max())
    tol = 0.05 * max(1.0, float(np.abs(want).max()))
    if err > tol:
        raise MXNetError(
            f"quant self-check: int8 output drifted {err:.4f} from "
            f"f32 (tol {tol:.4f})")

    # kill-switch shape: outside a scope (and under quantize(...,
    # enabled=False)) the same program carries no int8 at all
    def prog_off(x, w1, b1, w2):
        wrapped = [NDArray(a, None, _placed=True)
                   for a in (x, w1, b1, w2)]
        with quantize(table, enabled=False):
            return _tiny_forward(nd, *wrapped)._data
    off = lowered_text(prog_off, *jargs)
    if "s8[" in off or "q8_" in off:
        raise MXNetError(
            "quant self-check: int8 leaked outside the quantize scope")
    if verbose:
        print(f"quant self-check: round trip OK ({census} tagged, "
              f"zero hazards, |err|={err:.4f} <= {tol:.4f})")


def self_check(verbose: bool = False) -> int:
    """Probe the quantization contracts; returns 0 on success (raises
    on failure).  Run as a ci_static stage: ``python -m mxtpu.quant
    --self-check``."""
    _check_policy()
    if verbose:
        print(f"quant self-check: policy parse OK ({POLICY_PATH})")
    _check_roundtrip(verbose)
    if verbose:
        print("quant self-check: calibrate->quantize round trip OK "
              "(deterministic scales, i32 accumulation, no leak "
              "outside the scope)")
    return 0
