"""``mxtpu.amp`` — policy-driven bf16 autocast with f32 accumulation.

Reference: ``python/mxnet/contrib/amp/``† (MXNet v1.x automatic mixed
precision).  The reference hand-maintains FP16_FUNCS/FP32_FUNCS op
lists; here the op policy is *machine-derived* — PR 10's mxprec pass
classified every float-carrying HLO opcode across the six contract
targets into ``contracts/amp_policy.json`` (allow / deny / fp32_force /
inherit), and this module is the pass that consumes that file at trace
time.  Runtime behaviour and the committed evidence can never diverge:
an op is cast to bf16 only when its lowered jaxpr contains an
allow-class contraction opcode and nothing from the deny or fp32_force
classes.

How a cast decision is made (``_cast_decision``):

* only ops in :data:`ACCUM_READY` are candidates — the contraction ops
  whose implementations thread ``preferred_element_type=float32`` so
  bf16 inputs still accumulate in f32 (the policy's accumulation rule);
* the op's function is abstractly traced (``jax.make_jaxpr`` on the
  actual input avals + resolved params), its primitives mapped to HLO
  opcodes, and the decision is ``opcodes ⊆ allow`` — a deny-listed
  transcendental or fp32_force reduction anywhere inside vetoes the
  cast.  Decisions are cached per (op, avals, params) signature.

The transform itself is an interposition at the single eager/symbolic
dispatch choke point (``ndarray._invoke_op_inner``): inside an
:func:`autocast` scope, candidate ops have their f32 inputs cast to
bf16 *inside* the recorded function, so both jax AD and the eager
autograd tape differentiate through the casts.  Everything else —
transcendentals, reductions, collectives, elementwise glue — stays in
f32 because ``TrainStep``/``ModelRunner`` upcast every float parameter
to f32 at graph entry; the only sub-f32 values in the program are the
short bf16 edges feeding MXU contractions.  XLA folds the resulting
``convert(convert(w))`` chains at the weight edges.

Kill switch: ``MXTPU_AMP=0`` forces AMP off everywhere and the lowered
programs are bit-identical to pre-AMP behaviour (asserted by
``tests/test_amp.py``).  ``python -m mxtpu.amp --self-check`` probes
the policy parse, an autocast round-trip on the selftest program, and
the loss-scaler unit behaviour (wired as a ``tools/ci_static.py``
stage).
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
from typing import Any, Dict, FrozenSet, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import knobs
from ..base import MXNetError

__all__ = [
    "POLICY_PATH", "load_policy", "policy_sets", "resolve",
    "scaler_config", "autocast", "active", "matmul_preferred",
    "wrap_op", "conv_general", "dot_general", "matmul",
    "scaler_init", "scaler_update",
    "all_finite", "self_check",
]

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
POLICY_PATH = os.path.join(_REPO_ROOT, "contracts", "amp_policy.json")

_BF16 = jnp.bfloat16
_F32 = jnp.float32
_SCALE_MAX = 2.0 ** 24


# ----------------------------------------------------------------------
# policy file
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def load_policy(path: Optional[str] = None) -> Dict[str, Any]:
    """Parse ``contracts/amp_policy.json`` (cached)."""
    p = path or POLICY_PATH
    try:
        with open(p, "r", encoding="utf-8") as f:
            policy = json.load(f)
    except (OSError, ValueError) as e:
        raise MXNetError(f"mxtpu.amp: cannot load AMP policy {p!r}: {e}")
    for key in ("allow", "deny", "fp32_force", "inherit"):
        if not isinstance(policy.get(key), dict):
            raise MXNetError(
                f"mxtpu.amp: policy {p!r} missing opcode class {key!r} "
                f"— regenerate with `python -m tools.mxprec --update`")
    return policy


@functools.lru_cache(maxsize=None)
def policy_sets(path: Optional[str] = None
                ) -> Tuple[FrozenSet[str], FrozenSet[str], FrozenSet[str]]:
    """(allow, deny, fp32_force) opcode sets from the policy file."""
    policy = load_policy(path)
    return (frozenset(policy["allow"]),
            frozenset(policy["deny"]),
            frozenset(policy["fp32_force"]))


def resolve(flag: Optional[bool] = None) -> bool:
    """Resolve the effective AMP switch: ``MXTPU_AMP=0`` kills it
    everywhere, ``MXTPU_AMP=1`` forces it on, otherwise the per-call
    ``amp=`` argument decides (default off)."""
    env = str(knobs.get("MXTPU_AMP")).strip().lower()
    if env in ("0", "off", "false", "no"):
        return False
    if flag is not None:
        return bool(flag)
    return env in ("1", "on", "true", "yes")


def scaler_config() -> Tuple[bool, float, int]:
    """(enabled, init_scale, grow_window) for the dynamic loss scaler.
    ``MXTPU_AMP_LOSS_SCALE=0`` disables scaling entirely."""
    init = float(knobs.get("MXTPU_AMP_LOSS_SCALE"))
    window = max(1, int(knobs.get("MXTPU_AMP_SCALE_WINDOW")))
    return init > 0.0, init, window


# ----------------------------------------------------------------------
# autocast scope (trace-time module globals — same zero-overhead-off
# shape as profiler._ACTIVE: one attribute read on the off path)
# ----------------------------------------------------------------------
_ACTIVE = False
_PREFERRED = None  # jnp.float32 while a scope is active


@contextlib.contextmanager
def autocast(enabled: bool = True):
    """Scope under which allow-listed contractions dispatched through
    the nd op registry run on bf16 inputs with f32 accumulation."""
    global _ACTIVE, _PREFERRED
    prev = (_ACTIVE, _PREFERRED)
    _ACTIVE, _PREFERRED = bool(enabled), (_F32 if enabled else None)
    try:
        yield
    finally:
        _ACTIVE, _PREFERRED = prev


def active() -> bool:
    return _ACTIVE


def matmul_preferred(*operands) -> Optional[Any]:
    """The ``preferred_element_type`` a contraction should request:
    f32 when an autocast scope is live and some float operand is
    sub-f32, else None (identical lowering to pre-AMP)."""
    if _PREFERRED is None:
        return None
    sub = False
    for a in operands:
        dt = getattr(a, "dtype", None)
        if dt is None or not jnp.issubdtype(dt, jnp.floating):
            return None
        if jnp.dtype(dt).itemsize < 4:
            sub = True
    return _PREFERRED if sub else None


# ----------------------------------------------------------------------
# cast classification
# ----------------------------------------------------------------------
# Contraction ops whose impls thread preferred_element_type=f32 so a
# bf16 cast keeps f32 accumulation.  Deconvolution is deliberately
# absent: lax.conv_transpose has no f32-accumulating VJP path here.
ACCUM_READY = frozenset({
    "dot", "batch_dot", "matmul", "linalg_gemm", "linalg_gemm2",
    "FullyConnected", "fully_connected",
    "Convolution", "convolution", "Convolution_v1",
})

# jax primitive -> pre-optimization HLO opcode, for the policy-class
# veto scan.  Structural/elementwise primitives are deliberately
# unmapped (the policy's `inherit` class); any *mapped* opcode outside
# the allow class vetoes the cast.
_PRIM_TO_HLO = {
    "dot_general": "dot",
    "conv_general_dilated": "convolution",
    "div": "divide",
    "exp": "exponential", "exp2": "exponential",
    "expm1": "exponential",
    "log": "log", "log1p": "log",
    "rsqrt": "rsqrt", "sqrt": "sqrt", "cbrt": "cbrt",
    "tanh": "tanh", "tan": "tan",
    "sin": "sine", "cos": "cosine", "atan2": "atan2",
    "erf": "erf", "erf_inv": "erf-inv", "logistic": "logistic",
    "pow": "power",
    "reduce_sum": "reduce", "reduce_prod": "reduce",
    "reduce_max": "reduce", "reduce_min": "reduce",
    "reduce_and": "reduce", "reduce_or": "reduce",
    "argmax": "reduce", "argmin": "reduce",
    "cumsum": "reduce-window", "cumprod": "reduce-window",
    "cummax": "reduce-window", "cummin": "reduce-window",
    "reduce_window_sum": "reduce-window",
    "reduce_window_max": "reduce-window",
    "reduce_window_min": "reduce-window",
    "psum": "all-reduce", "pmax": "all-reduce", "pmin": "all-reduce",
    "psum_scatter": "reduce-scatter",
    "all_gather": "all-gather",
    "all_to_all": "all-to-all",
}

_CAST_CACHE: Dict[Any, bool] = {}


def _sub_jaxprs(value):
    from jax.extend import core
    if isinstance(value, core.Jaxpr):
        yield value
    elif isinstance(value, core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _walk_opcodes(jaxpr, out: set) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "pallas_call":
            # kernel bodies are opaque custom calls; their precision
            # contract lives in the policy's custom_calls section
            out.add("custom-call")
            continue
        hlo = _PRIM_TO_HLO.get(prim)
        if hlo is not None:
            out.add(hlo)
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _walk_opcodes(sub, out)


def _param_key(resolved: Dict[str, Any]) -> str:
    try:
        return repr(sorted(resolved.items(), key=lambda kv: kv[0]))
    except Exception:
        return "<unkeyable>"


def _cast_decision(name: str, op, arrays, resolved) -> bool:
    key = (name,
           tuple((tuple(a.shape), str(a.dtype)) for a in arrays),
           _param_key(resolved))
    hit = _CAST_CACHE.get(key)
    if hit is not None:
        return hit
    allow, deny, force = policy_sets()
    structs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]
    # no catch-all here: a trace that fails must surface, not turn
    # autocast off for the op in silence
    closed = jax.make_jaxpr(
        lambda *xs: op.fn(*xs, **resolved))(*structs)
    opcodes: set = set()
    _walk_opcodes(closed.jaxpr, opcodes)
    # the policy drives the decision: cast only when the op lowers
    # to allow-class contractions and nothing deny/fp32_force-class
    decision = bool(opcodes) and opcodes <= allow
    assert not (opcodes & (deny | force)) or not decision
    _CAST_CACHE[key] = decision
    return decision


def wrap_op(name: str, op, arrays, resolved):
    """Inside an autocast scope, return a replacement for ``op.fn``
    that casts f32 inputs to bf16 (f32 accumulation comes from the
    impl's preferred_element_type) — or None to leave the op alone.
    Called from ``ndarray._invoke_op_inner``."""
    if name not in ACCUM_READY:
        return None
    if not _cast_decision(name, op, arrays, resolved):
        return None

    def fn(*arrs):
        arrs = [a.astype(_BF16)
                if getattr(a, "dtype", None) == _F32 else a
                for a in arrs]
        return op.fn(*arrs, **resolved)
    return fn


# ----------------------------------------------------------------------
# bf16 convolution with f32 accumulation.  lax.conv_general_dilated's
# builtin transpose rule rejects a bf16-operand/f32-cotangent pair on
# this jax pin, so the f32-accumulating conv needs an explicit VJP: the
# cotangent is cast back to bf16 (the AMP gradient dtype) and both
# transpose convolutions again request f32 accumulation.
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def conv_general(x, w, strides, padding, rhs_dilation, dn, groups):
    return lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        rhs_dilation=rhs_dilation, dimension_numbers=dn,
        feature_group_count=groups, preferred_element_type=_F32)


def _conv_fwd(x, w, strides, padding, rhs_dilation, dn, groups):
    return conv_general(x, w, strides, padding, rhs_dilation, dn,
                        groups), (x, w)


def _conv_bwd(strides, padding, rhs_dilation, dn, groups, res, g):
    from jax._src.lax import convolution as _convmod
    x, w = res
    g = g.astype(x.dtype)
    dnums = lax.conv_dimension_numbers(x.shape, w.shape, dn)
    kw = dict(window_strides=strides, padding=padding,
              lhs_dilation=(1,) * len(strides),
              rhs_dilation=rhs_dilation, dimension_numbers=dnums,
              feature_group_count=groups, batch_group_count=1,
              precision=None, preferred_element_type=_F32,
              out_sharding=None)
    dx = _convmod._conv_general_dilated_transpose_lhs(g, x, w, **kw)
    dw = _convmod._conv_general_dilated_transpose_rhs(g, x, w, **kw)
    return dx.astype(x.dtype), dw.astype(w.dtype)


conv_general.defvjp(_conv_fwd, _conv_bwd)


# ----------------------------------------------------------------------
# bf16 dot_general with f32 accumulation, both directions.  Without
# this, lax's builtin transpose rule promotes the bf16 operand to match
# the f32 cotangent and the *backward* GEMMs — two thirds of a
# transformer's contraction FLOPs — silently run on f32.  Same shape as
# conv_general: residuals are the bf16 inputs, the cotangent is cast to
# the AMP gradient dtype first, and both transpose dots again request
# f32 accumulation before the edge downcast.
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def dot_general(lhs, rhs, dnums):
    return lax.dot_general(lhs, rhs, dimension_numbers=dnums,
                           preferred_element_type=_F32)


def _dg_fwd(lhs, rhs, dnums):
    return dot_general(lhs, rhs, dnums), (lhs, rhs)


def _dg_bwd(dnums, res, g):
    from jax._src.lax import lax as _laxmod
    lhs, rhs = res
    g = g.astype(lhs.dtype)
    kw = dict(dimension_numbers=dnums, precision=None,
              preferred_element_type=_F32, out_sharding=None)
    dl = _laxmod._dot_general_transpose_lhs(g, lhs, rhs, **kw)
    dr = _laxmod._dot_general_transpose_rhs(g, lhs, rhs, **kw)
    return dl.astype(lhs.dtype), dr.astype(rhs.dtype)


dot_general.defvjp(_dg_fwd, _dg_bwd)


def matmul(a, b):
    """``jnp.matmul`` semantics (ndim >= 2 operands) routed through
    :func:`dot_general` — batch dims broadcast, last axis of ``a``
    contracts with the second-to-last of ``b``."""
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    a = jnp.broadcast_to(a, batch + a.shape[-2:])
    b = jnp.broadcast_to(b, batch + b.shape[-2:])
    nb = len(batch)
    dn = (((a.ndim - 1,), (b.ndim - 2,)),
          (tuple(range(nb)), tuple(range(nb))))
    return dot_general(a, b, dn)


# ----------------------------------------------------------------------
# dynamic loss scaler (pure functions; state is threaded through the
# train step and rides save_states/load_states)
# ----------------------------------------------------------------------
def scaler_init(init_scale: Optional[float] = None):
    """(scale f32, good_steps i32, skipped_steps i32)."""
    if init_scale is None:
        init_scale = float(knobs.get("MXTPU_AMP_LOSS_SCALE"))
    return (jnp.asarray(init_scale, jnp.float32),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(0, jnp.int32))


def scaler_update(state, finite, window: Optional[int] = None):
    """Grow x2 after ``window`` consecutive finite steps (capped at
    2^24), halve (floor 1.0) and count a skipped step on non-finite."""
    if window is None:
        window = max(1, int(knobs.get("MXTPU_AMP_SCALE_WINDOW")))
    scale, good, skipped = state
    finite = jnp.asarray(finite, bool)
    good1 = good + 1
    grow = jnp.logical_and(finite, good1 >= window)
    new_scale = jnp.where(
        finite,
        jnp.where(grow, jnp.minimum(scale * 2.0, _SCALE_MAX), scale),
        jnp.maximum(scale * 0.5, 1.0))
    new_good = jnp.where(jnp.logical_and(finite, jnp.logical_not(grow)),
                         good1, jnp.zeros_like(good))
    new_skipped = skipped + jnp.where(finite, 0, 1).astype(skipped.dtype)
    return (new_scale.astype(scale.dtype), new_good.astype(good.dtype),
            new_skipped)


def all_finite(tree) -> Any:
    """Scalar bool: every float leaf of ``tree`` is finite."""
    ok = jnp.asarray(True)
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))
    return ok


# ----------------------------------------------------------------------
# self-check (ci_static stage): policy parse + autocast round-trip on
# the selftest program + scaler unit probe
# ----------------------------------------------------------------------
def _check_policy() -> None:
    policy = load_policy()
    allow, deny, force = policy_sets()
    if "dot" not in allow:
        raise MXNetError("amp self-check: policy allow class lost `dot`")
    if not deny or "reduce" not in force:
        raise MXNetError("amp self-check: policy deny/fp32_force empty")
    if allow & (deny | force):
        raise MXNetError("amp self-check: policy classes overlap")
    for cc in ("batch_norm", "flash_attention", "layer_norm"):
        meta = policy.get("custom_calls", {}).get(cc, {})
        if meta.get("accum_dtype") != "f32":
            raise MXNetError(
                f"amp self-check: custom call {cc} lost its f32 "
                f"accumulation contract")


def _check_autocast_roundtrip() -> None:
    import numpy as np
    from .. import nd
    from ..analysis import dtypeflow, lowered_text

    def program(a, b):
        with autocast():
            y = nd.dot(nd.NDArray(a, None, _placed=True),
                       nd.NDArray(b, None, _placed=True))
            z = nd.softmax(y)
        return (z._data.astype(jnp.float32) ** 2).sum()

    a = jnp.asarray(np.linspace(-1, 1, 64, dtype=np.float32).reshape(8, 8))
    b = jnp.asarray(np.linspace(1, -1, 32, dtype=np.float32).reshape(8, 4))
    text = lowered_text(program, a, b)
    ledger = dtypeflow.program_ledger(text)
    hazards = ledger.get("hazards", [])
    if hazards:
        raise MXNetError(
            f"amp self-check: autocast round-trip produced hazards: "
            f"{hazards}")
    if "bf16" not in text:
        raise MXNetError(
            "amp self-check: autocast produced no bf16 edges on the "
            "selftest dot")
    flows = ledger.get("flows", {})
    if not any("f32->bf16" in k or ("f32" in k and "bf16" in k)
               for k in flows):
        raise MXNetError(
            f"amp self-check: no f32->bf16 cast flow recorded "
            f"({sorted(flows)})")
    # kill-switch shape: outside a scope the same program is pure f32
    def program_off(a, b):
        y = nd.dot(nd.NDArray(a, None, _placed=True),
                   nd.NDArray(b, None, _placed=True))
        z = nd.softmax(y)
        return (z._data.astype(jnp.float32) ** 2).sum()
    if "bf16" in lowered_text(program_off, a, b):
        raise MXNetError("amp self-check: bf16 leaked outside autocast")


def _check_scaler() -> None:
    import numpy as np
    upd = jax.jit(functools.partial(scaler_update, window=3))
    st = scaler_init(1024.0)
    for _ in range(3):
        st = upd(st, True)
    if float(st[0]) != 2048.0 or int(st[1]) != 0:
        raise MXNetError(f"amp self-check: scaler grow broken: {st}")
    st = upd(st, False)
    if float(st[0]) != 1024.0 or int(st[2]) != 1:
        raise MXNetError(f"amp self-check: scaler backoff broken: {st}")
    st = upd(st, True)
    if float(st[0]) != 1024.0 or int(st[1]) != 1 or int(st[2]) != 1:
        raise MXNetError(f"amp self-check: scaler resume broken: {st}")
    bad = (np.ones(3, np.float32), np.array([1.0, np.inf], np.float32))
    if bool(all_finite(bad)) or not bool(all_finite(bad[0])):
        raise MXNetError("amp self-check: all_finite broken")


def self_check(verbose: bool = False) -> int:
    """Probe the three AMP contracts; returns 0 on success (raises on
    failure).  Run as a ci_static stage: ``python -m mxtpu.amp
    --self-check``."""
    _check_policy()
    if verbose:
        print("amp self-check: policy parse OK "
              f"({POLICY_PATH})")
    _check_autocast_roundtrip()
    if verbose:
        print("amp self-check: autocast round-trip OK "
              "(bf16 dot, zero hazards, no leak outside the scope)")
    _check_scaler()
    if verbose:
        print("amp self-check: loss-scaler unit probe OK "
              "(grow/backoff/skip accounting)")
    return 0
