"""Fused BatchNorm(+Add)+ReLU Pallas kernels — training mode.

The reference ships fused BN kernels at its cuDNN tier
(``src/operator/nn/cudnn/cudnn_batch_norm.cc``† and the fused
``BatchNormAddRelu``/NHWC BN in ``src/operator/nn/batch_norm.cu``†).
On TPU the XLA-composite BatchNorm is already at its *fusion-level*
minimum HBM traffic — fwd ``2R+1W``, bwd ``4R+1W`` of activation-sized
tensors — because the stats/sums reductions are barriers XLA cannot
fuse across.  These kernels beat that minimum by exploiting the one
structural fact XLA's fuser can't: BN statistics are **per channel**,
so a whole channel-block (all ``N*H*W`` elements of ``cb`` channels)
can be staged in VMEM once and both phases (stats then normalize, or
sums then dx) run on the staged copy:

    fwd:  1R + 1W   (stats + scale/shift + optional add + relu)
    bwd:  2R + 1W   (dbeta/dgamma sums + drelu mask + dx, one read
                     each of x and dy)

The ReLU (and the bottleneck's residual add) ride along for free —
the drelu mask is recomputed in-kernel from the staged x and the
per-channel scale/shift, so no mask tensor is ever materialized.

Feasibility is shape-gated: a channel-block of ``cb`` channels costs
``N * cb * pad128(S) * itemsize`` bytes of VMEM per buffer and Mosaic
double-buffers every grid operand, so large-spatial layers (ResNet's
112x112 stem) fall back to the analytic-VJP composite
(``ops_impl._bn_train_core``) which keeps the XLA-minimum traffic.

MEASURED OUTCOME (r5, tools/probe_bn_fusion.py + BASELINE.md "Fused-BN
verdict"): standalone, the kernel beats the composite (e.g. fwd 1.46
vs 1.65 ms/layer at s4_7 b256 bf16).  In a real conv network it LOSES
— XLA lays conv activations out channels-minor (``{1,0,3,2}``: lanes =
C, sublanes = N) while a pallas custom call pins its operands
row-major, so every call is bracketed by full-tensor transpose copies
that cost more than the fused pass saves; and re-expressing the kernel
in the native channels-minor layout is VMEM-infeasible for the stages
holding ~90% of the BN bytes (the reduction extent N*H*W times the
128-lane minimum block is 51-205 MB).  The Pallas path is therefore
**opt-in** (``MXTPU_FUSED_BN=1``); the default composite keeps the
XLA-minimum traffic with the add/relu epilogue fused by XLA.

Layout contract: channel axis 1 (``(N, C, *spatial)``) — the bench /
model-zoo NCHW convention.  Other axes use the composite fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import knobs

# Declared numerics contract for ``contracts/amp_policy.json`` (see
# flash_attention.PRECISION).
PRECISION = {
    "accum_dtype": "f32",
    "safe_input_dtypes": ["bf16", "f32"],
    "note": "the staged channel-block is cast to f32 before the "
            "per-channel stats/sums reductions; scale/shift and the "
            "add+relu epilogue compute in f32",
}

# Declared operand-layout contract, aggregated by
# ``mxtpu.kernels.layout_metadata()`` — what each variant pins about
# the physical layout of the tensors the custom call binds, so the
# layout cost (transpose brackets, r5's measured loss) is stated where
# the dispatch decision lives instead of rediscovered per audit.
LAYOUT = {
    "channels_major": {
        "view": "(N, C, S) blocks, C on sublanes",
        "binds": "row-major NCHW operands; conv nets whose "
                 "activations XLA stores channels-minor ({1,0,3,2}) "
                 "pay full-tensor transpose brackets per call",
    },
    "channels_minor": {
        "view": "(N*S, C) blocks, C on lanes",
        "binds": "the native channels-minor conv activation layout — "
                 "the (N,C,S)->(N*S,C) relayout resolves to the "
                 "copy XLA already performs (or a no-op when the "
                 "producer is channels-minor), removing the "
                 "per-call transpose brackets",
    },
    "dispatch": "MXTPU_BN_LAYOUT: auto prefers channels-minor when "
                "one (rows, C) stage fits MXTPU_BN_VMEM_CAP_MB, else "
                "channels-major, else composite; cm/major force",
}


# ----------------------------------------------------------------------
# composite oracle (plain jnp, jax-autodiff) — parity target for tests
# ----------------------------------------------------------------------

def bn_act_reference(x, gamma, beta, eps=1e-5, act="none",
                     residual=None):
    """Pure-jnp BN(+add)+act with batch stats; returns (y, mean, var)."""
    axes = tuple(i for i in range(x.ndim) if i != 1)
    shape = tuple(-1 if i == 1 else 1 for i in range(x.ndim))
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes)
    var = jnp.mean(jnp.square(x32), axis=axes) - jnp.square(mean)
    var = jnp.maximum(var, 0.0)
    rstd = lax.rsqrt(var + eps)
    scale = gamma.astype(jnp.float32) * rstd
    shift = beta.astype(jnp.float32) - mean * scale
    y = x32 * scale.reshape(shape) + shift.reshape(shape)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype), mean, var


# ----------------------------------------------------------------------
# kernels
# ----------------------------------------------------------------------

def _fwd_kernel(*refs, n, eps, act, add):
    # Vectorized over the whole (N, cb, S) block, with channels kept on
    # SUBLANES throughout: reductions go lanes-first (axis 2, keepdims)
    # then over the untiled leading axis, so per-channel values live as
    # (cb, 1) and broadcast back with a lane-splat — never forming the
    # 1-D lane vector whose lane->sublane relayout Mosaic rejects.
    # (A per-sample fori_loop formulation compiles too but is ~2x
    # slower: 256 tiny 2-D iterations are loop-bound, not VPU-bound —
    # tools/probe_bn_fusion.py history.)
    if add:
        x_ref, r_ref, g_ref, b_ref, y_ref, mean_ref, var_ref = refs
    else:
        x_ref, g_ref, b_ref, y_ref, mean_ref, var_ref = refs
    x = x_ref[:].astype(jnp.float32)                     # (N, cb, S)
    s1 = jnp.sum(jnp.sum(x, axis=2, keepdims=True), axis=0)
    s2 = jnp.sum(jnp.sum(x * x, axis=2, keepdims=True), axis=0)
    mean = s1 / n                                        # (cb, 1)
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    rstd = lax.rsqrt(var + eps)
    g = g_ref[:].astype(jnp.float32)                     # (cb, 1)
    scale = g * rstd
    shift = b_ref[:].astype(jnp.float32) - mean * scale
    y = x * scale[None, :, :] + shift[None, :, :]
    if add:
        y = y + r_ref[:].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    var_ref[:] = var


def _bwd_kernel(*refs, n, act, add):
    if add:
        (x_ref, r_ref, dy_ref, g_ref, b_ref, mean_ref, rstd_ref,
         dx_ref, dr_ref, dg_ref, db_ref) = refs
    else:
        (x_ref, dy_ref, g_ref, b_ref, mean_ref, rstd_ref,
         dx_ref, dg_ref, db_ref) = refs
    mean = mean_ref[:]                                   # (cb, 1)
    rstd = rstd_ref[:]
    g = g_ref[:].astype(jnp.float32)
    b = b_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)                     # (N, cb, S)
    dy = dy_ref[:].astype(jnp.float32)
    xhat = (x - mean[None, :, :]) * rstd[None, :, :]
    if act == "relu":
        # recompute the pre-activation sign from the staged x — no
        # mask tensor is ever written to HBM
        a = xhat * g[None, :, :] + b[None, :, :]
        if add:
            a = a + r_ref[:].astype(jnp.float32)
        dy = jnp.where(a > 0, dy, 0.0)
    if add:
        dr_ref[:] = dy.astype(dr_ref.dtype)
    dbeta = jnp.sum(jnp.sum(dy, axis=2, keepdims=True), axis=0)
    dgamma = jnp.sum(jnp.sum(dy * xhat, axis=2, keepdims=True), axis=0)
    grs = g * rstd
    dx = grs[None, :, :] * (dy - (dbeta / n)[None, :, :]
                            - xhat * (dgamma / n)[None, :, :])
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dg_ref[:] = dgamma
    db_ref[:] = dbeta


def _fwd_kernel_cm(*refs, n, eps, act, add):
    # Channels-MINOR twin: the block is (R, cbl) with channels on
    # LANES — the layout conv activations already have — and the
    # per-channel stats reduce over the row (sublane) axis, landing
    # as (1, cbl) lane vectors that broadcast back row-wise with no
    # relayout at all.
    if add:
        x_ref, r_ref, g_ref, b_ref, y_ref, mean_ref, var_ref = refs
    else:
        x_ref, g_ref, b_ref, y_ref, mean_ref, var_ref = refs
    x = x_ref[:].astype(jnp.float32)                     # (R, cbl)
    s1 = jnp.sum(x, axis=0, keepdims=True)               # (1, cbl)
    s2 = jnp.sum(x * x, axis=0, keepdims=True)
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    rstd = lax.rsqrt(var + eps)
    g = g_ref[:].astype(jnp.float32)                     # (1, cbl)
    scale = g * rstd
    shift = b_ref[:].astype(jnp.float32) - mean * scale
    y = x * scale + shift
    if add:
        y = y + r_ref[:].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    var_ref[:] = var


def _bwd_kernel_cm(*refs, n, act, add):
    if add:
        (x_ref, r_ref, dy_ref, g_ref, b_ref, mean_ref, rstd_ref,
         dx_ref, dr_ref, dg_ref, db_ref) = refs
    else:
        (x_ref, dy_ref, g_ref, b_ref, mean_ref, rstd_ref,
         dx_ref, dg_ref, db_ref) = refs
    mean = mean_ref[:]                                   # (1, cbl)
    rstd = rstd_ref[:]
    g = g_ref[:].astype(jnp.float32)
    b = b_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)                     # (R, cbl)
    dy = dy_ref[:].astype(jnp.float32)
    xhat = (x - mean) * rstd
    if act == "relu":
        a = xhat * g + b
        if add:
            a = a + r_ref[:].astype(jnp.float32)
        dy = jnp.where(a > 0, dy, 0.0)
    if add:
        dr_ref[:] = dy.astype(dr_ref.dtype)
    dbeta = jnp.sum(dy, axis=0, keepdims=True)
    dgamma = jnp.sum(dy * xhat, axis=0, keepdims=True)
    grs = g * rstd
    dx = grs * (dy - dbeta / n - xhat * (dgamma / n))
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dg_ref[:] = dgamma
    db_ref[:] = dbeta


# ----------------------------------------------------------------------
# block selection / feasibility
# ----------------------------------------------------------------------

def _vmem_cap():
    return knobs.get("MXTPU_BN_VMEM_CAP_MB") << 20


def _pick_cb(N, C, S, itemsize, mult):
    """Largest channel-block that divides C, respects the sublane tile,
    and keeps the kernel's scoped-VMEM footprint under the cap.

    ``mult`` is the measured scoped-VMEM multiplier in units of one
    (N, cb, pad128(S)) block at the native dtype: double-buffered I/O
    blocks plus the f32 temporaries Mosaic materializes.  Measured on
    the real chip (fwd kernel, bf16, s4_7 cb=256: 124.73M scoped for a
    16.8M block ~ 7.5x); 14 for the backward (x, dy, dx I/O + f32
    temps), 20 for the residual-add backward.  None -> composite
    fallback."""
    sub = 16 if itemsize == 2 else 8
    spad = -(-S // 128) * 128
    per_ch = N * spad * itemsize
    best = None
    cb = sub
    while cb <= C:
        if C % cb == 0 and mult * cb * per_ch <= _vmem_cap():
            best = cb
        cb += sub
    return best


def _pick_cbl(R, C, itemsize, mult):
    """Channels-minor lane-block: the largest channel count (lane
    extent) dividing C whose (R, cbl) stage — rows padded to the
    sublane tile, lanes to 128 — keeps ``mult`` staged copies under
    the VMEM cap.  The reduction extent R = N*S stages WHOLE, which is
    what makes the large-spatial stages infeasible in this layout
    (the r5 measurement) and why dispatch is per-layer."""
    sub = 16 if itemsize == 2 else 8
    rpad = -(-R // sub) * sub
    best = None
    cands = sorted({c for c in list(range(128, C + 1, 128)) + [C]
                    if C % c == 0})
    for cbl in cands:
        lpad = -(-cbl // 128) * 128
        if mult * rpad * lpad * itemsize <= _vmem_cap():
            best = cbl
    return best


# ----------------------------------------------------------------------
# pallas_call wrappers (operate on (N, C, S) views)
# ----------------------------------------------------------------------

def _blk3(N, cb, S):
    return pl.BlockSpec((N, cb, S), lambda i: (0, i, 0),
                        memory_space=pltpu.VMEM)


def _blkc(cb):
    return pl.BlockSpec((cb, 1), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _compiler_params(interpret):
    if interpret:
        return None
    # the default scoped-VMEM limit for TPU custom calls is 16 MiB;
    # the channel-block staging strategy deliberately uses most of
    # physical VMEM (measured OOM text: "Scoped allocation ... limit
    # 16.00M" — see tools/probe_bn_fusion.py)
    return pltpu.CompilerParams(vmem_limit_bytes=_vmem_cap())


def _fwd_call(x3, gamma, beta, resid3, eps, act, cb, interpret):
    N, C, S = x3.shape
    n = float(N * S)
    grid = (C // cb,)
    ins = [x3] + ([resid3] if resid3 is not None else []) + \
        [gamma.reshape(C, 1), beta.reshape(C, 1)]
    in_specs = [_blk3(N, cb, S)] + \
        ([_blk3(N, cb, S)] if resid3 is not None else []) + \
        [_blkc(cb), _blkc(cb)]
    y, mean, var = pl.pallas_call(
        functools.partial(_fwd_kernel, n=n, eps=eps, act=act,
                          add=resid3 is not None),
        name="batch_norm_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[_blk3(N, cb, S), _blkc(cb), _blkc(cb)],
        out_shape=[
            jax.ShapeDtypeStruct((N, C, S), x3.dtype),
            jax.ShapeDtypeStruct((C, 1), jnp.float32),
            jax.ShapeDtypeStruct((C, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*ins)
    return y, mean.reshape(C), var.reshape(C)


def _bwd_call(x3, resid3, dy3, gamma, beta, mean, rstd, act, cb,
              interpret):
    N, C, S = x3.shape
    n = float(N * S)
    grid = (C // cb,)
    add = resid3 is not None
    ins = [x3] + ([resid3] if add else []) + \
        [dy3, gamma.reshape(C, 1), beta.reshape(C, 1),
         mean.reshape(C, 1), rstd.reshape(C, 1)]
    in_specs = [_blk3(N, cb, S)] + ([_blk3(N, cb, S)] if add else []) + \
        [_blk3(N, cb, S), _blkc(cb), _blkc(cb), _blkc(cb), _blkc(cb)]
    out_specs = [_blk3(N, cb, S)] + ([_blk3(N, cb, S)] if add else []) + \
        [_blkc(cb), _blkc(cb)]
    out_shape = [jax.ShapeDtypeStruct((N, C, S), x3.dtype)] + \
        ([jax.ShapeDtypeStruct((N, C, S), dy3.dtype)] if add else []) + \
        [jax.ShapeDtypeStruct((C, 1), jnp.float32),
         jax.ShapeDtypeStruct((C, 1), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, act=act, add=add),
        name="batch_norm_bwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*ins)
    if add:
        dx, dr, dg, db = outs
    else:
        dx, dg, db = outs
        dr = None
    return dx, dr, dg.reshape(C), db.reshape(C)


# ----------------------------------------------------------------------
# pallas_call wrappers — channels-minor ((N*S, C) views)
# ----------------------------------------------------------------------

def _blk2(R, cbl):
    return pl.BlockSpec((R, cbl), lambda i: (0, i),
                        memory_space=pltpu.VMEM)


def _blkc_cm(cbl):
    return pl.BlockSpec((1, cbl), lambda i: (0, i),
                        memory_space=pltpu.VMEM)


def _fwd_call_cm(x2, gamma, beta, resid2, eps, act, cbl, interpret):
    R, C = x2.shape
    n = float(R)
    grid = (C // cbl,)
    add = resid2 is not None
    ins = [x2] + ([resid2] if add else []) + \
        [gamma.reshape(1, C), beta.reshape(1, C)]
    in_specs = [_blk2(R, cbl)] + ([_blk2(R, cbl)] if add else []) + \
        [_blkc_cm(cbl), _blkc_cm(cbl)]
    y, mean, var = pl.pallas_call(
        functools.partial(_fwd_kernel_cm, n=n, eps=eps, act=act,
                          add=add),
        name="batch_norm_fwd_cm",
        grid=grid,
        in_specs=in_specs,
        out_specs=[_blk2(R, cbl), _blkc_cm(cbl), _blkc_cm(cbl)],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), x2.dtype),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*ins)
    return y, mean.reshape(C), var.reshape(C)


def _bwd_call_cm(x2, resid2, dy2, gamma, beta, mean, rstd, act, cbl,
                 interpret):
    R, C = x2.shape
    n = float(R)
    grid = (C // cbl,)
    add = resid2 is not None
    ins = [x2] + ([resid2] if add else []) + \
        [dy2, gamma.reshape(1, C), beta.reshape(1, C),
         mean.reshape(1, C), rstd.reshape(1, C)]
    in_specs = [_blk2(R, cbl)] + ([_blk2(R, cbl)] if add else []) + \
        [_blk2(R, cbl), _blkc_cm(cbl), _blkc_cm(cbl), _blkc_cm(cbl),
         _blkc_cm(cbl)]
    out_specs = [_blk2(R, cbl)] + ([_blk2(R, cbl)] if add else []) + \
        [_blkc_cm(cbl), _blkc_cm(cbl)]
    out_shape = [jax.ShapeDtypeStruct((R, C), x2.dtype)] + \
        ([jax.ShapeDtypeStruct((R, C), dy2.dtype)] if add else []) + \
        [jax.ShapeDtypeStruct((1, C), jnp.float32),
         jax.ShapeDtypeStruct((1, C), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel_cm, n=n, act=act, add=add),
        name="batch_norm_bwd_cm",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*ins)
    if add:
        dx, dr, dg, db = outs
    else:
        dx, dg, db = outs
        dr = None
    return dx, dr, dg.reshape(C), db.reshape(C)


# ----------------------------------------------------------------------
# custom-VJP wrappers
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_bn(x3, gamma, beta, eps, act, cb):
    from . import interpret_mode
    return _fwd_call(x3, gamma, beta, None, eps, act, cb,
                     interpret_mode())


def _fused_bn_fwd(x3, gamma, beta, eps, act, cb):
    from . import interpret_mode
    y, mean, var = _fwd_call(x3, gamma, beta, None, eps, act, cb,
                             interpret_mode())
    return (y, mean, var), (x3, gamma, beta, mean, var)


def _fused_bn_bwd(eps, act, cb, res, dys):
    from . import interpret_mode
    x3, gamma, beta, mean, var = res
    rstd = lax.rsqrt(var + eps)
    dx, _, dg, db = _bwd_call(x3, None, dys[0], gamma, beta, mean,
                              rstd, act, cb, interpret_mode())
    return dx, dg.astype(gamma.dtype), db.astype(beta.dtype)


_fused_bn.defvjp(_fused_bn_fwd, _fused_bn_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_bn_add(x3, resid3, gamma, beta, eps, act, cb):
    from . import interpret_mode
    return _fwd_call(x3, gamma, beta, resid3, eps, act, cb,
                     interpret_mode())


def _fused_bn_add_fwd(x3, resid3, gamma, beta, eps, act, cb):
    from . import interpret_mode
    y, mean, var = _fwd_call(x3, gamma, beta, resid3, eps, act, cb,
                             interpret_mode())
    return (y, mean, var), (x3, resid3, gamma, beta, mean, var)


def _fused_bn_add_bwd(eps, act, cb, res, dys):
    from . import interpret_mode
    x3, resid3, gamma, beta, mean, var = res
    rstd = lax.rsqrt(var + eps)
    dx, dr, dg, db = _bwd_call(x3, resid3, dys[0], gamma, beta, mean,
                               rstd, act, cb, interpret_mode())
    return dx, dr, dg.astype(gamma.dtype), db.astype(beta.dtype)


_fused_bn_add.defvjp(_fused_bn_add_fwd, _fused_bn_add_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_bn_cm(x2, gamma, beta, eps, act, cbl):
    from . import interpret_mode
    return _fwd_call_cm(x2, gamma, beta, None, eps, act, cbl,
                        interpret_mode())


def _fused_bn_cm_fwd(x2, gamma, beta, eps, act, cbl):
    from . import interpret_mode
    y, mean, var = _fwd_call_cm(x2, gamma, beta, None, eps, act, cbl,
                                interpret_mode())
    return (y, mean, var), (x2, gamma, beta, mean, var)


def _fused_bn_cm_bwd(eps, act, cbl, res, dys):
    from . import interpret_mode
    x2, gamma, beta, mean, var = res
    rstd = lax.rsqrt(var + eps)
    dx, _, dg, db = _bwd_call_cm(x2, None, dys[0], gamma, beta, mean,
                                 rstd, act, cbl, interpret_mode())
    return dx, dg.astype(gamma.dtype), db.astype(beta.dtype)


_fused_bn_cm.defvjp(_fused_bn_cm_fwd, _fused_bn_cm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_bn_add_cm(x2, resid2, gamma, beta, eps, act, cbl):
    from . import interpret_mode
    return _fwd_call_cm(x2, gamma, beta, resid2, eps, act, cbl,
                        interpret_mode())


def _fused_bn_add_cm_fwd(x2, resid2, gamma, beta, eps, act, cbl):
    from . import interpret_mode
    y, mean, var = _fwd_call_cm(x2, gamma, beta, resid2, eps, act, cbl,
                                interpret_mode())
    return (y, mean, var), (x2, resid2, gamma, beta, mean, var)


def _fused_bn_add_cm_bwd(eps, act, cbl, res, dys):
    from . import interpret_mode
    x2, resid2, gamma, beta, mean, var = res
    rstd = lax.rsqrt(var + eps)
    dx, dr, dg, db = _bwd_call_cm(x2, resid2, dys[0], gamma, beta,
                                  mean, rstd, act, cbl,
                                  interpret_mode())
    return dx, dr, dg.astype(gamma.dtype), db.astype(beta.dtype)


_fused_bn_add_cm.defvjp(_fused_bn_add_cm_fwd, _fused_bn_add_cm_bwd)


# ----------------------------------------------------------------------
# public entry
# ----------------------------------------------------------------------

def fused_bn_act(x, gamma, beta, eps=1e-5, act="none", residual=None):
    """Training-mode BN over channel axis 1, with optional fused
    residual add and ReLU.  Returns ``(y, batch_mean, batch_var)``
    (mean/var are the aux-state channel — not differentiable outputs).

    Dispatches to the one-pass Pallas kernels when the channel-block
    fits VMEM (see module docstring); composite otherwise.  The
    composite fallback still uses the analytic-VJP BN core, so the
    gradient math is identical on every path.
    """
    from . import pallas_enabled
    eps = float(eps)
    # OPT-IN (MXTPU_FUSED_BN=1): the kernel wins per-op (probe table 1
    # in BASELINE.md) but XLA stores conv activations channels-minor
    # ({1,0,3,2}) while pallas custom calls force row-major operands,
    # so in a real conv network every call is bracketed by transpose
    # copies that cost more than the fusion saves (probe table 2).
    feasible = (
        pallas_enabled() and x.ndim >= 3
        and (residual is None or residual.shape == x.shape)
        and knobs.get("MXTPU_FUSED_BN")
    )
    if feasible:
        N, C = x.shape[0], x.shape[1]
        S = 1
        for d in x.shape[2:]:
            S *= d
        # bwd is the high-water mark for scoped VMEM (see _pick_cb)
        mult = 20 if residual is not None else 14
        layout = knobs.get("MXTPU_BN_LAYOUT").strip().lower()
        if layout in ("auto", "cm"):
            # channels-minor first (the AMP layout fix): C rides the
            # lanes like the conv activations feeding it, so the
            # custom call binds without the transpose brackets that
            # made the channels-major kernel a net loss in conv nets
            # (module docstring, r5).  Infeasible (large-spatial
            # stage) -> channels-major under "auto", composite when
            # forced "cm".
            cbl = _pick_cbl(N * S, C, x.dtype.itemsize, mult)
            if cbl is not None:
                x2 = x.reshape(N, C, S).swapaxes(1, 2).reshape(N * S, C)
                r2 = residual.reshape(N, C, S).swapaxes(1, 2) \
                    .reshape(N * S, C) if residual is not None else None
                if r2 is None:
                    y, mean, var = _fused_bn_cm(x2, gamma, beta, eps,
                                                act, cbl)
                else:
                    y, mean, var = _fused_bn_add_cm(x2, r2, gamma,
                                                    beta, eps, act,
                                                    cbl)
                y = y.reshape(N, S, C).swapaxes(1, 2).reshape(x.shape)
                return y, mean, var
        if layout in ("auto", "major"):
            cb = _pick_cb(N, C, S, x.dtype.itemsize, mult)
            if cb is not None:
                x3 = x.reshape(N, C, S)
                r3 = residual.reshape(N, C, S) \
                    if residual is not None else None
                if r3 is None:
                    y, mean, var = _fused_bn(x3, gamma, beta, eps, act,
                                             cb)
                else:
                    y, mean, var = _fused_bn_add(x3, r3, gamma, beta,
                                                 eps, act, cb)
                return y.reshape(x.shape), mean, var
    # composite fallback: analytic-VJP core + jnp epilogue
    from ..ndarray.ops_impl import _bn_train_core
    y, mean, var = _bn_train_core(x, gamma, beta, 1, eps)
    if residual is not None:
        y = y + residual
    if act == "relu":
        y = jnp.maximum(y, jnp.zeros((), y.dtype))
    return y, mean, var
