"""Fused LayerNorm (forward + backward) Pallas kernels.

Replaces the reference's cuDNN/hand-CUDA LayerNorm
(``src/operator/nn/layer_norm.cc``†) on TPU.  Fusion wins: one HBM
read of x per pass instead of XLA's potentially split mean/var/normalize
pipeline, with mean/rstd residuals saved for a one-read backward.

Layout: rows = all leading dims flattened, normalization over the last
axis.  Row blocks of 128 keep the VPU lanes full; the feature axis is
kept whole in VMEM (fine up to ~tens of thousands of features).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import knobs

# Declared numerics contract for ``contracts/amp_policy.json`` (see
# flash_attention.PRECISION).
PRECISION = {
    "accum_dtype": "f32",
    "safe_input_dtypes": ["bf16", "f32"],
    "note": "x is staged to f32 before mean/var; rstd and the "
            "normalize epilogue stay f32; mean/rstd residuals saved "
            "in f32 for the backward",
}

# Operand-layout contract (see batch_norm.LAYOUT): already minor-most
# on the reduced axis, so no relayout brackets arise — the row-major
# (rows, features) view IS the layout the producing matmuls emit.
LAYOUT = {
    "native": {
        "view": "(rows, features) row blocks, features on lanes",
        "binds": "row-major — matches the (…, D) activations the "
                 "surrounding matmuls produce; no transpose brackets",
    },
    "dispatch": "always; feature axis stages whole in VMEM",
}


def layer_norm_reference(x, gamma, beta, eps=1e-5):
    """Pure-lax composite — the fallback path and parity oracle."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    inv = lax.rsqrt(var + eps)
    return (x - mean) * inv * gamma + beta


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *,
                   eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    y = xc * rstd * g_ref[:].astype(jnp.float32) + \
        b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _ln_bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref, dx_ref,
                   dg_ref, db_ref):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = (x - mean) * rstd
    dyg = dy * g
    c1 = jnp.mean(dyg, axis=-1, keepdims=True)
    c2 = jnp.mean(dyg * xhat, axis=-1, keepdims=True)
    dx = rstd * (dyg - c1 - xhat * c2)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # per-row-block partial reductions; each block writes an 8-row tile
    # (TPU min sublane tile) with the partial in row 0 — summed outside
    dg_ref[:] = jnp.pad(jnp.sum(dy * xhat, axis=0, keepdims=True),
                        ((0, 7), (0, 0)))
    db_ref[:] = jnp.pad(jnp.sum(dy, axis=0, keepdims=True),
                        ((0, 7), (0, 0)))


def _row_block(n_rows: int, n_cols: int, budget: int = 4 << 20):
    """Largest row block that divides n_rows and keeps the x-block
    within a VMEM-friendly budget; None → use the lax fallback."""
    for blk in (256, 128, 64, 32, 16, 8):
        if n_rows % blk == 0 and blk * n_cols * 4 <= budget:
            return blk
    return None


def _pallas_ln_fwd(x2, gamma, beta, eps, interpret):
    R, C = x2.shape
    BR = _row_block(R, C)
    grid = (R // BR,)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        name="layer_norm_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((BR, C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, C), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, C), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((BR, C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BR, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BR, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), x2.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, gamma.reshape(1, C), beta.reshape(1, C))
    return y, mean, rstd


def _pallas_ln_bwd(x2, gamma, mean, rstd, dy2, interpret):
    R, C = x2.shape
    BR = _row_block(R, C)
    grid = (R // BR,)
    dx, dg_part, db_part = pl.pallas_call(
        _ln_bwd_kernel,
        name="layer_norm_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((BR, C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, C), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BR, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BR, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((BR, C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((BR, C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), x2.dtype),
            jax.ShapeDtypeStruct((R // BR * 8, C), jnp.float32),
            jax.ShapeDtypeStruct((R // BR * 8, C), jnp.float32),
        ],
        interpret=interpret,
    )(x2, gamma.reshape(1, C), mean, rstd, dy2)
    return dx, dg_part.sum(0), db_part.sum(0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm_pallas(x2, gamma, beta, eps):
    from . import interpret_mode
    y, _, _ = _pallas_ln_fwd(x2, gamma, beta, eps, interpret_mode())
    return y


def _ln_fwd_rule(x2, gamma, beta, eps):
    from . import interpret_mode
    y, mean, rstd = _pallas_ln_fwd(x2, gamma, beta, eps,
                                   interpret_mode())
    return y, (x2, gamma, mean, rstd)


def _ln_bwd_rule(eps, res, dy):
    from . import interpret_mode
    x2, gamma, mean, rstd = res
    dx, dg, db = _pallas_ln_bwd(x2, gamma, mean, rstd, dy,
                                interpret_mode())
    return dx, dg.astype(gamma.dtype), db.astype(gamma.dtype)


_layer_norm_pallas.defvjp(_ln_fwd_rule, _ln_bwd_rule)


def layer_norm(x, gamma, beta, eps=1e-5):
    """Fused LayerNorm over the last axis.  Pallas on TPU (or interpret
    mode), lax composite elsewhere."""
    from . import pallas_enabled
    C = x.shape[-1]
    n_rows = 1
    for d in x.shape[:-1]:
        n_rows *= d
    if not pallas_enabled() or _row_block(n_rows, C) is None:
        return layer_norm_reference(x, gamma, beta, eps)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, C)
    y = _layer_norm_pallas(x2, gamma.reshape(-1), beta.reshape(-1),
                           float(eps))
    return y.reshape(*lead, C)


# ======================================================================
# fused residual epilogue: y = LN(res + dropout(h + bias))
#
# The transformer post-LN epilogue (proj-bias add, dropout, residual
# add, LayerNorm) is 4 elementwise/reduction ops between two GEMMs.
# Unfused, XLA streams:  fwd  read h,res / write u  +  read u / write y
# (5 (R,C) HBM transfers, plus u resident until the backward);  fused:
# read h,res / write y (3 transfers, no u activation at all).  The bwd
# recomputes the dropout mask and u from h/res in VMEM (4 reads, 2
# writes vs 6 unfused).  Traffic analysis + in-context measurements in
# BASELINE.md "BERT cost split" (fused-BN evidentiary standard).
#
# The mask comes from a hand-rolled threefry2x32 over the global linear
# element index — pure uint32 jnp arithmetic, so the SAME function runs
# inside the Pallas kernel (interpret or compiled: `pltpu.prng_*` has
# no CPU interpret lowering in this jax) and inside the lax composite
# below, making fused-vs-composite parity exact, not statistical.
# ======================================================================

_THREEFRY_PARITY = np.uint32(0x1BD11BDA)
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _threefry2x32(k0, k1, x0, x1):
    """Standard 20-round threefry2x32 in pure uint32 jnp ops."""
    ks = (k0, k1, _THREEFRY_PARITY ^ k0 ^ k1)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for grp in range(5):
        for rot in _ROTATIONS[grp % 2]:
            x0 = x0 + x1
            x1 = (x1 << rot) | (x1 >> (32 - rot))
            x1 = x1 ^ x0
        x0 = x0 + ks[(grp + 1) % 3]
        x1 = x1 + ks[(grp + 2) % 3] + np.uint32(grp + 1)
    return x0, x1


def _mask_bits(k0, k1, row0, n_rows, n_cols):
    """uint32 bits for rows [row0, row0+n_rows) of an (R, C) dropout
    mask; counter = global linear element index, so any row block of
    the same logical tensor draws identical bits."""
    r = lax.broadcasted_iota(jnp.uint32, (n_rows, n_cols), 0)
    c = lax.broadcasted_iota(jnp.uint32, (n_rows, n_cols), 1)
    ctr = (row0 + r) * jnp.uint32(n_cols) + c
    bits, _ = _threefry2x32(k0, k1, ctr, jnp.zeros_like(ctr))
    return bits


def _keep_thresh(keep: float) -> int:
    # P(bits < thresh) == keep for bits ~ U[0, 2^32)
    return min((1 << 32) - 1, int(round(keep * (1 << 32))))


def fused_residual_ln_reference(h, bias, res, gamma, beta, key_data,
                                p=0.1, eps=1e-5, training=True):
    """Lax composite of the epilogue using the SAME threefry mask as
    the Pallas kernel — the non-TPU fallback and exact parity oracle."""
    C = h.shape[-1]
    hb = h.astype(jnp.float32) + bias.astype(jnp.float32).reshape(-1)
    if training and p > 0.0:
        keep = 1.0 - p
        n = 1
        for d in h.shape:
            n *= d
        k0 = key_data.reshape(-1)[0].astype(jnp.uint32)
        k1 = key_data.reshape(-1)[1].astype(jnp.uint32)
        if n < (1 << 32):
            bits = _mask_bits(k0, k1, jnp.uint32(0),
                              n // C, C).reshape(h.shape)
            mask = bits < jnp.uint32(_keep_thresh(keep))
        else:  # counter would wrap; no Pallas path here either
            key = jax.random.wrap_key_data(jnp.stack([k0, k1]))
            mask = jax.random.bernoulli(key, keep, h.shape)
        hb = jnp.where(mask, hb * (1.0 / keep), 0.0)
    u = res.astype(jnp.float32) + hb
    y = layer_norm_reference(u, gamma.astype(jnp.float32),
                             beta.astype(jnp.float32), eps)
    return y.astype(h.dtype)


def _frln_fwd_kernel(seed_ref, h_ref, bias_ref, res_ref, g_ref, b_ref,
                     y_ref, mean_ref, rstd_ref, *, eps, keep, thresh,
                     block_rows):
    hb = h_ref[:].astype(jnp.float32) + bias_ref[:].astype(jnp.float32)
    if keep < 1.0:
        row0 = (pl.program_id(0) * block_rows).astype(jnp.uint32)
        bits = _mask_bits(seed_ref[0], seed_ref[1], row0, *hb.shape)
        hb = jnp.where(bits < jnp.uint32(thresh),
                       hb * (1.0 / keep), 0.0)
    u = res_ref[:].astype(jnp.float32) + hb
    mean = jnp.mean(u, axis=-1, keepdims=True)
    uc = u - mean
    var = jnp.mean(uc * uc, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    y = uc * rstd * g_ref[:].astype(jnp.float32) + \
        b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _frln_bwd_kernel(seed_ref, h_ref, bias_ref, res_ref, g_ref,
                     mean_ref, rstd_ref, dy_ref,
                     dh_ref, dres_ref, dg_ref, db_ref, dbias_ref, *,
                     keep, thresh, block_rows):
    # recompute the mask and u = res + dropout(h + bias) in VMEM — no
    # saved activation between the GEMM and the LN
    hb = h_ref[:].astype(jnp.float32) + bias_ref[:].astype(jnp.float32)
    if keep < 1.0:
        row0 = (pl.program_id(0) * block_rows).astype(jnp.uint32)
        mask = _mask_bits(seed_ref[0], seed_ref[1], row0,
                          *hb.shape) < jnp.uint32(thresh)
        hb = jnp.where(mask, hb * (1.0 / keep), 0.0)
    u = res_ref[:].astype(jnp.float32) + hb
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = (u - mean) * rstd
    dy = dy_ref[:].astype(jnp.float32)
    dyg = dy * g_ref[:].astype(jnp.float32)
    c1 = jnp.mean(dyg, axis=-1, keepdims=True)
    c2 = jnp.mean(dyg * xhat, axis=-1, keepdims=True)
    du = rstd * (dyg - c1 - xhat * c2)
    if keep < 1.0:
        dh = jnp.where(mask, du * (1.0 / keep), 0.0)
    else:
        dh = du
    dh_ref[:] = dh.astype(dh_ref.dtype)
    dres_ref[:] = du.astype(dres_ref.dtype)
    # 8-row padded partial-reduction tiles, summed outside (same
    # convention as _ln_bwd_kernel)
    dg_ref[:] = jnp.pad(jnp.sum(dy * xhat, axis=0, keepdims=True),
                        ((0, 7), (0, 0)))
    db_ref[:] = jnp.pad(jnp.sum(dy, axis=0, keepdims=True),
                        ((0, 7), (0, 0)))
    dbias_ref[:] = jnp.pad(jnp.sum(dh, axis=0, keepdims=True),
                           ((0, 7), (0, 0)))


def _pallas_frln_fwd(h2, bias, res2, gamma, beta, seed, keep, eps,
                     interpret):
    R, C = h2.shape
    BR = _row_block(R, C, budget=1 << 20)
    grid = (R // BR,)
    row = lambda i: (i, 0)
    vrow = lambda bs: pl.BlockSpec(bs, row, memory_space=pltpu.VMEM)
    one = lambda: pl.BlockSpec((1, C), lambda i: (0, 0),
                               memory_space=pltpu.VMEM)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_frln_fwd_kernel, eps=eps, keep=keep,
                          thresh=_keep_thresh(keep), block_rows=BR),
        name="fused_residual_layer_norm_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            vrow((BR, C)), one(), vrow((BR, C)), one(), one(),
        ],
        out_specs=[vrow((BR, C)), vrow((BR, 1)), vrow((BR, 1))],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), h2.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(seed, h2, bias.reshape(1, C), res2, gamma.reshape(1, C),
      beta.reshape(1, C))
    return y, mean, rstd


def _pallas_frln_bwd(h2, bias, res2, gamma, seed, mean, rstd, dy2,
                     keep, interpret):
    R, C = h2.shape
    BR = _row_block(R, C, budget=1 << 20)
    grid = (R // BR,)
    row = lambda i: (i, 0)
    vrow = lambda bs: pl.BlockSpec(bs, row, memory_space=pltpu.VMEM)
    one = lambda: pl.BlockSpec((1, C), lambda i: (0, 0),
                               memory_space=pltpu.VMEM)
    part = jax.ShapeDtypeStruct((R // BR * 8, C), jnp.float32)
    dh, dres, dg_p, db_p, dbias_p = pl.pallas_call(
        functools.partial(_frln_bwd_kernel, keep=keep,
                          thresh=_keep_thresh(keep), block_rows=BR),
        name="fused_residual_layer_norm_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            vrow((BR, C)), one(), vrow((BR, C)), one(),
            vrow((BR, 1)), vrow((BR, 1)), vrow((BR, C)),
        ],
        out_specs=[vrow((BR, C)), vrow((BR, C)),
                   vrow((8, C)), vrow((8, C)), vrow((8, C))],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), h2.dtype),
            jax.ShapeDtypeStruct((R, C), h2.dtype),
            part, part, part,
        ],
        interpret=interpret,
    )(seed, h2, bias.reshape(1, C), res2, gamma.reshape(1, C),
      mean, rstd, dy2)
    return dh, dres, dg_p.sum(0), db_p.sum(0), dbias_p.sum(0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _fused_residual_ln_pallas(h2, bias, res2, gamma, beta, seed, keep,
                              eps):
    from . import interpret_mode
    y, _, _ = _pallas_frln_fwd(h2, bias, res2, gamma, beta, seed, keep,
                               eps, interpret_mode())
    return y


def _frln_fwd_rule(h2, bias, res2, gamma, beta, seed, keep, eps):
    from . import interpret_mode
    y, mean, rstd = _pallas_frln_fwd(h2, bias, res2, gamma, beta, seed,
                                     keep, eps, interpret_mode())
    return y, (h2, bias, res2, gamma, seed, mean, rstd)


def _frln_bwd_rule(keep, eps, saved, dy):
    from . import interpret_mode
    h2, bias, res2, gamma, seed, mean, rstd = saved
    dh, dres, dg, db, dbias = _pallas_frln_bwd(
        h2, bias, res2, gamma, seed, mean, rstd, dy, keep,
        interpret_mode())
    return (dh, dbias.astype(bias.dtype), dres,
            dg.astype(gamma.dtype), db.astype(gamma.dtype),
            np.zeros(seed.shape, dtype=jax.dtypes.float0))


_fused_residual_ln_pallas.defvjp(_frln_fwd_rule, _frln_bwd_rule)


def epilogue_enabled() -> bool:
    """Kill switch for the Pallas epilogue (MXTPU_FUSED_LN_EPILOGUE=0
    falls back to the lax composite with identical mask numerics)."""
    return knobs.get("MXTPU_FUSED_LN_EPILOGUE")


def fused_residual_layer_norm(h, bias, res, gamma, beta, key_data,
                              p=0.1, eps=1e-5, training=True):
    """y = LayerNorm(res + dropout(h + bias)) over the last axis.

    ``key_data`` is raw uint32[2] threefry key words (from
    ``jax.random.key_data``).  Pallas on TPU/interpret, lax composite
    elsewhere — both draw the identical mask."""
    from . import pallas_enabled
    C = h.shape[-1]
    n_rows = 1
    for d in h.shape[:-1]:
        n_rows *= d
    keep = 1.0 if (not training or p <= 0.0) else float(1.0 - p)
    if (not pallas_enabled() or not epilogue_enabled()
            or _row_block(n_rows, C, budget=1 << 20) is None
            or n_rows * C >= (1 << 32)):
        return fused_residual_ln_reference(
            h, bias, res, gamma, beta, key_data, p=p, eps=eps,
            training=training)
    lead = h.shape[:-1]
    seed = key_data.reshape((2,)).astype(jnp.uint32)
    y = _fused_residual_ln_pallas(
        h.reshape(-1, C), bias.reshape(-1), res.reshape(-1, C),
        gamma.reshape(-1), beta.reshape(-1), seed, keep, float(eps))
    return y.reshape(*lead, C)
