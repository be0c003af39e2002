"""Flash attention (blockwise, online softmax) Pallas kernel.

The reference has no fused attention (2018-era; attention lived in
example code) — this is a *new-capability* kernel mandated by the north
star (SURVEY.md §5.7): O(T) memory attention for long-context training,
the building block for the BERT/Transformer configs.

Design: grid (batch·heads, q_blocks, kv_blocks) with the kv axis
innermost; VMEM scratch carries the running max ``m``, normalizer ``l``
and accumulator across kv blocks (the TPU grid is sequential, so
scratch persists).  Softmax runs in f32 regardless of input dtype; the
q·kᵀ and p·v matmuls hit the MXU with
``preferred_element_type=float32``.  Causal blocks strictly above the
diagonal are skipped via ``pl.when``.

Backward: blockwise Pallas kernels (flash-attention-2 style).  The
forward additionally emits the per-row logsumexp; the backward
recomputes each (q_block, kv_block) score tile from q/k and the saved
lse — p = exp(s − lse) is exactly the forward's normalized softmax —
and accumulates dq (kv-innermost grid) and dk/dv (q-innermost grid) in
VMEM scratch.  Memory stays O(T·D) per head; the O(T²) attention
matrix is never materialised in either direction.

Backward dispatch (``MXTPU_FLASH_BWD``): ``auto`` (default) picks AD
through the fused lax reference below T=1024 — measured faster on
v5e while everything is floor-bound — and the blockwise kernels from
T=1024 up (1.4×/2.2×/3.8× vs the fallback at T=1024/2048/4096 with
512-blocks, r4 honest harness; and the only option when O(T²) would
blow HBM); ``pallas``/``ref`` force a path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Declared numerics contract, aggregated by
# ``mxtpu.kernels.precision_metadata`` into
# ``contracts/amp_policy.json`` — custom calls are opaque to the HLO
# dtype-flow scan, so the kernel states its accumulation discipline
# here and the parity tests hold it to that.
PRECISION = {
    "accum_dtype": "f32",
    "safe_input_dtypes": ["bf16", "f32"],
    "note": "online softmax (m/l/acc scratch) in f32; q.kT and p.v "
            "matmuls use preferred_element_type=float32; single "
            "downcast to the input dtype on output",
}

# Operand-layout contract (see batch_norm.LAYOUT): head_dim minor is
# the layout the QKV projection matmuls emit, so the custom call
# binds transpose-free on every operand.
LAYOUT = {
    "native": {
        "view": "(seq_block, head_dim) tiles per (batch*heads) "
                "program, head_dim on lanes",
        "binds": "row-major (B, H, T, D) — the projection matmul "
                 "output layout; k is transposed in-kernel on the "
                 "MXU, never relaid out in HBM",
    },
    "dispatch": "MXTPU_FLASH_BWD picks the backward path; forward "
                "always blockwise on TPU",
}


def attention_reference(q, k, v, causal=False, sm_scale=None):
    """Pure-lax attention — fallback path and parity oracle.
    q: (B, H, Tq, D); k, v: (B, H, Tk, D).

    f32 inputs run the MXU at HIGHEST precision (the same discipline as
    the Pallas kernel's _precision_for): on TPU the jax default feeds
    bf16 multiplicands, which would make the oracle ~3 decimal digits
    loose and the production f32 fallback silently half-precision."""
    D = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / (D ** 0.5)
    prec = _precision_for(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32,
                   precision=prec) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        row = jnp.arange(Tq)[:, None] + (Tk - Tq)
        col = jnp.arange(Tk)[None, :]
        s = jnp.where(col <= row, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    if causal and s.shape[-2] > s.shape[-1]:
        # rows with NO visible key (Tq > Tk) output 0, not the uniform
        # attention a softmax over all-sentinel scores degrades to —
        # matches the Pallas kernel's fully-masked-row convention
        Tq, Tk = s.shape[-2], s.shape[-1]
        visible = (jnp.arange(Tq) + (Tk - Tq)) >= 0
        p = p * visible[:, None].astype(p.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      precision=_precision_for(v.dtype))


def _block(n: int, prefer: int) -> int:
    for blk in (prefer, 256, 128, 64, 32, 16, 8):
        if blk <= prefer and n % blk == 0:
            return blk
    return n


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
               acc_scr, *, sm_scale, causal, bq, bk, nk, delta,
               valid_kv, precision):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip blocks strictly above the diagonal (every column in
    # the block is in the future of every row); delta = Tk - Tq aligns
    # the diagonal when kv is longer than q (cached decoding)
    run = True
    if causal:
        first_row = i * bq + delta
        first_col = j * bk
        run = first_col <= first_row + bq - 1

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * sm_scale
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + \
                i * bq + delta
            col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + \
                j * bk
            s = jnp.where(col <= row, s, _NEG_INF)
        if valid_kv is not None:
            # static pad-mask bound: key columns >= valid_kv are
            # zero-padding, not data — sentinel them out before the
            # online softmax so they carry exactly zero weight
            col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + \
                j * bk
            s = jnp.where(col < valid_kv, s, _NEG_INF)
        m_prev = m_scr[:]
        l_prev = l_scr[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        # fully-masked rows (causal with Tq > Tk): every score is the
        # _NEG_INF sentinel, so m never rises above its init — l==0
        # canNOT detect this (p=exp(0)=1 per masked column makes l=Tk)
        # and lse=m+log(l) would absorb log(l) into -1e30, inflating
        # the backward's p=exp(s-lse) to 1 instead of 0.  Such rows
        # output 0 with lse=+BIG: fwd and bwd are then consistent
        # (zero output, zero grads) — see attention_reference, which
        # applies the same convention.
        masked = m_scr[:] == _NEG_INF
        l = l_scr[:]
        safe = jnp.where(l == 0.0, 1.0, l)
        o = acc_scr[:] / safe
        o_ref[0] = jnp.where(masked, 0.0, o).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(masked, -_NEG_INF,
                               m_scr[:] + jnp.log(safe))


def _precision_for(dtype):
    """f32 inputs get true-f32 MXU passes (Pallas' default is bf16
    multiplicands — 0.5% relative error at T=4k); bf16 inputs keep the
    fast single-pass path."""
    return jax.lax.Precision.HIGHEST \
        if jnp.dtype(dtype) == jnp.float32 else None


def _flash_forward(q3, k3, v3, causal, sm_scale, interpret,
                   valid_kv=None, delta=None):
    BH, Tq, D = q3.shape
    Tk = k3.shape[1]
    # 512-blocks: r4 measurement — 128-blocks made the grid 16x finer
    # and each MXU dot tiny; 512 took T=2048 fwd+bwd from 16.1 to
    # 5.1 ms (fallback: 10.9).  VMEM: s-tile 512^2 f32 = 1 MB.
    bq = _block(Tq, 512)
    bk = _block(Tk, 512)
    nq, nk = Tq // bq, Tk // bk
    kernel = functools.partial(_fa_kernel, sm_scale=sm_scale,
                               causal=causal, bq=bq, bk=bk, nk=nk,
                               delta=Tk - Tq if delta is None else delta,
                               valid_kv=valid_kv,
                               precision=_precision_for(q3.dtype))
    return pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            # (BH, Tq, 1) with (1, bq, 1) blocks: TPU lowering needs
            # the trailing two block dims ∈ {multiple-of-(8,128),
            # equal-to-array}; a 2D (1, bq) row block violates that
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)


# ----------------------------------------------------------------------
# blockwise backward (flash-attention-2): dq with kv innermost,
# dk/dv with q innermost; p recomputed from q,k and the saved lse
# ----------------------------------------------------------------------
def _recompute_p(q_ref, k_ref, lse_ref, sm_scale, causal, bq, bk,
                 i, j, delta, valid_kv, precision):
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision) * sm_scale
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + \
            i * bq + delta
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
        s = jnp.where(col <= row, s, _NEG_INF)
    if valid_kv is not None:
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
        s = jnp.where(col < valid_kv, s, _NEG_INF)
    return jnp.exp(s - lse_ref[0])  # lse block is (bq, 1) — broadcasts


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dt_ref, dq_ref,
                  dq_scr, *, sm_scale, causal, bq, bk, nk, delta,
                  valid_kv, precision):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = j * bk <= i * bq + delta + bq - 1

    @pl.when(run)
    def _step():
        p = _recompute_p(q_ref, k_ref, lse_ref, sm_scale, causal,
                         bq, bk, i, j, delta, valid_kv, precision)
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        ds = p * (dp - dt_ref[0]) * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dt_ref,
                   dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                   bq, bk, nq, delta, valid_kv, precision):
    j = pl.program_id(1)  # kv block (outer)
    i = pl.program_id(2)  # q block (inner)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = j * bk <= i * bq + delta + bq - 1

    @pl.when(run)
    def _step():
        p = _recompute_p(q_ref, k_ref, lse_ref, sm_scale, causal,
                         bq, bk, i, j, delta, valid_kv, precision)
        do = do_ref[0].astype(jnp.float32)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        ds = p * (dp - dt_ref[0]) * sm_scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q3, k3, v3, do3, lse, delta_rows, causal, sm_scale,
                    interpret, valid_kv=None, delta=None):
    BH, Tq, D = q3.shape
    Tk = k3.shape[1]
    bq = _block(Tq, 512)
    bk = _block(Tk, 512)
    nq, nk = Tq // bq, Tk // bk
    d = Tk - Tq if delta is None else delta

    q_spec_i = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                            memory_space=pltpu.VMEM)
    kv_spec_j = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0),
                             memory_space=pltpu.VMEM)
    row_spec_i = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0),
                              memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_fa_dq_kernel, sm_scale=sm_scale,
                          causal=causal, bq=bq, bk=bk, nk=nk, delta=d,
                          valid_kv=valid_kv,
                          precision=_precision_for(q3.dtype)),
        name="flash_attention_dq",
        grid=(BH, nq, nk),
        in_specs=[q_spec_i, kv_spec_j, kv_spec_j, q_spec_i, row_spec_i,
                  row_spec_i],
        out_specs=q_spec_i,
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta_rows)

    # q innermost now: index maps take (b, j, i)
    q_spec_t = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0),
                            memory_space=pltpu.VMEM)
    kv_spec_t = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0),
                             memory_space=pltpu.VMEM)
    row_spec_t = pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0),
                              memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_fa_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, bq=bq, bk=bk, nq=nq, delta=d,
                          valid_kv=valid_kv,
                          precision=_precision_for(q3.dtype)),
        name="flash_attention_dkv",
        grid=(BH, nk, nq),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[jax.ShapeDtypeStruct((BH, Tk, D), k3.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, D), v3.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta_rows)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_pallas(q, k, v, causal, sm_scale, valid_kv=None,
                            delta=None):
    from . import interpret_mode
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    o, _ = _flash_forward(q.reshape(B * H, Tq, D),
                          k.reshape(B * H, Tk, D),
                          v.reshape(B * H, Tk, D), causal, sm_scale,
                          interpret_mode(), valid_kv, delta)
    return o.reshape(B, H, Tq, D)


def _fa_fwd(q, k, v, causal, sm_scale, valid_kv=None, delta=None):
    from . import interpret_mode
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    o, lse = _flash_forward(q.reshape(B * H, Tq, D),
                            k.reshape(B * H, Tk, D),
                            v.reshape(B * H, Tk, D), causal, sm_scale,
                            interpret_mode(), valid_kv, delta)
    return o.reshape(B, H, Tq, D), (q, k, v, o.reshape(B, H, Tq, D),
                                    lse)


def _fa_bwd(causal, sm_scale, valid_kv, delta, res, do):
    q, k, v, o, lse = res
    from .. import knobs
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    mode = knobs.get("MXTPU_FLASH_BWD")
    if mode not in ("auto", "pallas", "ref"):
        raise ValueError(
            f"MXTPU_FLASH_BWD={mode!r} not recognised; "
            f"choices: auto, pallas, ref")
    # Measured on v5e (r4, honest chained harness with 512-blocks):
    # ref-bwd wins at T=512 (2.6 vs 3.5 ms), blockwise wins from
    # T=1024 (2.9 vs 4.0 ms; 2.2x at 2048, 3.8x at 4096) — and is the
    # only option when the score matrix would blow HBM.  (The r3
    # threshold of 4096 came from the retracted per-dispatch harness.)
    # Padded runs (valid_kv/delta set) always take the blockwise
    # kernels: attention_reference knows neither the pad-mask bound
    # nor a diagonal offset different from its own Tk - Tq.
    use_pallas = (mode == "pallas" or valid_kv is not None
                  or delta is not None
                  or (mode == "auto" and (max(Tq, Tk) >= 1024
                      or B * H * Tq * Tk * 4 > 2 ** 31)))
    if not use_pallas:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_reference(q_, k_, v_, causal,
                                                   sm_scale), q, k, v)
        return vjp(do)
    from . import interpret_mode
    # delta_i = rowsum(do ⊙ o) — the softmax-jacobian diagonal term
    delta_rows = jnp.sum(do.astype(jnp.float32) *
                         o.astype(jnp.float32), axis=-1)
    dq, dk, dv = _flash_backward(
        q.reshape(B * H, Tq, D), k.reshape(B * H, Tk, D),
        v.reshape(B * H, Tk, D), do.reshape(B * H, Tq, D),
        lse, delta_rows.reshape(B * H, Tq, 1), causal, sm_scale,
        interpret_mode(), valid_kv, delta)
    return (dq.reshape(B, H, Tq, D), dk.reshape(B, H, Tk, D),
            dv.reshape(B, H, Tk, D))


_flash_attention_pallas.defvjp(_fa_fwd, _fa_bwd)


_warned_fallback = set()


def _padded_flash(q, k, v, causal, scale):
    """Run the Pallas kernel on T-padded inputs, exactly.

    Sequence lengths are zero-padded up to the 8-multiple the TPU
    lowering needs, then the padded rows are sliced off the output.
    Padded KEY columns are masked *inside* the kernels: the static
    ``valid_kv`` bound turns their scores into the ``_NEG_INF``
    sentinel before the online softmax, so they carry exactly zero
    weight forward and contribute exactly zero dk/dv backward.  The
    causal diagonal keeps the ORIGINAL ``delta = Tk - Tq`` (passed
    statically), so cross-length causal attention — including
    Tq % 8 != Tk % 8, which the earlier plain-pad construction could
    not align — pads exactly too.  Padded QUERY rows compute values
    that are sliced off here; their cotangents are zero (jnp.pad's
    VJP), so no gradient leaks either direction.
    """
    Tq = q.shape[2]
    Tk = k.shape[2]
    pq = (-Tq) % 8
    pk = (-Tk) % 8
    padq = [(0, 0), (0, 0), (0, pq), (0, 0)]
    padk = [(0, 0), (0, 0), (0, pk), (0, 0)]
    out = _flash_attention_pallas(
        jnp.pad(q, padq), jnp.pad(k, padk), jnp.pad(v, padk),
        causal, scale, Tk if pk else None, Tk - Tq)
    return out[:, :, :Tq]


def flash_attention(q, k, v, causal=False, sm_scale=None):
    """Fused attention.  q: (B, H, Tq, D); k, v: (B, H, Tk, D).
    Pallas on TPU, lax reference elsewhere.
    Sequence lengths that are not multiples of 8 are padded to the
    block multiple and the pad keys masked statically inside the
    kernels (exactly — see ``_padded_flash``), so EVERY model-layer
    sequence length — odd T, causal, cross-length decoding — keeps the
    fused kernel's memory bound; the only remaining fallback is
    head_dim > 512.
    """
    import warnings

    from . import pallas_enabled
    D = q.shape[-1]
    scale = float(sm_scale) if sm_scale is not None else 1.0 / (D ** 0.5)
    Tq, Tk = q.shape[2], k.shape[2]
    if not pallas_enabled():
        # CPU / interpret-off: the reference path IS the intended path
        return attention_reference(q, k, v, causal, scale)
    if D > 512:
        # warn once per full (q, k) shape tuple: the O(T^2)-memory
        # fallback silently losing the flash memory guarantee is
        # exactly the failure mode a user needs to hear about — once
        # per distinct call shape, not once per step of a long epoch
        sig = ("head_dim", tuple(q.shape), tuple(k.shape))
        if sig not in _warned_fallback:
            _warned_fallback.add(sig)
            warnings.warn(
                f"flash_attention falling back to the O(T^2) reference "
                f"path (head_dim {D} > 512 kernel bound) for "
                f"q{tuple(q.shape)} k{tuple(k.shape)}", stacklevel=2)
        return attention_reference(q, k, v, causal, scale)
    if Tq % 8 or Tk % 8:
        return _padded_flash(q, k, v, bool(causal), scale)
    return _flash_attention_pallas(q, k, v, bool(causal), scale,
                                   None, None)
