"""Ring attention — sequence/context parallelism over a mesh axis.

New capability mandated by the north star (SURVEY.md §2.4 row SP/CP,
§5.7): the reference (2018-era) has nothing for long-context training;
its closest machinery is per-length bucketing.  Here the sequence axis
is sharded over a mesh axis and K/V blocks rotate around the ring via
``lax.ppermute`` while each device accumulates online-softmax partial
results for its local Q block — attention memory per device is
O(T/p · D), enabling sequences p× longer than one chip's HBM allows.

Collectives ride ICI: each of the p steps moves only the local K/V
block to the next neighbour, which XLA schedules as neighbour-to-
neighbour ``collective-permute`` (bandwidth-optimal on a torus).

The per-block math runs in f32 (softmax stability) with MXU matmuls;
fusing the per-block compute into the Pallas flash kernel is the
follow-up — the ring structure is identical.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30

__all__ = ["ring_attention"]


def _block_update(q, kb, vb, m, l, acc, scale, causal, my_idx, kv_idx,
                  t_local):
    """One online-softmax accumulation of q against a K/V block."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        row = jax.lax.broadcasted_iota(
            jnp.int32, s.shape[-2:], 0) + my_idx * t_local
        col = jax.lax.broadcasted_iota(
            jnp.int32, s.shape[-2:], 1) + kv_idx * t_local
        s = jnp.where(col <= row, s, _NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    # invariant: _NEG_INF is a FINITE sentinel, so exp(sentinel - m)
    # underflows to 0 for fully-masked blocks instead of producing
    # exp(-inf - -inf) = NaN — do not replace it with -jnp.inf
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
    acc_new = acc * alpha + pv
    return m_new, l_new, acc_new


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = False,
                   sm_scale: Optional[float] = None):
    """Attention with the sequence axis sharded over ``mesh[axis]``.

    q, k, v: (B, H, T, D) with T divisible by the axis size.  Returns
    (B, H, T, D) with the same sharding.  Semantics match
    ``kernels.attention_reference`` (tested to parity).
    """
    D = q.shape[-1]
    scale = float(sm_scale) if sm_scale is not None else 1.0 / (D ** 0.5)
    p_size = mesh.shape[axis]

    def local_fn(q_loc, k_loc, v_loc):
        # q_loc etc: (B, H, T/p, D) — this device's shard
        my_idx = lax.axis_index(axis)
        t_local = q_loc.shape[2]
        qf = q_loc.astype(jnp.float32)
        m = jnp.full(q_loc.shape[:3] + (1,), _NEG_INF, jnp.float32)
        l = jnp.zeros_like(m)
        acc = jnp.zeros(q_loc.shape[:3] + (q_loc.shape[3],),
                        jnp.float32)
        # mark the zero-init carries as device-varying so the fori_loop
        # carry types line up with the per-device accumulation
        m, l, acc = (lax.pcast(a, (axis,), to="varying")
                     for a in (m, l, acc))
        perm = [(j, (j + 1) % p_size) for j in range(p_size)]

        def body(i, carry):
            m, l, acc, kb, vb = carry
            kv_idx = (my_idx - i) % p_size
            m, l, acc = _block_update(qf, kb.astype(jnp.float32),
                                      vb.astype(jnp.float32), m, l, acc,
                                      scale, causal, my_idx, kv_idx,
                                      t_local)
            kb = lax.ppermute(kb, axis, perm)
            vb = lax.ppermute(vb, axis, perm)
            return m, l, acc, kb, vb

        m, l, acc, _, _ = lax.fori_loop(
            0, p_size, body, (m, l, acc, k_loc, v_loc))
        safe = jnp.where(l == 0.0, 1.0, l)
        return (acc / safe).astype(q_loc.dtype)

    spec = P(None, None, axis, None)
    from . import _device_put_global, _mesh_is_multiprocess
    if _mesh_is_multiprocess(mesh):
        # cross-process mesh: host inputs must be placed as global
        # arrays (every process passes the same full value; jit cannot
        # implicitly device_put onto non-addressable shardings)
        q, k, v = (_device_put_global(a, mesh, spec)
                   for a in (q, k, v))
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
