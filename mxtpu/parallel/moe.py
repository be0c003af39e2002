"""Mixture-of-Experts with expert parallelism (the ``ep`` mesh axis).

The reference era predates MoE; this is a new-capability subsystem
mandated by the north star (full dp/tp/pp/sp/**ep** sharding support).
Design is the Mesh-TensorFlow / Switch-Transformer capacity
formulation — the TPU-native shape-static way to route:

  gate     : (tokens, E) softmax over experts
  dispatch : (tokens, E, C) one-hot — token t is slot c of expert e
  combine  : dispatch * gate prob
  expert_in  = einsum('td,tec->ecd', x, dispatch)   # (E, C, D)
  expert_out = ffn_e(expert_in[e])                   # per expert
  y          = einsum('ecd,tec->td', expert_out, combine)

Everything is dense einsums over static shapes (no ragged gathers —
XLA tiles them onto the MXU), and expert parallelism is pure SPMD:
``expert_in``/``expert_out`` carry a ``P("ep")`` sharding constraint
on the expert axis, so GSPMD lowers the two einsums into all-to-all
dispatch/return collectives over ICI exactly like the reference
NCCL/MPI frameworks hand-code.  Tokens over capacity are dropped
(their combine weight is 0 and the residual path carries them) —
Switch semantics.

The serving path routes otherwise (``topk_router``,
``routed_experts``): softmax (or a sigmoid a logit) over the experts,
the ``top_k`` largest a token (after a selection bias, where there is
one), renormalised, NO capacity and no dropped token; a device may hold
a share of the experts (``held``) beside a shared expert.  Tokens are
sorted by expert and the experts' products are grouped matrix products
over the experts held (``_grouped_dot``: on the TPU the megablox
grouped-matmul Pallas kernel, which walks the groups' tiles of rows and
skips an empty group; elsewhere ``jax.lax.ragged_dot``): the routed work
only, each touched expert's weights read once.
"""
from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "switch_router", "MoEFFN", "topk_router",
           "routed_experts"]


def switch_router(x2d, gate_w, capacity: int, *, key=None,
                  jitter: float = 0.0):
    """Top-1 (Switch) routing: returns (dispatch, combine, aux_loss).

    x2d: (T, D) tokens; gate_w: (D, E).
    dispatch: (T, E, C) one-hot float; combine = dispatch * gate_prob.
    aux_loss is the Switch load-balancing loss (mean fraction *
    mean router prob per expert, scaled by E).
    """
    T, D = x2d.shape
    E = gate_w.shape[1]
    logits = (x2d.astype(jnp.float32)
              @ gate_w.astype(jnp.float32))          # (T, E)
    if jitter > 0.0 and key is not None:
        logits = logits + jax.random.uniform(
            key, logits.shape, minval=-jitter, maxval=jitter)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)              # (T,)
    onehot = jax.nn.one_hot(expert, E,
                            dtype=jnp.float32)       # (T, E)
    # position of each token within its expert's queue (prefix count)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # (T, E), -1 ow
    keep = (pos >= 0) & (pos < capacity)
    pos_c = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    slot = jax.nn.one_hot(pos_c, capacity,
                          dtype=jnp.float32)         # (T, E, C)
    dispatch = slot * keep.astype(jnp.float32)[..., None]
    gate_p = jnp.sum(probs * onehot, axis=-1)        # (T,)
    combine = dispatch * gate_p[:, None, None]
    # load-balancing aux (Switch eq. 4): E * sum_e f_e * P_e
    frac = jnp.mean(onehot, axis=0)
    mean_p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_p)
    return dispatch, combine, aux


def moe_ffn(x, gate_w, w1, b1, w2, b2, *, capacity_factor: float = 1.25,
            mesh: Optional[Mesh] = None, ep_axis: str = "ep",
            activation: Callable = jax.nn.relu, key=None,
            jitter: float = 0.0):
    """Switch-MoE feed-forward.  x: (..., T, D) or (T, D);
    per-expert params w1: (E, D, H), b1: (E, H), w2: (E, H, D),
    b2: (E, D).  Returns (y, aux_loss).

    With ``mesh`` given, the expert axis of the dispatched activations
    is shard-constrained to ``ep_axis`` — GSPMD inserts the
    all-to-alls; each device computes only its local experts."""
    orig_shape = x.shape
    D = orig_shape[-1]
    x2d = x.reshape(-1, D)
    T = x2d.shape[0]
    E = w1.shape[0]
    capacity = max(int(math.ceil(T / E * capacity_factor)), 1)
    dispatch, combine, aux = switch_router(
        x2d, gate_w, capacity, key=key, jitter=jitter)

    cdt = x.dtype
    expert_in = jnp.einsum("td,tec->ecd", x2d.astype(jnp.float32),
                           dispatch).astype(cdt)     # (E, C, D)

    def constrain(v):
        if mesh is not None:
            v = jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P(ep_axis)))
        return v

    # the PARAMETERS shard over ep too — expert parallelism's whole
    # point is that each device stores and computes only its local
    # experts' weights (r4 review: constraining activations alone
    # leaves every device holding all E experts' parameters)
    w1c = constrain(w1.astype(cdt))
    b1c = constrain(b1.astype(cdt))
    w2c = constrain(w2.astype(cdt))
    b2c = constrain(b2.astype(cdt))
    expert_in = constrain(expert_in)
    h = jnp.einsum("ecd,edh->ech", expert_in, w1c) \
        + b1c[:, None, :]
    h = activation(h)
    expert_out = jnp.einsum("ech,ehd->ecd", h, w2c) \
        + b2c[:, None, :]
    expert_out = constrain(expert_out)
    y = jnp.einsum("ecd,tec->td", expert_out.astype(jnp.float32),
                   combine).astype(cdt)
    return y.reshape(orig_shape), aux


class MoEFFN:
    """Parameter container + apply for a Switch-MoE FFN (functional
    API — compose inside jitted train steps)."""

    def __init__(self, units: int, hidden: int, num_experts: int,
                 capacity_factor: float = 1.25, seed: int = 0):
        k = jax.random.PRNGKey(seed)
        ks = jax.random.split(k, 5)
        E, D, H = num_experts, units, hidden
        s1 = 1.0 / math.sqrt(D)
        s2 = 1.0 / math.sqrt(H)
        self.gate_w = jax.random.normal(ks[0], (D, E)) * s1
        self.w1 = jax.random.normal(ks[1], (E, D, H)) * s1
        self.b1 = jnp.zeros((E, H))
        self.w2 = jax.random.normal(ks[2], (E, H, D)) * s2
        self.b2 = jnp.zeros((E, D))
        self.capacity_factor = capacity_factor

    def params(self):
        return (self.gate_w, self.w1, self.b1, self.w2, self.b2)

    def apply(self, params, x, mesh=None, ep_axis="ep", key=None,
              jitter: float = 0.0):
        gate_w, w1, b1, w2, b2 = params
        return moe_ffn(x, gate_w, w1, b1, w2, b2,
                       capacity_factor=self.capacity_factor,
                       mesh=mesh, ep_axis=ep_axis, key=key,
                       jitter=jitter)


def topk_router(x2d, router_w, top_k: int, *, renormalise: bool = True,
                score: str = "softmax", bias=None, scale: float = 1.0):
    """Top-k routing with nothing dropped: ``(weights (T, k), experts
    (T, k) int32)``.  ``x2d``: (T, D); ``router_w``: (D, E).  The
    router's product and its scores are float32 at HIGHEST precision
    whatever the inputs are (E multiply-adds a token and feature: its
    cost is nothing, and a rounded router picks other experts at
    near-ties).  ``score``: ``"softmax"`` over the experts, or
    ``"sigmoid"`` of each logit alone.  The ``top_k`` largest of
    ``score + bias`` are chosen (the lower index first among equals;
    ``bias`` (E,) moves the choice and never the weights), each weighs
    its own score, divided by the chosen ones' sum where
    ``renormalise``, then times ``scale``."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"topk_router: unknown score {score!r}")
    if bias is None:
        top_p, top_e = jax.lax.top_k(probs, int(top_k))
    else:
        _, top_e = jax.lax.top_k(probs + bias.astype(jnp.float32),
                                 int(top_k))
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    if renormalise:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if scale != 1.0:
        top_p = top_p * jnp.float32(scale)
    return top_p, top_e.astype(jnp.int32)


# a tile of the grouped kernel: 128 rows by the whole contraction by as
# much of the output as keeps an expert's block of weights near 4 MB
# (two of them in flight fit the chip's fast memory beside the rows).
# At this cell's widths that is (128, 2304, 896) and (128, 896, 2304):
# 1.7 ms a layer's two products for a decode step's 184 rows over 62
# experts where the compiler's own ragged-dot kernel took 4.5 and the
# weights alone need 0.94 (my chip runs, PR 34: PERF.md sec. 6)
_TILE_ROWS = 128
_TILE_RHS_BYTES = 4.25 * 2 ** 20


def _grouped_dot(rows, w, sizes):
    """``rows[start_g : start_g + sizes_g] @ w[g]`` for every group g,
    float32 out: ``rows`` (M, K) sorted by group, ``w`` (G, K, N),
    ``sizes`` (G,) int32.  Rows past the last group hold no number.  On
    the TPU ``M`` is a whole number of ``_TILE_ROWS``."""
    from .. import kernels
    if not kernels.pallas_enabled():
        return jax.lax.ragged_dot(rows, w, sizes,
                                  preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    k, n = w.shape[1:]
    tile_n = n
    while k * tile_n * w.dtype.itemsize > _TILE_RHS_BYTES \
            and tile_n % 256 == 0:
        tile_n //= 2
    return gmm(rows, w, sizes, preferred_element_type=jnp.float32,
               tiling=(_TILE_ROWS, k, tile_n),
               interpret=kernels.interpret_mode())


def _swiglu(rows, w_in, w_out):
    """``(silu(g) * u) W_out`` with ``[g, u] = rows W_in``: inputs in the
    weights' dtype, accumulated in float32."""
    h = jnp.dot(rows.astype(w_in.dtype), w_in,
                preferred_element_type=jnp.float32)
    half = h.shape[-1] // 2
    act = (jax.nn.silu(h[:, :half]) * h[:, half:]).astype(w_out.dtype)
    return jnp.dot(act, w_out, preferred_element_type=jnp.float32)


def routed_experts(x, router_w, w_in, w_out, valid=None, *, top_k: int,
                   renormalise: bool = True, held=None,
                   score: str = "softmax", bias=None, scale: float = 1.0,
                   shared=None):
    """A sparse SwiGLU feed-forward: every token through its ``top_k``
    experts, weighted and summed; no bias in a product, no capacity.
    ``x``: (..., D); ``router_w``: (D, E); ``w_in``: (E', D, 2 F), each
    held expert's gate over its up projection; ``w_out``: (E', F, D);
    ``valid``: (...) bool, False for a padded token, which is routed
    nowhere: it is in no expert's group, reads no weight, and its routed
    output is zero.  Routing is ``topk_router``'s (``score``, ``bias``,
    ``renormalise``, ``scale``) over all E experts.

    ``held`` = (first, count): this device's share of an expert-parallel
    layer, experts ``first .. first + count - 1`` (``w_in`` and
    ``w_out`` hold those, E' = count; default: all E).  The weights are
    renormalised over all ``top_k`` chosen before the share is taken,
    so the shares' outputs add up to the whole layer's; an assignment to
    an expert held elsewhere goes nowhere, as a padded token's does.
    ``shared`` = (w_in (D, 2 F'), w_out (F', D)): a shared expert that
    every token passes, added to the routed sum (scope ``moe/shared``).

    Returns ``(y (..., D) float32, counts)``: ``counts`` the int32
    count of held experts given a token (``touched``) or, with
    ``held``, ``(2,)`` int32: that count and the assignments that
    landed on held experts.

    The ``T x top_k`` assignments are sorted by expert (scope
    ``moe/dispatch``); the two products run grouped over the experts'
    runs of rows (``moe/experts``: inputs in the weights' dtype,
    accumulated in float32); the rows go back to token order, are
    weighted and summed (``moe/combine``).  Every assignment of every
    valid token to a held expert is in the sum under any imbalance, all
    tokens on one expert included: a group is as long as its expert's
    tokens are many."""
    lead, D = x.shape[:-1], x.shape[-1]
    k = int(top_k)
    first, E = (0, router_w.shape[-1]) if held is None \
        else (int(held[0]), int(held[1]))
    x2d = x.reshape(-1, D)
    with jax.named_scope("moe/route"):
        weights, experts = topk_router(x2d, router_w, k,
                                       renormalise=renormalise, score=score,
                                       bias=bias, scale=scale)
        if held is not None:
            # an expert held elsewhere is nobody here: past every group
            experts = experts - first
            experts = jnp.where((experts >= 0) & (experts < E), experts, E)
        if valid is not None:
            # expert E is nobody: past every group
            experts = jnp.where(valid.reshape(-1, 1), experts, E)
    with jax.named_scope("moe/dispatch"):
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.sum(flat[:, None] == jnp.arange(E, dtype=jnp.int32),
                        axis=0, dtype=jnp.int32)
        # whole tiles of rows for the grouped kernel: the rows added
        # repeat token 0 past every group, where nothing is computed
        fill = (-order.shape[0]) % _TILE_ROWS
        rows = x2d.astype(w_in.dtype)[jnp.pad(order // k, (0, fill))]
    with jax.named_scope("moe/experts"):
        h = _grouped_dot(rows, w_in, sizes)
        half = h.shape[-1] // 2
        act = (jax.nn.silu(h[:, :half]) * h[:, half:]).astype(w_out.dtype)
        out = _grouped_dot(act, w_out, sizes)
    with jax.named_scope("moe/combine"):
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        out = out[back].reshape(-1, k, D)
        # a row past the last group is not computed: it holds no number
        y = jnp.sum(jnp.where((experts < E)[..., None],
                              out * weights[..., None], 0.0), axis=1)
        touched = jnp.sum(sizes > 0, dtype=jnp.int32)
        counts = touched if held is None else jnp.stack(
            [touched, jnp.sum(sizes, dtype=jnp.int32)])
    if shared is not None:
        with jax.named_scope("moe/shared"):
            y = y + _swiglu(x2d, *shared)
    return y.reshape(lead + (D,)), counts
