"""Fused RNN operator — LSTM/GRU/vanilla, multi-layer, bidirectional.

Reference: ``src/operator/rnn.cc``† + ``src/operator/nn/cudnn/
cudnn_rnn-inl.h``† — the fused cuDNN RNN op with a single flat parameter
vector, consumed by ``gluon/rnn/rnn_layer.py``†'s ``_forward_kernel``.

TPU-native design: one ``lax.scan`` per layer/direction over time.  The
input-to-hidden projection for ALL timesteps is hoisted out of the scan
as a single large matmul (MXU-friendly: one (T·N, in)×(in, G·H) GEMM
per layer instead of T small ones); only the hidden-to-hidden GEMM and
the elementwise gate math live inside the scan body.  XLA unrolls
nothing — the scan lowers to a While with static shapes.

Flat parameter layout (structurally the cuDNN/MXNet convention —
weights first, then biases):
  for layer in 0..L-1: for direction in 0..D-1:
      W_i2h (G*H, in_l)   then  W_h2h (G*H, H)
  then, in the same (layer, direction) order:
      b_i2h (G*H,)        then  b_h2h (G*H,)
with in_0 = input_size and in_l = D*H for l > 0.  Gate order: LSTM
[i, f, g, o], GRU [r, z, n] (cuDNN order).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..base import MXNetError
from ..ops.registry import Param, register_op

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def rnn_param_size(num_layers: int, input_size: int, state_size: int,
                   bidirectional: bool, mode: str) -> int:
    """Total flat parameter vector length (reference
    ``rnn_param_size``† in rnn-inl.h)."""
    gates = _GATES[mode]
    dirs = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_size = input_size if layer == 0 else state_size * dirs
        size += gates * state_size * (in_size + state_size + 2) * dirs
    return size


def _slice_params(params, num_layers, input_size, state_size,
                  dirs, gates):
    """Static slicing of the flat vector → per-(layer, dir) arrays."""
    H, G = state_size, gates
    weights = []
    off = 0
    for layer in range(num_layers):
        in_size = input_size if layer == 0 else H * dirs
        per_layer = []
        for _ in range(dirs):
            w_i2h = params[off:off + G * H * in_size].reshape(G * H,
                                                             in_size)
            off += G * H * in_size
            w_h2h = params[off:off + G * H * H].reshape(G * H, H)
            off += G * H * H
            per_layer.append([w_i2h, w_h2h, None, None])
        weights.append(per_layer)
    for layer in range(num_layers):
        for d in range(dirs):
            weights[layer][d][2] = params[off:off + G * H]
            off += G * H
            weights[layer][d][3] = params[off:off + G * H]
            off += G * H
    return weights, off


def _scan_dir(x, h0, c0, w_h2h, pre, mode, H, reverse):
    """One direction of one layer. pre: (T, N, G*H) precomputed i2h
    (+ biases as applicable); returns (outputs (T,N,H), h_T, c_T)."""

    if mode == "lstm":
        def body(carry, pre_t):
            h, c = carry
            gates = pre_t + h @ w_h2h.T
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c2 = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h2 = jax.nn.sigmoid(o) * jnp.tanh(c2)
            return (h2, c2), h2
        (h_t, c_t), ys = lax.scan(body, (h0, c0), pre, reverse=reverse)
        return ys, h_t, c_t

    if mode == "gru":
        # pre holds W x + b_i2h for all gates + b_h2h for r,z only; the
        # n-gate recurrent bias b_Rn is loop-invariant and closed over
        # (applied inside the reset product).
        pre_t, b_rn = pre

        def body(h, pre_step):
            hp = h @ w_h2h.T
            pr, pz, pn = jnp.split(pre_step, 3, axis=-1)
            hr, hz, hn = jnp.split(hp, 3, axis=-1)
            r = jax.nn.sigmoid(pr + hr)
            z = jax.nn.sigmoid(pz + hz)
            n = jnp.tanh(pn + r * (hn + b_rn))
            h2 = (1.0 - z) * n + z * h
            return h2, h2
        h_t, ys = lax.scan(body, h0, pre_t, reverse=reverse)
        return ys, h_t, None

    act = jnp.tanh if mode == "rnn_tanh" else jax.nn.relu

    def body(h, pre_t):
        h2 = act(pre_t + h @ w_h2h.T)
        return h2, h2
    h_t, ys = lax.scan(body, h0, pre, reverse=reverse)
    return ys, h_t, None


def _rnn_impl(data, parameters, state, *extra, state_size, num_layers,
              mode="lstm", bidirectional=False, p=0.0,
              state_outputs=False):
    """The fused RNN lowering rule. data: (T, N, I); state: (L*D, N, H);
    lstm also takes state_cell; an optional trailing PRNG key input
    enables inter-layer dropout.  Returns (output, state_n
    [, statecell_n]) — callers that set ``state_outputs=False`` get
    just the output."""
    if mode not in _GATES:
        raise MXNetError(f"unknown RNN mode {mode!r}")
    if mode == "lstm":
        state_cell = extra[0] if extra else None
        key = extra[1] if len(extra) > 1 else None
    else:
        state_cell = None
        key = extra[0] if extra else None
    H = int(state_size)
    L = int(num_layers)
    dirs = 2 if bidirectional else 1
    G = _GATES[mode]
    T, N, I = data.shape

    weights, used = _slice_params(parameters, L, I, H, dirs, G)
    if used != parameters.shape[0]:
        raise MXNetError(
            f"RNN parameter vector has {parameters.shape[0]} elements, "
            f"layout needs {used} (use rnn_param_size)")

    x = data
    h_finals = []
    c_finals = []
    for layer in range(L):
        outs = []
        for d in range(dirs):
            w_i2h, w_h2h, b_i2h, b_h2h = weights[layer][d]
            idx = layer * dirs + d
            h0 = state[idx]
            c0 = state_cell[idx] if state_cell is not None else None
            if mode == "gru":
                b_rn = b_h2h[2 * H:]
                b_rz = jnp.concatenate([b_h2h[:2 * H],
                                        jnp.zeros_like(b_rn)])
                pre = (x @ w_i2h.T + b_i2h + b_rz, b_rn)
            else:
                pre = x @ w_i2h.T + b_i2h + b_h2h
            ys, h_t, c_t = _scan_dir(x, h0, c0, w_h2h, pre, mode, H,
                                     reverse=(d == 1))
            outs.append(ys)
            h_finals.append(h_t)
            if c_t is not None:
                c_finals.append(c_t)
        x = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0.0 and key is not None and layer < L - 1:
            sub = jax.random.fold_in(key, layer) \
                if jnp.issubdtype(key.dtype, jax.dtypes.prng_key) \
                else jax.random.fold_in(jax.random.wrap_key_data(key),
                                        layer)
            keep = jax.random.bernoulli(sub, 1.0 - p, x.shape)
            x = jnp.where(keep, x / (1.0 - p), 0.0)

    state_n = jnp.stack(h_finals)
    if mode == "lstm":
        cell_n = jnp.stack(c_finals)
        if state_outputs:
            return x, state_n, cell_n
        return x
    if state_outputs:
        return x, state_n
    return x


def _rnn_num_outputs(attrs) -> int:
    so = attrs.get("state_outputs", False)
    if isinstance(so, str):
        so = so not in ("False", "false", "0")
    if not so:
        return 1
    return 3 if attrs.get("mode", "lstm") == "lstm" else 2


register_op(
    "RNN", num_inputs=-1, num_outputs=3,
    params=[Param("state_size", int),
            Param("num_layers", int),
            Param("mode", str, "lstm",
                  enum=("rnn_relu", "rnn_tanh", "lstm", "gru")),
            Param("bidirectional", bool, False),
            Param("p", float, 0.0),
            Param("state_outputs", bool, False)],
    num_outputs_fn=_rnn_num_outputs,
    doc=_rnn_impl.__doc__)(_rnn_impl)


def _resident_layout(x):
    """The layout the default device keeps an array of ``x``'s shape
    and dtype in.  The TPU chooses it from the shape — a float32 table
    of head_dim 64 lies with ``L`` minor, in (8, 128) tiles over
    (head_dim, L) — and a loop that carries such an array is free to
    carry it otherwise, at the price of a copy of it on the way in and
    another on the way out."""
    from jax.experimental.layout import Layout
    dev = jax.devices()[0]
    return Layout.from_pjrt_layout(dev.client.get_default_layout(
        np.dtype(x.dtype), tuple(x.shape), dev))


def _kv_cache_write_op(table, new, step, layer=0, plane=0, ring=False):
    """In-place write of one layer's new keys (``plane=0``) or values
    (``plane=1``) into the whole KV slot table of incremental decode
    (mxtpu.serving.generate).  ``table``: (layers, 2, B, H, L, D) —
    axis 2 holds one cache *lane* per in-flight request; ``new``:
    (B, H, T, D) freshly projected keys or values; ``step``: (B,)
    per-lane write offsets (each lane advances independently under
    continuous batching).  Returns the SAME table with rows
    ``[layer, plane, b, :, step_b : step_b + T, :]`` replaced, in the
    donated buffer — the table is never taken apart and re-stacked.
    One token a lane (``T == 1``, the decode step) on a TPU that keeps
    the table with ``L`` minor is a masked column store, one Pallas
    kernel over the lanes (``mxtpu.kernels.kv_write``); every other
    write is the lanes' loop of ``_write_lanes``.  Which of the two is
    decided from what is observed here — ``T``, the backend, the
    table's layout on the device — and both store the same bits.
    ``layer`` and ``plane`` are static attributes, so they ride the
    symbol's JSON.  Values are cast to the table's dtype on write, so
    a bf16 cache under mxtpu.amp stays bf16 regardless of compute
    dtype; a write that would run past ``L`` is clamped to end there,
    as ``dynamic_update_slice`` does.  With ``ring`` the table's ``L``
    columns are a ring: position ``p`` lives at column ``p mod L``, so a
    lane keeps its last ``L`` positions however long its context grows
    (a sliding-window layer's table: ``cached_attention(window=...)``
    reads it).  One token is the same column store at
    ``step mod L``; ``T`` tokens, which may straddle the wrap, are one
    masked store of the plane (``_write_ring``)."""
    from ..kernels import kv_write
    new = new.astype(table.dtype)
    idx = jnp.asarray(step).astype(jnp.int32)
    if ring:
        idx = idx % table.shape[4]
        if new.shape[2] > 1:
            return _write_ring(table, new, idx, layer, plane)
    if new.shape[2] == 1 and _capacity_is_minor(table):
        return kv_write.kv_write(
            table, new, jnp.clip(idx, 0, table.shape[4] - 1),
            jnp.int32(layer), jnp.int32(plane))
    return _write_lanes(table, new, idx, jnp.int32(layer),
                        jnp.int32(plane))


def _capacity_is_minor(table):
    """Whether the column store may take a one-token write: Pallas
    kernels run here, and the device keeps the table with ``L`` minor
    and ``head_dim`` next, so that the table with its last two axes
    swapped is the same bytes and the kernel binds it where it lies."""
    from .. import kernels
    if not kernels.pallas_enabled():
        return False
    order = tuple(_resident_layout(table).major_to_minor)
    return order == (0, 1, 2, 3, 5, 4)


# The float32 scores (B, H, T, L) past which XLA's form of a chunk's
# attention loses to the kernel.  On a v5e (PERF.md §6) XLA's
# fusions keep 42-71 MB of scores on the chip and take 0.05-0.09 ms a
# layer, as fast as the kernel or faster (a ring of 1,280, Olmo's one
# row of 2,304); 277-283 MB go through HBM and take 1.2-1.4 ms, where
# the kernel takes 0.2-0.6 (Mellum's full table, Olmo's four rows).
# Between them: 80 MiB (granite's rows of 256 in twos, the
# block-diffusion prefill row of 256 over 2,560 columns) XLA takes
# 0.09-0.10 ms, the kernel 0.11-0.20 on granite's and 0.04-0.12 on the
# prefill row's by its frontier; the block-diffusion pass's 122.5 MiB
# (49 rows of 8 over 2,560 columns) XLA takes 0.91 ms, the kernel 0.28
# at the traffic's frontiers and 0.44 at the table's end.
_SCORES_FOR_KERNEL = 96 << 20


def _attends_by_kernel(q, k_cache, block=1):
    """Whether ``cached_attention`` takes the blockwise kernel: Pallas
    kernels run here, the queries are a chunk (``T > 1``: a decode
    step's one query a row keeps XLA's form and its program), the table
    is bfloat16 — the dtype its products are fed at; a float32 table is
    read at twice the bytes for the same bfloat16 products and keeps
    XLA's form — the shapes tile (a group's ``g * T`` rows a whole
    number of bfloat16 sublane tiles, ``L`` of 128-column blocks), and
    XLA's form would make ``_SCORES_FOR_KERNEL`` bytes of
    float32 scores or more.  The kernel knows the causal and the
    block-causal mask alike (``block``: it stops at the end of each
    row's last query's block)."""
    from .. import kernels
    B, H, T = q.shape[:3]
    Hk, L = k_cache.shape[1], k_cache.shape[2]
    return (T > 1 and kernels.pallas_enabled()
            and k_cache.dtype == jnp.bfloat16
            and H // Hk * T % 16 == 0
            and L % 128 == 0 and B * H * T * L * 4 >= _SCORES_FOR_KERNEL)


def _keys_are_minor(table):
    """Whether the device keeps a slot table of ``table``'s shape
    (..., L, D) with ``L`` minor (a head of 64 lies so), so that the
    kernel binds it with its last two axes swapped, where it lies."""
    order = tuple(_resident_layout(table).major_to_minor)
    n = len(order)
    return order == tuple(range(n - 2)) + (n - 1, n - 2)


@jax.jit
def _write_lanes(table, new, idx, layer, plane):
    """The lanes' loop of ``kv_cache_write``: each turn one
    ``lax.dynamic_update_slice`` on the 6-D table, which XLA performs
    in the donated buffer, the loop's carry held to the layout the
    table has on the device (see ``_resident_layout``).  Right for a
    prefill's ``T`` contiguous positions, which are runs along the
    minor axis; a single position is a column there, stored element by
    element (4.2 us an update: PERF.md, PR 26 and PR 29), which is why
    the decode step takes the kernel.  What was measured against the
    loop on the chip (PERF.md, PR 26): the same updates unrolled take
    a third less time and add half a minute to every set-up;
    ``lax.scatter`` and a loop whose carry is left free bring two
    copies of the whole table, ``.at[...].set`` with index arrays a
    transpose of it round every write.
    ``layer`` and ``plane`` arrive as values so that one traced loop
    serves every plane: an eager forward compiles it once, and inside
    a program it is one callee whose arguments XLA folds to the
    constants they are."""
    from jax.experimental.layout import with_layout_constraint
    held = _resident_layout(table)
    zero = jnp.int32(0)

    def one_lane(b, t):
        t = with_layout_constraint(t, held)
        rows = lax.dynamic_slice_in_dim(new, b, 1, axis=0)[None, None]
        t = lax.dynamic_update_slice(
            t, rows, (layer, plane, b, zero, idx[b], zero))
        return with_layout_constraint(t, held)

    return lax.fori_loop(0, new.shape[0], one_lane, table)


def _write_ring(table, new, start, layer, plane):
    """``T`` positions a lane into a ring table: row b's columns
    ``(start_b + t) mod L`` take ``new[b, :, t]``, every other column
    stays.  The new rows are grown to the ring's width, turned by
    ``start_b`` and stored under a mask of the columns they cover: one
    pass over the plane, whether or not the run straddles the wrap (a
    run is cut at the wrap at a place known only when the program runs,
    so it is no two slices of static size).  The table here is a
    prefill's few gathered lanes, so the pass is small."""
    L, T = table.shape[4], new.shape[2]
    if T > L:
        raise MXNetError(f"kv_cache_write: {T} positions into a ring "
                         f"of {L}")
    with jax.named_scope("kv_ring_write"):
        wide = jnp.pad(new, ((0, 0), (0, 0), (0, L - T), (0, 0)))
        turned = jax.vmap(lambda rows, by: jnp.roll(rows, by, axis=1))(
            wide, start)
        covered = (jnp.arange(L, dtype=jnp.int32)[None, :]
                   - start[:, None]) % L < T
        return table.at[layer, plane].set(jnp.where(
            covered[:, None, :, None], turned, table[layer, plane]))


def write_whole_lanes(table, rows, lanes, axis):
    """``table`` with lane ``lanes[r]`` (along ``axis``) replaced whole
    by row ``r`` of ``rows``, for every r in turn: the way a prefill's
    rows go back into a slot table whose lanes are megabytes each.  As
    in ``_write_lanes``, a loop over the rows, each turn one
    ``lax.dynamic_update_slice`` on the whole table with the carry
    held to the table's device layout, so XLA writes into the donated
    buffer and no second table exists.  Rows that name one lane (the
    padding rows' scratch slot) land in order, the last one staying."""
    from jax.experimental.layout import with_layout_constraint
    held = _resident_layout(table)
    zero = jnp.int32(0)

    def one_row(r, t):
        t = with_layout_constraint(t, held)
        row = lax.dynamic_slice_in_dim(rows, r, 1, axis=axis)
        at = [zero] * t.ndim
        at[axis] = lanes[r]
        t = lax.dynamic_update_slice(t, row.astype(t.dtype), at)
        return with_layout_constraint(t, held)

    return lax.fori_loop(0, rows.shape[axis], one_row, table)


def read_whole_lanes(table, lanes, axis):
    """Lanes ``lanes[r]`` of ``table`` (along ``axis``) side by side,
    one ``lax.dynamic_slice`` a row: what a prefill takes out of a slot
    table before ``write_whole_lanes`` puts it back.  Not ``jnp.take``:
    the chip's compiler turns a gather of lanes that are megabytes each
    into slices of the WHOLE table laid beside it (5.0 GB of temporaries
    for a 4.5 GB table of keys and values), where a slice at a computed
    offset reads the table where it lies; and not slices stored one by
    one into a zeroed result, which the compiler does not do in place
    either (0.3 GB more than this at four lanes of 142 MB: PERF.md,
    PR 32)."""
    return jnp.concatenate(
        [lax.dynamic_slice_in_dim(table, lanes[r], 1, axis=axis)
         for r in range(lanes.shape[0])], axis=axis)


register_op("kv_cache_write", num_inputs=3, differentiable=False,
            params=[Param("layer", int, 0, lower=0),
                    Param("plane", int, 0, enum=(0, 1)),
                    Param("ring", bool, False)],
            doc=_kv_cache_write_op.__doc__)(_kv_cache_write_op)


def _kv_cache_read_op(table, layer=0, plane=0):
    """One layer's keys (``plane=0``) or values (``plane=1``) read from
    the KV slot table: (layers, 2, B, H, L, D) -> (B, H, L, D).  A
    static slice, so the attention contraction reads the table's own
    memory; ``layer`` and ``plane`` are static attributes like
    ``kv_cache_write``'s."""
    return table[layer, plane]


register_op("kv_cache_read", num_inputs=1, differentiable=False,
            params=[Param("layer", int, 0, lower=0),
                    Param("plane", int, 0, enum=(0, 1))],
            doc=_kv_cache_read_op.__doc__)(_kv_cache_read_op)


def _cached_attention_op(q, k_cache, v_cache, step, sm_scale=-1.0,
                         window=0, layer=-1, block=1):
    """Decode-step attention over a preallocated KV cache.  ``q``:
    (B, H, T, D) — the T new query tokens of each lane sit at absolute
    positions ``step_b + t``; ``k_cache``/``v_cache``: (B, H_kv, L, D)
    of any float dtype, with ``H = g * H_kv``: query head ``h`` reads
    key/value head ``h // g`` (grouped-query attention; ``g = 1`` is
    the equal-heads case and lowers to the program it always did).
    Causal masking against valid lengths (key position l attends iff
    ``l <= step_b + t``), so stale cache contents beyond a lane's
    frontier — including leftovers from a previous occupant of a
    reused lane — are unreachable by construction.  Scores, softmax
    and the probs @ V contraction all accumulate in f32 and only the
    final output is cast back to the query dtype: the zero-hazard
    bf16-decode/f32-accum recipe contracts/prec/generate_decode.json
    pins.  ``sm_scale < 0`` means 1/sqrt(D).

    ``window`` > 0 makes the cache's ``L`` columns a ring written by
    ``kv_cache_write(ring=True)`` and bounds a query's context to itself
    and the ``window - 1`` positions before it (a sliding-window layer):
    column ``c`` holds for the query at ``p`` the position ``p' = p -
    ((p - c) mod L)``, the newest one at or before ``p`` that lives
    there, and the mask is on that position — ``p - p' < window`` and
    ``p' >= 0`` — so a column nobody has written since the lane was
    taken, one a previous occupant left, and the later positions of the
    query's own chunk (``L >= window + T - 1`` puts them a window away)
    are unreachable by construction, as the frontier mask makes them
    without a window (a table that holds every position is a ring no
    wrap has reached).  Its work lies under the scope
    ``window_attention``; with no window the op is the program it
    was.

    ``block`` > 1 is the block-causal mask of a block-diffusion decoder:
    key position ``l`` attends iff ``l // block <= (step_b + t) //
    block``, so a query sees every earlier block and its own whole
    block, and each row's frontier is the end of its query's block.  A
    caller feeds whole blocks from a block's start, so every column up to
    that end is written by this call or before it, and columns past a
    row's valid tokens lie in a later block.  With ``block`` 1 (the
    causal mask) the op is the program it was.

    ``layer`` >= 0 hands the slot table itself (layers, 2, B, H_kv, L,
    D) as ``k_cache`` and ``v_cache``, the same table both, whose planes
    ``[layer, 0]`` and ``[layer, 1]`` are the keys and values: what
    ``kv_cache_read`` would slice out, so that the kernel below reads
    them where the table lies (a plane sliced out is a copy the
    compiler writes for a custom call's operand).

    A chunk (``T > 1``) of such a table in bfloat16 on a TPU is the
    blockwise Pallas kernel
    ``mxtpu.kernels.flash_attention.cached_attention``, which writes no
    scores out and stops at each row's frontier (``_attends_by_kernel``
    decides from the shapes, the dtype and the backend); a decode step
    (``T = 1``), a float32 table, planes handed alone and the CPU keep
    the XLA form below, which is the kernel's parity oracle."""
    table = None
    if layer >= 0:
        table = k_cache
        k_cache, v_cache = k_cache[layer, 0], v_cache[layer, 1]
    B, H, T, D = q.shape
    Hk, L = k_cache.shape[1], k_cache.shape[2]
    if H % Hk:
        raise MXNetError(f"cached_attention: {H} query heads over "
                         f"{Hk} key/value heads")
    scale = (1.0 / float(np.sqrt(D))) \
        if (sm_scale is None or sm_scale < 0) else float(sm_scale)
    window, block = int(window), int(block)
    if window and block > 1:
        raise MXNetError("cached_attention: a window and a block mask "
                         "together")
    if window > L - T + 1:
        raise MXNetError(
            f"cached_attention: a ring of {L} columns holds a window of "
            f"1..{L - T + 1} positions for {T} new tokens, not {window}")
    with jax.named_scope("window_attention" if window
                         else "cached_attention"):
        s = jnp.asarray(step).astype(jnp.int32)
        if table is not None and _attends_by_kernel(q, k_cache, block):
            from ..kernels.flash_attention import cached_attention
            minor = _keys_are_minor(table)
            return cached_attention(
                q, jnp.swapaxes(table, -1, -2) if minor else table, s,
                scale, layer, window=window, keys_minor=minor, block=block)
        pos_q = s[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        pos_k = jnp.arange(L, dtype=jnp.int32)
        if window:
            back = (pos_q[:, :, None] - pos_k[None, None, :]) % L
            mask = (back < window) & (back <= pos_q[:, :, None])
        elif block > 1:
            mask = pos_k[None, None, :] // block \
                <= pos_q[:, :, None] // block
        else:
            mask = pos_k[None, None, :] <= pos_q[:, :, None]
        q32 = q.astype(jnp.float32)
        k32, v32 = k_cache.astype(jnp.float32), v_cache.astype(jnp.float32)
        if H == Hk:
            scores = jnp.einsum("bhtd,bhld->bhtl", q32, k32,
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(mask[:, None, :, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhtl,bhld->bhtd", probs, v32,
                             preferred_element_type=jnp.float32)
            return out.astype(q.dtype)
        # grouped: the g query heads of a group share one read of
        # their key/value head, which is never repeated in memory
        qg = q32.reshape(B, Hk, H // Hk, T, D)
        scores = jnp.einsum("bkgtd,bkld->bkgtl", qg, k32,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgtl,bkld->bkgtd", probs, v32,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, H, T, D).astype(q.dtype)


register_op("cached_attention", num_inputs=4, differentiable=False,
            params=[Param("sm_scale", float, -1.0),
                    Param("window", int, 0, lower=0),
                    Param("layer", int, -1, lower=-1),
                    Param("block", int, 1, lower=1)],
            doc=_cached_attention_op.__doc__)(_cached_attention_op)


def rope_frequencies(head_dim, theta, yarn=None):
    """The ``head_dim / 2`` rotary frequencies of a layer, in numpy
    (they are a static attribute of ``rope``).  Plain: ``theta^(-2j /
    head_dim)``.  ``yarn`` (Peng et al. 2023, arXiv:2309.00071; a dict
    with ``factor``, ``original_max_position_embeddings`` and
    optionally ``beta_fast`` 32, ``beta_slow`` 1) divides the slow
    frequencies by ``factor`` and leaves the fast ones, with a linear
    ramp between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over the original length."""
    j = np.arange(0, head_dim, 2, dtype=np.float64)  # mxlint: disable=dtype-hygiene (host-side table, a static attribute: float64 on purpose)
    plain = float(theta) ** (-j / head_dim)
    if not yarn:
        return plain
    factor = float(yarn["factor"])
    original = float(yarn["original_max_position_embeddings"])

    def dim_of(turns):
        return head_dim * np.log(original / (turns * 2 * np.pi)) \
            / (2 * np.log(float(theta)))

    lo = max(int(np.floor(dim_of(float(yarn.get("beta_fast", 32.0))))), 0)
    hi = min(int(np.ceil(dim_of(float(yarn.get("beta_slow", 1.0))))),
             head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - lo)  # mxlint: disable=dtype-hygiene (host-side table)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rope_op(x, step, inv_freq=(), scale=1.0):
    """Rotary position embedding of queries or keys at their absolute
    positions.  ``x``: (B, H, T, D), row b's token t at position
    ``step_b + t``; ``inv_freq``: the ``D / 2`` frequencies, a static
    attribute (``rope_frequencies``); ``scale`` multiplies cos and sin
    (YaRN's ``attention_factor``).  Halves convention: ``x * cos +
    rotate_half(x) * sin`` with ``rotate_half(x) = [-x2, x1]`` over the
    two halves of ``D`` and the angles repeated over both.  Angles,
    cos and sin in float32 whatever ``x`` is.  Keys are rotated before
    ``kv_cache_write``, so a cache holds rotated keys and a decode step
    rotates one position a lane."""
    D = x.shape[-1]
    freq = np.asarray(inv_freq, np.float32)
    if freq.shape != (D // 2,):
        raise MXNetError(f"rope: {freq.size} frequencies for a head of "
                         f"{D}")
    with jax.named_scope("rope"):
        pos = jnp.asarray(step).astype(jnp.float32)[:, None] \
            + jnp.arange(x.shape[2], dtype=jnp.float32)[None, :]
        angle = pos[:, None, :, None] * freq            # (B, 1, T, D/2)
        cos = jnp.cos(angle) * float(scale)
        sin = jnp.sin(angle) * float(scale)
        x32 = x.astype(jnp.float32)
        x1, x2 = x32[..., :D // 2], x32[..., D // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
        return out.astype(x.dtype)


register_op("rope", num_inputs=2, differentiable=False,
            params=[Param("inv_freq", tuple, ()),
                    Param("scale", float, 1.0)],
            doc=_rope_op.__doc__)(_rope_op)


# ----------------------------------------------------------------------
# state-space (Mamba-2) layers of incremental decode: a lane's state is
# not a row per token but one array rewritten at every token
# ----------------------------------------------------------------------
def _rms(x32, weight, eps):
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return x32 * lax.rsqrt(ms + eps) * weight.astype(jnp.float32)


def _rms_norm_op(x, weight, eps=1e-5, scope="rms_norm"):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the last axis, the
    statistics in float32 whatever ``x`` is.  ``scope`` names the
    ``jax.named_scope`` the work is found under in a trace (the
    attention layers' norm of queries and keys asks for ``qk_norm``)."""
    with jax.named_scope(scope):
        return _rms(x.astype(jnp.float32), weight, eps).astype(x.dtype)


register_op("rms_norm", num_inputs=2,
            params=[Param("eps", float, 1e-5),
                    Param("scope", str, "rms_norm")],
            doc=_rms_norm_op.__doc__)(_rms_norm_op)


def _attn_gate_op(x, gate):
    """An attention layer's output gate: ``x * sigmoid(gate)``, in
    float32 whatever ``x`` is, under the scope ``attn_gate``."""
    with jax.named_scope("attn_gate"):
        return (x.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)


register_op("attn_gate", num_inputs=2,
            doc=_attn_gate_op.__doc__)(_attn_gate_op)


def _gated_rms_norm_op(y, z, weight, eps=1e-5, group=0,
                       norm_before_gate=False):
    """A mixer's output gate and norm.  As Mamba-2 has them:
    ``rms_norm(y * silu(z), weight)`` over the whole last axis (one
    group).  ``group`` > 0 is the per-head form: the last axis is cut
    into runs of ``group`` values, each normalised alone with the one
    ``weight`` of ``group`` values they share; ``norm_before_gate``
    gates the normalised value, ``rms_norm(y, weight) * silu(z)``, as
    the Gated DeltaNet layer does."""
    with jax.named_scope("rms_norm"):
        y32 = y.astype(jnp.float32)
        gate = jax.nn.silu(z.astype(jnp.float32))
        if not norm_before_gate:
            y32 = y32 * gate
        if group:
            heads = y32.reshape(y32.shape[:-1] + (-1, group))
            y32 = _rms(heads, weight, eps).reshape(y32.shape)
        else:
            y32 = _rms(y32, weight, eps)
        if norm_before_gate:
            y32 = y32 * gate
        return y32.astype(y.dtype)


register_op("gated_rms_norm", num_inputs=3,
            params=[Param("eps", float, 1e-5),
                    Param("group", int, 0, lower=0),
                    Param("norm_before_gate", bool, False)],
            doc=_gated_rms_norm_op.__doc__)(_gated_rms_norm_op)


def _keeps_state(step, length):
    """(B,) bool: False for a lane that takes its first tokens (``step``
    0, ``length`` > 0) and so starts from zero state whatever it held:
    admission needs no zeroing of the table from the host.  A row with
    nothing valid keeps what its lane holds."""
    return (jnp.asarray(step).astype(jnp.int32) > 0) \
        | (jnp.asarray(length).astype(jnp.int32) <= 0)


def _fresh(state, step, length):
    """``state`` with the lanes that ``_keeps_state`` says start anew
    zeroed."""
    keep = _keeps_state(step, length)
    return jnp.where(keep.reshape((-1,) + (1,) * (state.ndim - 1)),
                     state, jnp.zeros((), state.dtype))


def _ssm_conv_op(table, x, weight, *rest, layer=0, no_bias=False):
    """Causal depthwise convolution with carried state, then silu.
    Inputs ``(table, x, weight, bias, step, length)``, or without
    ``bias`` where ``no_bias`` is set.
    ``table``: (layers, B, K-1, C), lane b's last K-1 inputs of each
    layer; ``x``: (B, T, C) new inputs, of which row b's first
    ``length_b`` are valid; ``weight``: (C, K); ``bias``: (C,).
    ``y_t = silu(bias + sum_j weight[:, j] * in_{t-K+1+j})`` over the
    carried inputs followed by the new ones.  Returns ``(y, table)``
    with plane ``layer`` replaced whole by each lane's last K-1 VALID
    inputs (a lane with ``length`` 0 keeps what it had), so padded
    positions never enter the state.  A lane with ``step`` 0 and
    something valid starts from zeros.  ``layer`` is a static attribute."""
    bias, step, length = ((None,) if no_bias else ()) + rest
    with jax.named_scope("ssm/conv"):
        T, K = x.shape[1], weight.shape[1]
        state = _fresh(table[layer], step, length).astype(jnp.float32)
        full = jnp.concatenate([state, x.astype(jnp.float32)], axis=1)
        w = weight.astype(jnp.float32)
        y = None if no_bias else bias.astype(jnp.float32)
        for j in range(K):
            tap = full[:, j:j + T] * w[:, j]
            y = tap if y is None else y + tap
        y = jax.nn.silu(y)
        n = jnp.asarray(length).astype(jnp.int32)
        last = jax.vmap(lambda f, at: lax.dynamic_slice_in_dim(
            f, at, K - 1, axis=0))(full, n)
        table = table.at[layer].set(last.astype(table.dtype))
    return y.astype(x.dtype), table


register_op("ssm_conv", num_inputs=6, num_outputs=2, differentiable=False,
            params=[Param("layer", int, 0, lower=0),
                    Param("no_bias", bool, False)],
            doc=_ssm_conv_op.__doc__)(_ssm_conv_op)


def _ssd_chunked(x, dt, a_head, b_mat, c_mat, s0, chunk):
    """The selective scan ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
    B_t``, ``y_t = S_t C_t`` in its chunked (state-space dual) form
    (Dao & Gu 2024, sec. 6): inside a chunk of ``chunk`` positions the
    outputs are one masked matrix product, between chunks only the
    state is carried.  ``x`` (B, T, H, P), ``dt`` (B, T, H) already
    positive (0 at a padded position: no decay, no input), ``a_head``
    (H,) negative, ``b_mat``/``c_mat`` (B, T, N), ``s0`` (B, H, P, N).
    Returns ``(y (B, T, H, P), S_T)``.  What feeds the carried state
    is contracted at HIGHEST precision: the state is kept in float32
    and a default-precision product would round it to bfloat16."""
    B, T, H, P = x.shape
    N = b_mat.shape[-1]
    Q = min(int(chunk), T)
    pad = (-T) % Q
    if pad:
        grow = lambda z: jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))
        x, dt, b_mat, c_mat = grow(x), grow(dt), grow(b_mat), grow(c_mat)
    nc = (T + pad) // Q
    xc = x.reshape(B, nc, Q, H, P)
    dtc = dt.reshape(B, nc, Q, H)
    bc = b_mat.reshape(B, nc, Q, N)
    cc = c_mat.reshape(B, nc, Q, N)
    cum = jnp.cumsum(dtc * a_head, axis=2)              # (B, nc, Q, H)
    dx = dtc[..., None] * xc                            # dt_s x_s
    # inside a chunk: y_t = sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) dt_s x_s
    cb = jnp.einsum("bcqn,bcsn->bcqs", cc, bc,
                    preferred_element_type=jnp.float32)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,S,H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    w = cb[..., None] * jnp.exp(jnp.where(causal, diff, -jnp.inf))
    y = jnp.einsum("bcqsh,bcshp->bcqhp", w, dx,
                   preferred_element_type=jnp.float32)
    # what each chunk adds to the state at its own end, and its decay
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)           # (B, nc, Q, H)
    added = jnp.einsum("bcsh,bcshp,bcsn->bchpn", to_end, dx, bc,
                       precision=lax.Precision.HIGHEST)
    decay = jnp.exp(cum[:, :, -1, :])                   # (B, nc, H)

    def carry(s, chunk_in):
        add_c, dec_c = chunk_in
        return s * dec_c[..., None, None] + add_c, s

    s_end, s_in = lax.scan(carry, s0, (jnp.moveaxis(added, 1, 0),
                                       jnp.moveaxis(decay, 1, 0)))
    # the state a chunk starts from, decayed to each of its positions
    y = y + jnp.einsum("bcqn,cbhpn,bcqh->bcqhp", cc, s_in, jnp.exp(cum),
                       precision=lax.Precision.HIGHEST)
    return y.reshape(B, nc * Q, H, P)[:, :T], s_end


def _ssm_scan_op(table, x, dt, b_mat, c_mat, a_log, d_skip, dt_bias,
                 step, length, layer=0, chunk=256):
    """Mamba-2's selective state-space scan with carried state.
    ``table``: (layers, B, H, P, N) float32, lane b's state of each
    layer; ``x``: (B, T, H*P); ``dt``: (B, T, H) raw; ``b_mat``,
    ``c_mat``: (B, T, N) (one group); ``a_log``, ``d_skip``,
    ``dt_bias``: (H,).  Per head, with ``dt = softplus(dt + dt_bias)``
    and ``A = -exp(a_log)``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
    B_t``, ``y_t = S_t C_t + d_skip x_t``.  Row b's positions from
    ``length_b`` on are padding: their ``dt`` is 0, so they leave the
    state as it is; a lane with ``step`` 0 and something valid starts
    from zeros.  Returns
    ``(y (B, T, H*P), table)`` with plane ``layer`` replaced whole.
    T > 1 takes the chunked form (``_ssd_chunked``, scope
    ``ssm/scan``); T = 1 is one read and one write of the plane (scope
    ``ssm/state_update``), fenced by ``optimization_barrier`` so that
    the compiler fuses nothing of its neighbours into it and a trace
    can time it alone.  One token on a TPU that keeps a float32 table
    with ``N`` minor in whole tiles is one Pallas kernel over the lanes
    (``mxtpu.kernels.ssm_update``) that sums ``y`` from each block of
    the new state while it holds it; every other one-token update is
    XLA's: a fusion that reads and writes the plane and a second that
    reads it again for ``y``.  Which of the two is decided from what is
    observed here — ``T``, the backend, the table's dtype, shape and
    layout on the device (``_state_in_whole_tiles``) — and both compute
    the same float32 products and sums.  ``layer`` and ``chunk`` are
    static."""
    B, T, H = dt.shape
    P = x.shape[-1] // H
    f32 = jnp.float32
    if T == 1:
        x, dt, b_mat, c_mat, a_log, d_skip, dt_bias, step, length = \
            lax.optimization_barrier(
                (x, dt, b_mat, c_mat, a_log, d_skip, dt_bias, step,
                 length))
    n = jnp.asarray(length).astype(jnp.int32)
    scope = "ssm/state_update" if T == 1 else "ssm/scan"
    with jax.named_scope(scope):
        a_head = -jnp.exp(a_log.astype(f32))
        valid = jnp.arange(T, dtype=jnp.int32)[None, :] < n[:, None]
        dtp = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)) \
            * valid[..., None].astype(f32)
        by_kernel = T == 1 and _state_in_whole_tiles(table)
        if by_kernel:
            x = _held_as_rows(x)
        xh = x.astype(f32).reshape(B, T, H, P)
        if by_kernel:
            from ..kernels import ssm_update
            d1 = dtp[:, 0]
            y, table = ssm_update.ssm_update(
                table, _keeps_state(step, n), jnp.exp(d1 * a_head),
                d1[..., None] * xh[:, 0], b_mat.astype(f32)[:, 0],
                c_mat.astype(f32)[:, 0], jnp.int32(layer))
            y = y[:, None]
        else:
            s0 = _fresh(table[layer], step, n).astype(f32)
            if T == 1:
                d1, x1 = dtp[:, 0], xh[:, 0]
                s_new = s0 * jnp.exp(d1 * a_head)[..., None, None] \
                    + (d1[..., None] * x1)[..., None] \
                    * b_mat.astype(f32)[:, 0, None, None, :]
                y = jnp.sum(
                    s_new * c_mat.astype(f32)[:, 0, None, None, :],
                    axis=-1)[:, None]
            else:
                y, s_new = _ssd_chunked(xh, dtp, a_head, b_mat.astype(f32),
                                        c_mat.astype(f32), s0, chunk)
        y = y + d_skip.astype(f32)[None, None, :, None] * xh
        y = y.reshape(B, T, H * P).astype(x.dtype)
        if by_kernel:               # which wrote the plane itself
            y = _held_as_rows(y)
        else:
            table = table.at[layer].set(s_new.astype(table.dtype))
    if T == 1:
        y = lax.optimization_barrier(y)
    return y, table


def _held_as_rows(z):
    """``z`` (B, 1, C) held as the device holds a matrix (B, C), a lane
    a row: how the projections on either side of the mixer leave and
    take a decode step's activations.  The kernel's operands have a
    layout of their own ((B, H, P): a head a row), which the compiler
    would otherwise hand on to everything that feeds it and everything
    it feeds — the input projection's product, the convolution, the
    gate and the norm all a row a tile, an eighth of each tile used
    (PERF.md, PR 35)."""
    from jax.experimental.layout import with_layout_constraint
    flat = z.reshape(z.shape[0], -1)
    return with_layout_constraint(flat, _resident_layout(flat)).reshape(
        z.shape)


def _state_in_whole_tiles(table):
    """Whether the Pallas kernel may take a one-token update of the
    ``ssm`` table (layers, B, H, P, N): Pallas kernels run here, the
    state is float32, and the device keeps the table with ``N`` minor
    and ``P`` next, both filling whole (8, 128) tiles, so that a block
    of a lane's heads is the bytes as they lie."""
    from .. import kernels
    if not kernels.pallas_enabled() or table.dtype != jnp.float32:
        return False
    if table.shape[-1] % 128 or table.shape[-2] % 8:
        return False
    order = tuple(_resident_layout(table).major_to_minor)
    return order == (0, 1, 2, 3, 4)


register_op("ssm_scan", num_inputs=10, num_outputs=2,
            differentiable=False,
            params=[Param("layer", int, 0, lower=0),
                    Param("chunk", int, 256, lower=1)],
            doc=_ssm_scan_op.__doc__)(_ssm_scan_op)


# ----------------------------------------------------------------------
# gated delta-rule (Gated DeltaNet) layers: the state is a matrix a
# head, corrected at every token by a rank-one update of itself
# ----------------------------------------------------------------------
_HIGHEST = lax.Precision.HIGHEST


def _l2_normalised(x32):
    return x32 * lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + 1e-6)


def _delta_step(s0, q, k, v, g, beta):
    """One position of the gated delta rule.  ``s0`` (B, H, dk, dv);
    ``q``, ``k`` (B, H, dk); ``v`` (B, H, dv); ``g``, ``beta`` (B, H).
    ``S' = exp(g) S``; ``r = v - S'^T k``; ``S = S' + beta k r^T``;
    ``o = S^T q``: element-wise passes over the state and sums down its
    ``dk`` axis, nothing reshapes it.  Returns ``(o (B, H, dv), S)``."""
    s = s0 * jnp.exp(g)[..., None, None]
    r = v - jnp.sum(s * k[..., None], axis=2)
    s = s + k[..., None] * (beta[..., None] * r)[..., None, :]
    return jnp.sum(s * q[..., None], axis=2), s


def _unit_lower_inverse(A, mm):
    """``(I + A)^-1`` of strictly lower triangular ``A`` (..., rows,
    rows) by products alone; ``A`` is grown with zeros to a power of
    two.  The diagonal blocks of 4 rows are nilpotent, so their Neumann
    series ends: ``(I - D)(I + D^2)``.  Then pairs of blocks are joined until one is left: the inverse of
    ``[[X, 0], [Y, Z]]`` is ``[[X', 0], [-Z' Y X', Z']]``, and every
    block on the way is a block of the answer, so nothing grows past it
    (the whole series over 64 rows overflows where the keys of a chunk
    are alike).  On the keys a model makes it agrees with forward
    substitution to the last digits; on a chunk of keys within a
    hundredth of each other with ``beta`` 1.9 it is 3e-5 off where
    forward substitution is 3e-6 (8-row blocks: 2e-4), far under the
    bfloat16 products it feeds.  The chip runs it in six tenths of the
    time of its ``triangular_solve`` call (PERF.md sec. 6, PR 32)."""
    rows, lead = A.shape[-1], A.shape[:-2]
    C = 1 << (rows - 1).bit_length()
    A = jnp.pad(A, [(0, 0)] * len(lead) + [(0, C - rows)] * 2)
    h = min(4, C)

    def blocks(below):
        """Down the diagonal, the h x h blocks on it, or (``below``)
        the one under the first of each pair: plain slices, side by
        side (an indexed gather here crashed the chip's compiler)."""
        step = 2 * h if below else h
        return jnp.stack([A[..., i + below * h:i + below * h + h, i:i + h]
                          for i in range(0, C, step)], axis=-3)

    eye = jnp.eye(h, dtype=A.dtype)
    D = blocks(0)
    T = mm("...ij,...jk->...ik", eye - D,
           eye + mm("...ij,...jk->...ik", D, D))
    while h < C:
        pairs = T.reshape(lead + (C // (2 * h), 2, h, h))
        X, Z = pairs[..., 0, :, :], pairs[..., 1, :, :]
        low = -mm("...ij,...jk->...ik", Z,
                  mm("...ij,...jk->...ik", blocks(1), X))
        T = jnp.concatenate(
            [jnp.concatenate([X, jnp.zeros_like(X)], axis=-1),
             jnp.concatenate([low, Z], axis=-1)], axis=-2)
        h *= 2
    return T[..., 0, :rows, :rows]


def _delta_chunked(q, k, v, g, beta, s0, chunk):
    """The gated delta rule over many positions in its chunked (WY)
    form (Yang, Kautz & Hatamizadeh 2024, arXiv:2412.06464, sec. 3).
    ``q``, ``k`` (B, T, H, dk), ``v`` (B, T, H, dv), ``g`` <= 0 and
    ``beta`` (B, T, H) (both 0 at a padded position: no decay, no
    correction), ``s0`` (B, H, dk, dv).  With ``G`` the running sum of
    ``g`` inside a chunk and ``d_t = beta_t r_t`` the correction each
    position writes, the recurrence unrolls to ``S_t = e^{G_t} S_0 +
    sum_{i<=t} e^{G_t-G_i} k_i d_i^T``, and the ``d`` of a chunk solve
    the unit-lower-triangular system ``(I + A) D = beta V - (beta
    e^{G} K) S_0`` with ``A_ti = beta_t e^{G_t-G_i} (k_t.k_i)``, i < t.
    So per chunk and head: ``U = (I+A)^-1 beta V`` and ``W = (I+A)^-1
    beta e^G K`` for all chunks at once (the inverse by products:
    ``_unit_lower_inverse``), then from chunk to chunk only
    ``D = U - W S``, ``O = e^G (Q S) + (M * Q K^T) D`` and ``S' =
    e^{G_C} S + (e^{G_C-G} K)^T D`` are carried.  Every ratio of decays
    is ``exp`` of a difference of ``G``, never a quotient of products
    (which underflows inside a chunk).  All contractions at HIGHEST:
    they feed the float32 state.  Returns ``(o (B, T, H, dv), S_T)``."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    C = min(int(chunk), T)
    pad = (-T) % C
    if pad:
        grow = lambda z: jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
    nc = (T + pad) // C
    # (B, nc*C, H, ...) -> (nc, B, H, C, ...)
    cut = lambda z: jnp.moveaxis(
        z.reshape((B, nc, C) + z.shape[2:]), (1, 3), (0, 2))
    q, k, v, g, beta = cut(q), cut(k), cut(v), cut(g), cut(beta)
    G = jnp.cumsum(g, axis=-1)                          # (nc, B, H, C)
    diff = G[..., :, None] - G[..., None, :]            # G_t - G_i
    lower = jnp.tril(jnp.ones((C, C), bool), -1)
    upto = jnp.tril(jnp.ones((C, C), bool))
    mm = lambda spec, a, b: jnp.einsum(spec, a, b, precision=_HIGHEST)
    A = beta[..., :, None] * jnp.exp(jnp.where(lower, diff, -jnp.inf)) \
        * mm("...td,...id->...ti", k, k)
    rhs = jnp.concatenate([beta[..., None] * v,
                           (beta * jnp.exp(G))[..., None] * k], axis=-1)
    uw = mm("...ti,...iv->...tv", _unit_lower_inverse(A, mm), rhs)
    qk = mm("...td,...id->...ti", q, k) \
        * jnp.exp(jnp.where(upto, diff, -jnp.inf))
    k_end = k * jnp.exp(G[..., -1:] - G)[..., None]
    into, at_end = jnp.exp(G), jnp.exp(G[..., -1])

    def carry(s, c):
        uw_c, q_c, qk_c, k_end_c, into_c, at_end_c = c
        d = uw_c[..., :dv] - mm("bhck,bhkv->bhcv", uw_c[..., dv:], s)
        o = into_c[..., None] * mm("bhck,bhkv->bhcv", q_c, s) \
            + mm("bhti,bhiv->bhtv", qk_c, d)
        s = at_end_c[..., None, None] * s + mm("bhck,bhcv->bhkv", k_end_c, d)
        return s, o

    s_end, o = lax.scan(carry, s0, (uw, q, qk, k_end, into, at_end))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, nc * C, H, dv)
    return o[:, :T], s_end


def _delta_rule_op(table, q, k, v, g, beta, step, length, layer=0,
                   chunk=64):
    """The gated delta rule with carried state (Gated DeltaNet).
    ``table``: (layers, B, H, dk, dv) float32, lane b's state of each
    layer, a (dk, dv) matrix a head; ``q``, ``k``: (B, T, H*dk) and
    ``v``: (B, T, H*dv), after their convolution; ``g``: (B, T, H) the
    log of the decay, <= 0;
    ``beta``: (B, T, H) the correction's strength.  Per head ``q`` and
    ``k`` are brought to unit length (1e-6 under the root) and ``q``
    scaled by ``dk^-1/2``; then ``S' = exp(g_t) S_{t-1}``, ``r_t = v_t
    - S'^T k_t``, ``S_t = S' + beta_t k_t r_t^T``, ``o_t = S_t^T q_t``.
    Row b's positions from ``length_b`` on are padding: their ``g`` and
    ``beta`` are 0, so they leave the state as it is; a lane with
    ``step`` 0 and something valid starts from zeros.  Returns ``(o (B,
    T, H*dv), table)`` with plane ``layer`` replaced whole.  T > 1
    takes the chunked form (``_delta_chunked``, scope ``delta/chunk``);
    T = 1 is one read and one write of the plane (scope
    ``delta/state_update``), fenced as ``ssm_scan``'s is so that a
    trace can time it alone.  ``layer`` and ``chunk`` are static."""
    B, T, H = g.shape
    dk, dv = k.shape[-1] // H, v.shape[-1] // H
    f32 = jnp.float32
    if T == 1:
        q, k, v, g, beta, step, length = lax.optimization_barrier(
            (q, k, v, g, beta, step, length))
    n = jnp.asarray(length).astype(jnp.int32)
    with jax.named_scope("delta/state_update" if T == 1
                         else "delta/chunk"):
        valid = (jnp.arange(T, dtype=jnp.int32)[None, :]
                 < n[:, None])[..., None].astype(f32)
        g32, b32 = g.astype(f32) * valid, beta.astype(f32) * valid
        qh = _l2_normalised(q.astype(f32).reshape(B, T, H, dk)) \
            * (1.0 / float(np.sqrt(dk)))
        kh = _l2_normalised(k.astype(f32).reshape(B, T, H, dk))
        s0 = _fresh(table[layer], step, n).astype(f32)
        vh = v.astype(f32).reshape(B, T, H, dv)
        if T == 1:
            o, s_new = _delta_step(s0, qh[:, 0], kh[:, 0], vh[:, 0],
                                   g32[:, 0], b32[:, 0])
        else:
            o, s_new = _delta_chunked(qh, kh, vh, g32, b32, s0, chunk)
        o = o.reshape(B, T, H * dv)
        o = o.astype(v.dtype)
        table = table.at[layer].set(s_new.astype(table.dtype))
    if T == 1:
        o = lax.optimization_barrier(o)
    return o, table


register_op("delta_rule", num_inputs=8, num_outputs=2,
            differentiable=False,
            params=[Param("layer", int, 0, lower=0),
                    Param("chunk", int, 64, lower=1)],
            doc=_delta_rule_op.__doc__)(_delta_rule_op)


def _flash_attention_op(q, k, v, causal=False, sm_scale=-1.0):
    """Fused attention op (new capability; no reference counterpart —
    SURVEY.md §5.7 mandates it for long-context).  q: (B,H,Tq,D),
    k/v: (B,H,Tk,D); sm_scale < 0 means 1/sqrt(D)."""
    from ..kernels import flash_attention
    scale = None if sm_scale is None or sm_scale < 0 else sm_scale
    return flash_attention(q, k, v, causal=causal, sm_scale=scale)


register_op("flash_attention", num_inputs=3,
            params=[Param("causal", bool, False),
                    Param("sm_scale", float, -1.0)],
            aliases=("contrib_flash_attention",),
            doc=_flash_attention_op.__doc__)(_flash_attention_op)
