"""One-token write into the KV slot table: a masked column store.

The chip keeps the table ``(layers, 2, slots, heads, L, head_dim)``
with ``L`` minor, in (8, 128) tiles over ``(head_dim, L)``, so the
position a decode step writes for one lane is a *column*: one element
in each of ``heads * head_dim`` rows.  XLA's ``dynamic_update_slice``
stores such a column element by element.  This kernel sees the table
with its last two axes swapped — the same bytes under that layout —
loads, for each lane, the 128 positions that hold the lane's frontier
(all of head_dim, as many heads as fit the block), replaces the one
column by a lane mask and stores the block back.  The table in is
aliased to the table out: blocks the grid never visits are never
touched, and the donated buffer is updated where it lies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._sites import CallSites

_LANES = 128
# one block of the table in VMEM; the pipeline holds four (in and out,
# double-buffered), well inside the 16 MB a kernel may use on a v5e
_BLOCK_BYTES = 1 << 20

# Declared numerics contract (see flash_attention.PRECISION).
PRECISION = {
    "accum_dtype": "none",
    "safe_input_dtypes": ["bf16", "f32"],
    "note": "rounds nothing: the new row arrives already cast to the "
            "table's dtype and is placed by transpose and select, "
            "never through the matrix unit; the stored bits equal "
            "dynamic_update_slice's",
}

# Operand-layout contract (see batch_norm.LAYOUT).
LAYOUT = {
    "native": {
        "view": "(layers, 2, slots, heads, head_dim, L): one block of "
                "(heads, head_dim, 128 positions) per lane, positions "
                "on lanes",
        "binds": "the table's resident layout, L minor in (8, 128) "
                 "tiles over (head_dim, L): the swap of the last two "
                 "axes round the call is a bitcast, and the table is "
                 "aliased to the result",
    },
    "dispatch": "one-token writes where the device keeps the table "
                "with L minor (rnn_impl._capacity_is_minor); every "
                "other write keeps the lanes' loop",
}

# ``with call_sites() as traced``: the kernel call sites traced inside
# the block, what a program that was lowered there holds of this kernel
call_sites = CallSites()


def _kernel(lp_ref, idx_ref, new_ref, table_ref, out_ref):
    del lp_ref  # the index maps read it
    col = idx_ref[pl.program_id(0)] % table_ref.shape[-1]
    # head_dim arrives along the lanes; the table wants it along the
    # sublanes
    turned = new_ref[...].T                       # (D, heads)
    plane = table_ref.shape[1:]                   # (D, positions)
    here = lax.broadcasted_iota(jnp.int32, plane, 1) == col
    for h in range(table_ref.shape[0]):
        value = jnp.broadcast_to(turned[:, h:h + 1], plane)
        out_ref[h] = jnp.where(here, value, table_ref[h])


def _heads_per_block(heads, row_bytes, itemsize):
    """The most heads whose block stays under ``_BLOCK_BYTES``: all of
    them, or a divisor that fills whole sublane tiles of the new row
    (8 rows of 32 bits)."""
    whole_tile = 8 * 4 // itemsize
    fits = [h for h in range(heads, 0, -1)
            if heads % h == 0 and (h == heads or h % whole_tile == 0)]
    return next((h for h in fits if h * row_bytes <= _BLOCK_BYTES),
                fits[-1])


def kv_write(table, new, idx, layer, plane):
    """``table`` (layers, 2, B, H, L, D) with
    ``[layer, plane, b, :, idx[b], :]`` replaced by ``new[b, :, 0, :]``
    for every lane b in turn; ``new`` (B, H, 1, D) of the table's
    dtype, ``idx`` (B,) int32 within ``[0, L)``, ``layer`` and
    ``plane`` int32 scalars — values that ride the scalar prefetch, so
    that one traced kernel serves every call site of a program, and an
    eager call is one program whose swaps are bitcasts."""
    from . import interpret_mode
    call_sites.note()
    return _column_store(table, new, idx, layer, plane,
                         interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames="interpret")
def _column_store(table, new, idx, layer, plane, *, interpret):
    n_layers, _, B, H, L, D = table.shape
    width = min(_LANES, L)
    hb = _heads_per_block(H, D * width * table.dtype.itemsize,
                          table.dtype.itemsize)
    block = pl.BlockSpec(
        (None, None, None, hb, D, width),
        lambda b, h, lp, idx: (lp[0], lp[1], b, h, 0, idx[b] // width))
    out = pl.pallas_call(
        _kernel,
        name="kv_cache_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb),
            in_specs=[pl.BlockSpec((None, hb, D),
                                   lambda b, h, lp, idx: (b, h, 0)),
                      block],
            out_specs=block,
        ),
        out_shape=jax.ShapeDtypeStruct((n_layers, 2, B, H, D, L),
                                       table.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(jnp.stack([layer, plane]), idx, new.reshape(B, H, D),
      jnp.swapaxes(table, -1, -2))
    return jnp.swapaxes(out, -1, -2)
