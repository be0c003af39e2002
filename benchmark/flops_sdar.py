"""Operations and bytes of an ``sdar_moe`` configuration, from shapes
alone.

The yardstick for the ``*_mfu`` and ``*_roofline`` metrics of its cells.
Counts what the mathematics needs, not what a compiler emitted or one
implementation happens to move: a multiply-add is two operations; norms,
rotations, activations, the softmaxes and the sort of a dispatch are not
counted; a position's feed-forward is its ``num_experts_per_tok``
experts and no other; a position reads the keys of every earlier block
and of its own whole block (the block-causal mask); an expert's weights
are read once by a call that gives it a position and not at all by one
that does not; a table's bytes are those of its elements.  A pass counts
every position it feeds: the clean block a folded pass stores and the
block it denoises, however many passes that block takes.  Nothing here
imports the program or JAX.
"""
from .flops import roofline_seconds  # noqa: F401 — the readers' one way in
from .weights_sdar import leaf_shapes, sizes


def attention_params(cfg):
    """One layer's four projections."""
    s = sizes(cfg)
    return 2 * s["d"] * s["hq"] * s["dh"] + 2 * s["d"] * s["hk"] * s["dh"]


def expert_params(cfg):
    """ONE expert: gate, up and down."""
    s = sizes(cfg)
    return 3 * s["d"] * s["f"]


def layer_gemm_params(cfg):
    """Weights in a matrix product for ONE position, over all layers:
    projections, router and the ``k`` experts it takes."""
    s = sizes(cfg)
    return s["layers"] * (attention_params(cfg) + s["d"] * s["e"]
                          + s["k"] * expert_params(cfg))


def param_count(cfg):
    """Every parameter, as ``weights_sdar.leaf_shapes`` lays them out."""
    total = 0
    for shape in leaf_shapes(cfg).values():
        n = 1
        for dim in shape:
            n *= dim
        total += n
    return total


def attended(cfg, start, length):
    """Keys the ``length`` positions fed from ``start`` (a block's start,
    whole blocks) read in ONE layer: each position its block's end."""
    b = sizes(cfg)["b"]
    return sum((p // b + 1) * b for p in range(start, start + length))


def row_flops(cfg, start, length):
    """Forward operations of one row of a call: ``length`` positions fed
    from ``start`` through every layer, each query against the keys it
    reads, and the head over the row's last block."""
    s = sizes(cfg)
    return 2.0 * length * layer_gemm_params(cfg) \
        + 2.0 * s["b"] * s["d"] * s["v"] \
        + 4.0 * s["hq"] * s["dh"] * s["layers"] * attended(cfg, start, length)


def step_flops(cfg, rows, positions, keys):
    """Operations of one pass: ``positions`` fed over ``rows`` active
    rows, which read ``keys`` keys a layer between them (the
    ``attended`` count of the ``gen/decode`` region)."""
    s = sizes(cfg)
    return 2.0 * positions * layer_gemm_params(cfg) \
        + 2.0 * rows * s["b"] * s["d"] * s["v"] \
        + 4.0 * s["hq"] * s["dh"] * s["layers"] * keys


def kv_bytes_per_position(cfg, bytes_per_el=2):
    """Keys and values of one position in ONE layer."""
    s = sizes(cfg)
    return 2 * s["hk"] * s["dh"] * bytes_per_el


def expert_bytes(cfg, bytes_per_el=2):
    return expert_params(cfg) * bytes_per_el


def dense_weight_bytes(cfg, bytes_per_el=2):
    """What every pass reads whatever it routes: the layers'
    projections, routers and norms, the final norm and the head; of the
    embedding only the rows looked up, which are not counted."""
    s = sizes(cfg)
    experts = s["layers"] * s["e"] * expert_params(cfg)
    return (param_count(cfg) - s["v"] * s["d"] - experts) * bytes_per_el


def step_bytes(cfg, context_tokens, positions, experts_touched,
               weight_bytes_per_el=2, kv_bytes_per_el=2):
    """Bytes one pass has to move: the dense weights once, each TOUCHED
    expert's weights once (``experts_touched``: summed over the layers),
    and each active lane's keys and values up to its block's end
    (``context_tokens``: the positions before the rows, ``positions``:
    those the rows feed), in every layer."""
    return dense_weight_bytes(cfg, weight_bytes_per_el) \
        + experts_touched * expert_bytes(cfg, weight_bytes_per_el) \
        + sizes(cfg)["layers"] * (context_tokens + positions) \
        * kv_bytes_per_position(cfg, kv_bytes_per_el)
