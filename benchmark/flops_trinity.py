"""Operations and bytes of an ``afmoe`` configuration's share, from shapes
alone.

The yardstick for the ``ep_*`` metrics and ``serve_step_mfu`` of its
cell.  Counts what the mathematics of THIS device's share needs, not
what a compiler emitted or one implementation happens to move: a
multiply-add is two operations; norms, rotations, the gate's sigmoid,
activations, the router's scores and the sort of a dispatch are not
counted; a token's routed work is its assignments to the experts this
device holds (``moe_assignments_held``, counted by the program; where a
count is not at hand, the expected ``top_k x held / routed`` of them),
beside the shared expert; a sliding layer's query reads ``min(p,
sliding_window)`` positions, a full layer's ``p``; a held expert's
weights are read once by a call that gives it a token and not at all by
one that does not; a table's bytes are those of its elements.  Nothing
here imports the program or JAX.
"""
from .flops import roofline_seconds
from .weights_trinity import leaf_shapes, sizes


def layer_counts(cfg):
    """(full-attention layers, sliding-attention layers, dense layers,
    routed layers)."""
    kinds = cfg["layer_types"]
    dense = min(sizes(cfg)["dense_layers"], len(kinds))
    return (kinds.count("full_attention"), kinds.count("sliding_attention"),
            dense, len(kinds) - dense)


def attention_params(cfg):
    """One layer's five projections: q, k, v, the gate and o."""
    s = sizes(cfg)
    return 3 * s["d"] * s["hq"] * s["dh"] + 2 * s["d"] * s["hk"] * s["dh"]


def expert_params(cfg):
    """ONE routed expert: gate, up and down."""
    s = sizes(cfg)
    return 3 * s["d"] * s["f"]


def token_params(cfg, head=True):
    """Weights in a matrix product for ONE token whatever it routes to:
    every layer's projections, the dense layers' MLPs, the routed
    layers' routers and shared experts, and the head (once a prompt or
    a decode step: ``head=False`` leaves it out)."""
    s = sizes(cfg)
    _, _, n_dense, n_routed = layer_counts(cfg)
    return len(cfg["layer_types"]) * attention_params(cfg) \
        + n_dense * 3 * s["d"] * s["dense"] \
        + n_routed * (s["d"] * s["e"] + 3 * s["d"] * s["shared"]) \
        + (s["d"] * s["v"] if head else 0)


def expected_held(cfg):
    """Assignments to held experts a token makes over the routed layers,
    on the mean (an even spread over the experts routed over)."""
    s = sizes(cfg)
    return layer_counts(cfg)[3] * s["k"] * s["held"] / s["e"]


def param_count(cfg):
    """Every parameter this device holds, as ``weights_trinity``
    lays them out (embedding and head both, the held experts)."""
    total = 0
    for shape in leaf_shapes(cfg).values():
        n = 1
        for dim in shape:
            n *= dim
        total += n
    return total


def attended(cfg, position):
    """Keys the query at 0-based ``position`` reads, over all layers."""
    n_full, n_win, _, _ = layer_counts(cfg)
    return n_full * (position + 1) \
        + n_win * min(position + 1, sizes(cfg)["window"])


def prompt_flops(cfg, length):
    """Forward operations of ONE prompt of ``length`` tokens on this
    device: every token through its layers, each query against the
    keys it reads, the head once (the last position's)."""
    s = sizes(cfg)
    n_full, n_win, _, _ = layer_counts(cfg)
    w = min(length, s["window"])
    full = length * (length + 1) // 2
    win = w * (w + 1) // 2 + (length - w) * w
    return 2.0 * length * (token_params(cfg, head=False)
                           + expected_held(cfg) * expert_params(cfg)) \
        + 2.0 * s["d"] * s["v"] \
        + 4.0 * s["hq"] * s["dh"] * (n_full * full + n_win * win)


def decode_flops_per_token(cfg, context):
    """Forward operations to produce one token against ``context``
    cached positions (the new one among them)."""
    s = sizes(cfg)
    return 2.0 * (token_params(cfg)
                  + expected_held(cfg) * expert_params(cfg)) \
        + 4.0 * s["hq"] * s["dh"] * attended(cfg, context - 1)


def kv_bytes_per_position(cfg, bytes_per_el=2):
    """Keys and values of one position in ONE layer."""
    s = sizes(cfg)
    return 2 * s["hk"] * s["dh"] * bytes_per_el


def expert_bytes(cfg, bytes_per_el=2):
    return expert_params(cfg) * bytes_per_el


def dense_weight_bytes(cfg, bytes_per_el=2):
    """What every step reads whatever it routes: the layers'
    projections, norms, dense MLPs, routers, biases and shared experts,
    the final norm and the head; of the embedding only the rows looked
    up, which are not counted."""
    s = sizes(cfg)
    held = layer_counts(cfg)[3] * s["held"] * expert_params(cfg)
    return (param_count(cfg) - s["v"] * s["d"] - held) * bytes_per_el


def decode_step_bytes(cfg, full_positions, window_positions,
                      experts_touched, weight_bytes_per_el=2,
                      kv_bytes_per_el=2):
    """Bytes one decode step has to move: the dense weights once, each
    TOUCHED held expert's weights once (``experts_touched``, summed over
    the layers), and the keys and values the lanes read: ``full_
    positions`` in each full layer (every lane's whole context) and
    ``window_positions`` in each sliding one (``min(p, window)`` a
    lane)."""
    n_full, n_win, _, _ = layer_counts(cfg)
    return dense_weight_bytes(cfg, weight_bytes_per_el) \
        + experts_touched * expert_bytes(cfg, weight_bytes_per_el) \
        + (n_full * full_positions + n_win * window_positions) \
        * kv_bytes_per_position(cfg, kv_bytes_per_el)


def decode_step_flops(cfg, active, held_assignments, full_positions,
                      window_positions):
    """Operations of one decode step: a token for each of the ``active``
    lanes through its layers and the head, the ``held_assignments``
    (summed over the layers) through their experts, each query against
    the keys it reads."""
    s = sizes(cfg)
    n_full, n_win, _, _ = layer_counts(cfg)
    return 2.0 * active * token_params(cfg) \
        + 2.0 * held_assignments * expert_params(cfg) \
        + 4.0 * s["hq"] * s["dh"] * (n_full * full_positions
                                     + n_win * window_positions)


def experts_roofline_seconds(cfg, held_assignments, experts_touched,
                             peaks, bytes_per_el=2):
    """The least time of a call's routed expert products over all
    layers: ``held_assignments`` (token, held expert) pairs through
    gate, up and down, and ``experts_touched`` held experts' weights
    read once, both summed over the layers."""
    return roofline_seconds(
        2.0 * held_assignments * expert_params(cfg),
        experts_touched * expert_bytes(cfg, bytes_per_el),
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])[0]
