"""Operations and bytes of a ``mellum`` configuration, from shapes alone.

The yardstick for the ``*_mfu`` and ``*_roofline`` metrics of its cells.
Counts what the mathematics needs, not what a compiler emitted or one
implementation happens to move: a multiply-add is two operations; norms,
rotations, activations, the router's softmax and the sort of a dispatch
are not counted; a token's feed-forward is its ``num_experts_per_tok``
experts and no other; a sliding layer's query reads ``min(p,
sliding_window)`` positions, a full layer's ``p``; an expert's weights
are read once by a step that gives it a token and not at all by one that
does not; a table's bytes are those of its elements.  Nothing here
imports the program or JAX.
"""
from .flops import roofline_seconds  # noqa: F401 — the readers' one way in
from .weights_mellum2 import leaf_shapes, sizes


def layer_counts(cfg):
    """(full-attention layers, sliding-attention layers)."""
    kinds = cfg["layer_types"]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


def attention_params(cfg):
    """One layer's four projections."""
    s = sizes(cfg)
    return 2 * s["d"] * s["hq"] * s["dh"] + 2 * s["d"] * s["hk"] * s["dh"]


def expert_params(cfg):
    """ONE expert: gate, up and down."""
    s = sizes(cfg)
    return 3 * s["d"] * s["f"]


def active_gemm_params(cfg, head=True):
    """Weights in a matrix product for ONE token: each layer's
    projections, router and the ``k`` experts the token takes, and the
    head (once a prompt or a decode step: ``head=False`` leaves it
    out).  The embedding lookup is a gather, not a product."""
    s = sizes(cfg)
    per_layer = attention_params(cfg) + s["d"] * s["e"] \
        + s["k"] * expert_params(cfg)
    return len(cfg["layer_types"]) * per_layer \
        + (s["d"] * s["v"] if head else 0)


def param_count(cfg):
    """Every parameter, as ``weights_mellum2.leaf_shapes`` lays them
    out (embedding and head both, all experts)."""
    total = 0
    for shape in leaf_shapes(cfg).values():
        n = 1
        for dim in shape:
            n *= dim
        total += n
    return total


def attended(cfg, position):
    """Keys the query at 0-based ``position`` reads, over all layers:
    itself and what is before it, a window's worth in a sliding layer."""
    n_full, n_win = layer_counts(cfg)
    return n_full * (position + 1) \
        + n_win * min(position + 1, sizes(cfg)["window"])


def prompt_flops(cfg, length):
    """Forward operations of ONE prompt of ``length`` tokens: every
    token through its layers, each query against the keys it reads,
    the head once (the last position's)."""
    s = sizes(cfg)
    n_full, n_win = layer_counts(cfg)
    w = min(length, s["window"])
    full = length * (length + 1) // 2
    # 1 + 2 + ... + w, then w for each later position
    win = w * (w + 1) // 2 + (length - w) * w
    return 2.0 * length * active_gemm_params(cfg, head=False) \
        + 2.0 * s["d"] * s["v"] \
        + 4.0 * s["hq"] * s["dh"] * (n_full * full + n_win * win)


def decode_flops_per_token(cfg, context):
    """Forward operations to produce one token against ``context``
    cached positions (the new one among them)."""
    s = sizes(cfg)
    return 2.0 * active_gemm_params(cfg) \
        + 4.0 * s["hq"] * s["dh"] * attended(cfg, context - 1)


def kv_bytes_per_position(cfg, bytes_per_el=2):
    """Keys and values of one position in ONE layer."""
    s = sizes(cfg)
    return 2 * s["hk"] * s["dh"] * bytes_per_el


def expert_bytes(cfg, bytes_per_el=2):
    return expert_params(cfg) * bytes_per_el


def dense_weight_bytes(cfg, bytes_per_el=2):
    """What every step reads whatever it routes: the layers'
    projections, routers and norms, the final norm and the head; of the
    embedding only the rows looked up, which are not counted."""
    s = sizes(cfg)
    experts = len(cfg["layer_types"]) * s["e"] * expert_params(cfg)
    return (param_count(cfg) - s["v"] * s["d"] - experts) * bytes_per_el


def context_positions(cfg, active, context_tokens):
    """(full, sliding): positions a decode step reads in one layer of
    each kind, for ``active`` lanes that hold ``context_tokens``
    positions between them: every lane's whole context in a full layer,
    ``min(p, sliding_window)`` of it in a sliding one.  The sum of the
    minima is taken as ``min(context_tokens, active x window)``, which
    it equals wherever every lane is past the window (this
    configuration's cell: no prompt is shorter than it) or none is."""
    return context_tokens, min(context_tokens,
                               active * sizes(cfg)["window"])


def decode_step_bytes(cfg, active, context_tokens, experts_touched,
                      weight_bytes_per_el=2, kv_bytes_per_el=2):
    """Bytes one decode step has to move: the dense weights once, each
    TOUCHED expert's weights once (``experts_touched``: summed over the
    layers), and the keys and values the ``active`` lanes read."""
    n_full, n_win = layer_counts(cfg)
    full, win = context_positions(cfg, active, context_tokens)
    return dense_weight_bytes(cfg, weight_bytes_per_el) \
        + experts_touched * expert_bytes(cfg, weight_bytes_per_el) \
        + (n_full * full + n_win * win) \
        * kv_bytes_per_position(cfg, kv_bytes_per_el)


def decode_step_flops(cfg, active, context_tokens):
    """Operations of one decode step: a token for each of the ``active``
    lanes, each query against the keys it reads."""
    s = sizes(cfg)
    n_full, n_win = layer_counts(cfg)
    full, win = context_positions(cfg, active, context_tokens)
    return 2.0 * active * active_gemm_params(cfg) \
        + 4.0 * s["hq"] * s["dh"] * (n_full * full + n_win * win)


def routed_flops(cfg, assignments):
    """Operations of the experts' products in ONE layer for
    ``assignments`` (token, expert) pairs: gate, up and down."""
    return 2.0 * assignments * expert_params(cfg)


def experts_roofline_seconds(cfg, assignments, experts_touched, peaks,
                             bytes_per_el=2):
    """The least time of a call's expert products over all layers:
    ``assignments`` pairs in each layer (a call's valid tokens x k),
    ``experts_touched`` experts' weights read once (summed over the
    layers)."""
    return roofline_seconds(
        len(cfg["layer_types"]) * routed_flops(cfg, assignments),
        experts_touched * expert_bytes(cfg, bytes_per_el),
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])[0]


def window_attention_bytes(cfg, active, context_tokens, bytes_per_el=2):
    """Keys and values a decode step's sliding layers have to read."""
    _, n_win = layer_counts(cfg)
    _, win = context_positions(cfg, active, context_tokens)
    return n_win * win * kv_bytes_per_position(cfg, bytes_per_el)


def lane_bytes(cfg, kv_capacity, ring, bytes_per_el=2):
    """One lane of both tables: what a prefill row gathers."""
    n_full, n_win = layer_counts(cfg)
    return (n_full * kv_capacity + n_win * ring) \
        * kv_bytes_per_position(cfg, bytes_per_el)
