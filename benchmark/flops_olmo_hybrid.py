"""Operations and bytes of an ``olmo_hybrid`` configuration, from shapes
alone.

The yardstick for the ``*_mfu`` and ``*_roofline`` metrics of its cells.
Counts what the algorithm needs, not what a compiler emitted: a
multiply-add is two operations; norms, activations, gates and other
element-wise work are not counted; a table's bytes are those of its
elements, not of the tiles a device pads them to.  Nothing here imports
the program or JAX.

The delta rule, per ``linear_attention`` layer and token, over the
``heads x dk x dv`` elements of the state: the decay (1), the read
``S'^T k`` (a multiply-add: 2), the rank-one correction ``+ beta k
r^T`` (2) and the read-out ``S^T q`` (2) — 7 operations an element; the
depthwise convolution is ``2 x kernel`` a channel.  The chunked form a
program may use for many positions at once is counted on its own
(``chunk_flops``): per chunk of C positions and head the products ``K
K^T`` and ``Q K^T`` (2 C^2 dk each), the unit-lower-triangular solve for
``dv + dk`` right-hand sides (C^2 each), ``W S`` and ``Q S`` (2 C dk dv
each), ``(M * Q K^T) D`` (2 C^2 dv) and ``K^T D`` into the state (2 C dk
dv).
"""
from .flops import roofline_seconds  # noqa: F401 — the readers' one way in
from .weights_olmo_hybrid import leaf_shapes, sizes


def layer_counts(cfg):
    """(full-attention layers, linear-attention layers)."""
    kinds = cfg["layer_types"]
    return kinds.count("full_attention"), kinds.count("linear_attention")


def gemm_params(cfg):
    """Weights that sit in a matrix product on every token: each layer's
    projections and MLP, and the head.  The embedding lookup is a
    gather, not a product."""
    s = sizes(cfg)
    d, f, h = s["d"], s["f"], s["heads"]
    n_full, n_lin = layer_counts(cfg)
    mlp = 3 * d * f
    full = 4 * d * d
    lin = d * (2 * h * s["dk"] + 2 * h * s["dv"] + 2 * h) + h * s["dv"] * d
    return n_full * (full + mlp) + n_lin * (lin + mlp) + d * s["v"]


def param_count(cfg):
    """Every parameter, as ``weights_olmo_hybrid.leaf_shapes`` lays
    them out (embedding and head both)."""
    total = 0
    for shape in leaf_shapes(cfg).values():
        n = 1
        for dim in shape:
            n *= dim
        total += n
    return total


def state_elements(cfg):
    """Elements of ONE lane's delta-rule state in ONE layer."""
    s = sizes(cfg)
    return s["heads"] * s["dk"] * s["dv"]


def scan_flops_per_token(cfg):
    """The recurrence and the convolution of all linear-attention
    layers, for one token."""
    s = sizes(cfg)
    _, n_lin = layer_counts(cfg)
    return n_lin * (7.0 * state_elements(cfg)
                    + 2.0 * s["k"] * s["channels"])


def attention_flops(cfg, q_len, kv_len, causal=False):
    """Forward operations of ONE sequence's attention cores over the
    full-attention layers: QK^T and PV, 2 * q_len * kv_len * hidden
    each.  A causal square counts the half a causal kernel has to
    compute."""
    s = sizes(cfg)
    n_full, _ = layer_counts(cfg)
    ops = 4.0 * q_len * kv_len * s["hq"] * s["dh"]
    if causal and q_len == kv_len:
        ops *= 0.5 * (1.0 + 1.0 / q_len)
    return n_full * ops


def forward_flops_per_token(cfg, seq, causal=True):
    """Forward operations per token of a full sequence of ``seq``."""
    return 2.0 * gemm_params(cfg) + scan_flops_per_token(cfg) \
        + attention_flops(cfg, seq, seq, causal) / seq


def decode_flops_per_token(cfg, context):
    """Forward operations to produce one token against ``context``
    cached positions."""
    return 2.0 * gemm_params(cfg) + scan_flops_per_token(cfg) \
        + attention_flops(cfg, 1, context)


def weight_bytes(cfg, bytes_per_el=2):
    """Bytes a step reads of the weights: every product's matrix once
    and the small leaves; of the embedding only the rows looked up,
    which are not counted."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (param_count(cfg) - v * d) * bytes_per_el


def state_bytes_per_lane(cfg, delta_bytes_per_el=4, conv_bytes_per_el=4):
    """One lane's recurrent state over all linear-attention layers: the
    delta-rule state and the convolution's last ``kernel - 1`` inputs."""
    s = sizes(cfg)
    _, n_lin = layer_counts(cfg)
    return n_lin * (state_elements(cfg) * delta_bytes_per_el
                    + (s["k"] - 1) * s["channels"] * conv_bytes_per_el)


def kv_bytes_per_token(cfg, bytes_per_el=2):
    """Keys and values one cached position holds over the full-attention
    layers."""
    s = sizes(cfg)
    n_full, _ = layer_counts(cfg)
    return n_full * 2 * s["hq"] * s["dh"] * bytes_per_el


def lane_bytes(cfg, kv_capacity, kv_bytes_per_el=2):
    """One lane of every table: what a prefill row gathers."""
    return kv_capacity * kv_bytes_per_token(cfg, kv_bytes_per_el) \
        + state_bytes_per_lane(cfg)


def decode_step_bytes(cfg, active, context_tokens, weight_bytes_per_el=2,
                      kv_bytes_per_el=2):
    """Bytes one decode step has to move: the weights once, the
    recurrent state of the ``active`` lanes read AND written, and the
    keys and values of the ``context_tokens`` positions those lanes hold
    between them."""
    return weight_bytes(cfg, weight_bytes_per_el) \
        + 2 * active * state_bytes_per_lane(cfg) \
        + context_tokens * kv_bytes_per_token(cfg, kv_bytes_per_el)


def decode_step_flops(cfg, active, context_tokens):
    """Operations of one decode step: a token for each of the ``active``
    lanes, attention over the positions they hold between them."""
    s = sizes(cfg)
    n_full, _ = layer_counts(cfg)
    return active * (2.0 * gemm_params(cfg) + scan_flops_per_token(cfg)) \
        + n_full * 4.0 * context_tokens * s["hq"] * s["dh"]


def state_update_bytes(cfg, slots, delta_bytes_per_el=4):
    """Bytes the one-token update moves over all linear-attention layers
    for ``slots`` slots: each state read once and written once."""
    _, n_lin = layer_counts(cfg)
    return 2 * slots * n_lin * state_elements(cfg) * delta_bytes_per_el


def state_update_flops(cfg, slots):
    _, n_lin = layer_counts(cfg)
    return 7.0 * slots * n_lin * state_elements(cfg)


def chunk_flops(cfg, rows, positions):
    """Operations of the chunked delta rule over all linear-attention
    layers for ``rows`` sequences of ``positions`` each (whole chunks:
    a last chunk is computed whole)."""
    s = sizes(cfg)
    _, n_lin = layer_counts(cfg)
    c = int(cfg.get("linear_chunk_size", 64))
    dk, dv = s["dk"], s["dv"]
    chunks = -(-positions // c)
    per_chunk = 4.0 * c * c * dk + c * c * (dv + dk) \
        + 2.0 * c * c * dv + 6.0 * c * dk * dv
    return n_lin * rows * chunks * s["heads"] * per_chunk


def chunk_bytes(cfg, rows, positions, delta_bytes_per_el=4):
    """Bytes the chunked delta rule has to move: q, k, v in and o out in
    float32 for every position, g and beta, and each row's state read
    once and written once a call."""
    s = sizes(cfg)
    _, n_lin = layer_counts(cfg)
    per_pos = (2 * s["heads"] * s["dk"] + 2 * s["heads"] * s["dv"]
               + 2 * s["heads"]) * 4
    return n_lin * rows * (positions * per_pos
                           + 2 * state_elements(cfg) * delta_bytes_per_el)
