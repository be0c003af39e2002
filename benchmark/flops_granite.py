"""Operations and bytes of a ``granitemoehybrid`` configuration with no
routed experts, from shapes alone.

The yardstick for the ``*_mfu`` and ``*_roofline`` metrics of its cells.
Counts what the algorithm needs, not what a compiler emitted: a
multiply-add is two operations; norms, activations, the gate and other
element-wise work are not counted.  Nothing here imports the program or
JAX.

The state-space recurrence, per Mamba layer and token, over the
``heads x head size x state`` elements of the state: decay and add (2),
the outer product ``dt x (x) B`` that is added (1), and the read-out
``S C`` (a multiply-add: 2) — 5 operations an element; the depthwise
convolution is ``2 x kernel`` a channel.  The chunked form a program may
use for many positions at once does more arithmetic than this and is
credited with no more.
"""
from .flops import roofline_seconds  # noqa: F401 — the readers' one way in
from .weights_granite import leaf_shapes, sizes


def layer_counts(cfg):
    kinds = cfg["layer_types"]
    return kinds.count("attention"), kinds.count("mamba")


def gemm_params(cfg):
    """Weights that sit in a matrix product on every token: each layer's
    projections and MLP, and the tied head.  The embedding lookup is a
    gather, not a product."""
    s = sizes(cfg)
    d, f = s["d"], s["f"]
    n_attn, n_mamba = layer_counts(cfg)
    mlp = 3 * d * f                              # 2f x d in, d x f out
    attn = 2 * d * s["hq"] * s["dh"] + 2 * d * s["hk"] * s["dh"]
    mamba = d * (s["inner"] + s["channels"] + s["heads"]) + s["inner"] * d
    return n_attn * (attn + mlp) + n_mamba * (mamba + mlp) + d * s["v"]


def param_count(cfg):
    """Every parameter, as ``weights_granite.leaf_shapes`` lays them out
    (the tied embedding once)."""
    total = 0
    for shape in leaf_shapes(cfg).values():
        n = 1
        for dim in shape:
            n *= dim
        total += n
    return total


def scan_flops_per_token(cfg):
    """The recurrence and the convolution of all Mamba layers, for one
    token."""
    s = sizes(cfg)
    _, n_mamba = layer_counts(cfg)
    return n_mamba * (5.0 * s["inner"] * s["n"] + 2.0 * s["k"] * s["channels"])


def attention_flops(cfg, q_len, kv_len, causal=False):
    """Forward operations of ONE sequence's attention cores over the
    attention layers: QK^T and PV, 2 * q_len * kv_len * (query heads x
    head size) each.  A causal square counts the half a causal kernel
    has to compute."""
    s = sizes(cfg)
    n_attn, _ = layer_counts(cfg)
    ops = 4.0 * q_len * kv_len * s["hq"] * s["dh"]
    if causal and q_len == kv_len:
        ops *= 0.5 * (1.0 + 1.0 / q_len)
    return n_attn * ops


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
    (the tied embedding is the head's) and the small leaves."""
    return param_count(cfg) * bytes_per_el


def state_bytes_per_lane(cfg, ssm_bytes_per_el=4, conv_bytes_per_el=4):
    """One lane's recurrent state over all Mamba layers: the scan's
    state and the convolution's last ``kernel - 1`` inputs."""
    s = sizes(cfg)
    _, n_mamba = layer_counts(cfg)
    return n_mamba * (s["inner"] * s["n"] * ssm_bytes_per_el
                      + (s["k"] - 1) * s["channels"] * conv_bytes_per_el)


def kv_bytes_per_token(cfg, bytes_per_el=2):
    """Keys and values one cached position holds over the attention
    layers."""
    s = sizes(cfg)
    n_attn, _ = layer_counts(cfg)
    return n_attn * 2 * s["hk"] * s["dh"] * bytes_per_el


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
    n_attn, _ = layer_counts(cfg)
    return active * (2.0 * gemm_params(cfg) + scan_flops_per_token(cfg)) \
        + n_attn * 4.0 * context_tokens * s["hq"] * s["dh"]


def state_update_bytes(cfg, slots, ssm_bytes_per_el=4):
    """Bytes the scan's one-token update moves over all Mamba layers for
    ``slots`` slots: each state read once and written once."""
    s = sizes(cfg)
    _, n_mamba = layer_counts(cfg)
    return 2 * slots * n_mamba * s["inner"] * s["n"] * ssm_bytes_per_el


def state_update_flops(cfg, slots):
    s = sizes(cfg)
    _, n_mamba = layer_counts(cfg)
    return 5.0 * slots * n_mamba * s["inner"] * s["n"]
