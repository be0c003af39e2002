"""Operations and bytes of the timed work, from shapes alone.

The yardstick for every ``*_mfu`` and ``*_roofline`` metric.  Counts what
the algorithm needs, not what a compiler emitted: a multiply-add is two
operations; recomputation, the optimizer and element-wise work are not
counted.  Nothing here imports the program or JAX.
"""


def gemm_params(cfg):
    """Weights that sit in a matrix product on every token: four
    attention projections and two FFN matrices per layer, and the output
    head.  Embedding lookups are gathers, not products."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 4 * d * d + 2 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def param_count(cfg):
    """Every parameter, as weights.leaf_shapes lays them out."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n = v * d + cfg["max_position_embeddings"] * d + 2 * d
    if cfg.get("use_token_type"):
        n += cfg.get("type_vocab_size", 2) * d
    n += cfg["num_hidden_layers"] * (4 * (d * d + d) + 2 * d * f + f + d
                                     + 4 * d)
    return n + v * d + v


def attention_flops(cfg, q_len, kv_len, causal=False):
    """Forward operations of ONE sequence's attention cores over all
    layers: QK^T and PV, 2 * q_len * kv_len * hidden each.  A causal
    square counts the half a causal kernel has to compute."""
    d = cfg["hidden_size"]
    ops = 4.0 * q_len * kv_len * d
    if causal and q_len == kv_len:
        ops *= 0.5 * (1.0 + 1.0 / q_len)
    return cfg["num_hidden_layers"] * ops


def forward_flops_per_token(cfg, seq, causal=False):
    """Forward operations per token of a full sequence of ``seq``."""
    return 2.0 * gemm_params(cfg) \
        + attention_flops(cfg, seq, seq, causal) / seq


def train_flops_per_token(cfg, seq):
    """Forward + backward (twice the forward) per token."""
    return 3.0 * forward_flops_per_token(cfg, seq, bool(cfg.get("causal")))


def decode_flops_per_token(cfg, context):
    """Forward operations to produce one token against ``context``
    cached positions."""
    return 2.0 * gemm_params(cfg) + attention_flops(cfg, 1, context)


def flash_attention_call(batch, heads, q_len, kv_len, head_dim, causal,
                         backward, bytes_per_el):
    """(operations, bytes) one attention call needs.  Forward: QK^T and
    PV.  Backward: the five products of the standard backward (dV, dP,
    dQ, dK and the recomputed QK^T), 2.5 times the forward.  Bytes: q,
    k, v and o read or written once, and their gradients too in the
    backward."""
    ops = 4.0 * batch * heads * q_len * kv_len * head_dim
    if causal and q_len == kv_len:
        ops *= 0.5 * (1.0 + 1.0 / q_len)
    q_bytes = batch * heads * q_len * head_dim * bytes_per_el
    kv_bytes = batch * heads * kv_len * head_dim * bytes_per_el
    fwd_bytes = 2 * q_bytes + 2 * kv_bytes          # q, o, k, v
    if not backward:
        return ops, fwd_bytes
    # reads q, k, v, o, do; writes dq, dk, dv
    return 2.5 * ops, 4 * q_bytes + 4 * kv_bytes


def decode_step_bytes(cfg, slots, kv_capacity, weight_bytes_per_el=4,
                      kv_bytes_per_el=4):
    """Bytes one decode step over ``slots`` slots has to read: every
    weight of the products once, and the whole key/value table (the
    step's attention is masked, not cut, so every slot's full capacity
    is read).  The embedding tables are gathered, not read whole."""
    d = cfg["hidden_size"]
    weights = gemm_params(cfg) * weight_bytes_per_el
    table = cfg["num_hidden_layers"] * 2 * slots * kv_capacity * d \
        * kv_bytes_per_el
    return weights + table


def roofline_seconds(ops, nbytes, peak_flops, peak_bytes_per_s):
    """The least time the chip could take, and which bound sets it."""
    t_c, t_m = ops / peak_flops, nbytes / peak_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
