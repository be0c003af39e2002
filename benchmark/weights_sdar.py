"""Weights of an ``sdar_moe`` configuration, from a seed, on the device, in
bfloat16.

The leaves are ``weights_mellum2``'s, one a tensor: ``embed``, per layer
``l3.norm1``, ``l3.q`` (heads x head size, d), ``l3.k``, ``l3.v``
(key/value heads x head size, d), ``l3.o`` (d, heads x head size),
``l3.q_norm``, ``l3.k_norm`` (head size,), ``l3.norm2``, ``l3.router``
(d, experts), ``l3.w_in`` (experts, d, 2 x expert width: each expert's
gate beside its up projection) and ``l3.w_out`` (experts, expert width,
d), then ``final_norm`` and ``head`` (untied).  A projection is (out,
in), as ``y = x W^T``; the router and the experts are (in, out), stacked
on the expert axis.

Matrices are normal(0, 0.02), the router too; norm weights are 1.  The
EMBEDDING is normal(0, 1): a token's row then has the unit RMS that the
stream of a trained model has, so each position's stream carries its own
token and the router's logits (spread about 0.02 sqrt(2048) = 0.9 on a
norm's output) differ from token to token, where rows of 0.02 leave
every stream alike after the first layer's norms and send every token of
a call to the same few experts.  Every leaf is rounded to bfloat16, the
checkpoint's dtype: the program is given these arrays and the reference
upcasts the SAME values.  Leaves are drawn one at a time, so making
8.7 GB of them never holds more than one leaf in float32 (an expert
stack: 1.6 GB).
"""
import functools

from .weights import key_words


def sizes(cfg):
    return {"d": cfg["hidden_size"], "v": cfg["vocab_size"],
            "hq": cfg["num_attention_heads"],
            "hk": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "e": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"], "b": cfg["block_length"],
            "layers": cfg["num_hidden_layers"]}


def layer_shapes(cfg):
    """``{leaf: shape}`` of one layer, in the program's order."""
    s = sizes(cfg)
    d, dh = s["d"], s["dh"]
    return {"norm1": (d,), "q": (s["hq"] * dh, d), "k": (s["hk"] * dh, d),
            "v": (s["hk"] * dh, d), "o": (d, s["hq"] * dh),
            "q_norm": (dh,), "k_norm": (dh,), "norm2": (d,),
            "router": (d, s["e"]), "w_in": (s["e"], d, 2 * s["f"]),
            "w_out": (s["e"], s["f"], d)}


def leaf_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        for name, shape in layer_shapes(cfg).items():
            shapes[f"l{i}.{name}"] = shape
    shapes["final_norm"] = (d,)
    shapes["head"] = (v, d)
    return shapes


@functools.lru_cache(maxsize=32)
def _maker(shape, kind):
    """One jitted call that draws one leaf of ``shape``."""
    import jax
    import jax.numpy as jnp
    if kind == "ones":
        return jax.jit(lambda key: jnp.ones(shape, jnp.bfloat16))
    scale = 1.0 if kind == "embed" else 0.02
    return jax.jit(lambda key: (scale * jax.random.normal(
        key, shape, jnp.float32)).astype(jnp.bfloat16))


def make(cfg, seed):
    """``{name: bfloat16 device array}`` for ``cfg`` from ``seed``.  A
    leaf depends on the seed and its own place alone, so a configuration
    cut to its first layers holds the same leaves as the whole model's
    first layers."""
    import jax
    key = jax.random.wrap_key_data(key_words(seed))
    per_layer = list(layer_shapes(cfg))
    out = {}
    for name, shape in leaf_shapes(cfg).items():
        if name.startswith("l"):
            i, leaf = name[1:].split(".")
            at = jax.random.fold_in(jax.random.fold_in(key, 1 + int(i)),
                                    per_layer.index(leaf))
        else:
            at = jax.random.fold_in(
                jax.random.fold_in(key, 0),
                ("embed", "final_norm", "head").index(name))
        kind = "ones" if "norm" in name else \
            "embed" if name == "embed" else "normal"
        out[name] = _maker(tuple(shape), kind)(at)
    return out
