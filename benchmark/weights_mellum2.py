"""Weights of a ``mellum`` configuration, from a seed, on the device, in
bfloat16.

One leaf a tensor: ``embed``, per layer ``l7.norm1``, ``l7.q`` (heads x
head size, d), ``l7.k``, ``l7.v`` (key/value heads x head size, d),
``l7.o`` (d, heads x head size), ``l7.q_norm``, ``l7.k_norm`` (head
size,), ``l7.norm2``, ``l7.router`` (d, experts), ``l7.w_in`` (experts,
d, 2 x expert width: each expert's gate beside its up projection) and
``l7.w_out`` (experts, expert width, d), then ``final_norm`` and
``head`` (untied).  A projection is (out, in), as ``y = x W^T``; the
router and the experts are (in, out), stacked on the expert axis, as the
program's grouped product takes them.

Matrices are normal(0, 0.02) — the router too: its logits on a norm's
unit-RMS output then spread by about 0.02 sqrt(2304) = 0.96, so the top
8 of 64 are a choice and not a tie; norm weights are 1.  Every leaf is
rounded to bfloat16, the checkpoint's dtype: the program is given these
arrays and the reference upcasts the SAME values.  Leaves are drawn one
at a time, so making 10.9 GB of them never holds more than one leaf in
float32 (an expert stack: 1.06 GB).
"""
import functools

from .weights import key_words


def sizes(cfg):
    return {"d": cfg["hidden_size"], "v": cfg["vocab_size"],
            "hq": cfg["num_attention_heads"],
            "hk": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "e": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "window": cfg["sliding_window"]}


def layer_shapes(cfg):
    """``{leaf: shape}`` of one layer (both kinds hold the same), in the
    program's order."""
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
    for i in range(len(cfg["layer_types"])):
        for name, shape in layer_shapes(cfg).items():
            shapes[f"l{i}.{name}"] = shape
    shapes["final_norm"] = (d,)
    shapes["head"] = (v, d)
    return shapes


@functools.lru_cache(maxsize=32)
def _maker(shape, ones):
    """One jitted call that draws one leaf of ``shape``."""
    import jax
    import jax.numpy as jnp
    if ones:
        return jax.jit(lambda key: jnp.ones(shape, jnp.bfloat16))
    return jax.jit(lambda key: (0.02 * jax.random.normal(
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
        out[name] = _maker(tuple(shape), "norm" in name)(at)
    return out
