"""Weights of a BERT-shaped configuration, from a seed, on the device.

One jitted call makes every leaf, in float32, as normal(0, 0.02) (BERT's
``initializer_range``); LayerNorm scales are 1 + that.  The layout is the
plain reference's, which is the source's: separate query, key and value
projections, one entry per layer.  Leaves are named ``word_embed``,
``l7.q_w``, ...; a Dense weight is (out, in), as ``y = x W^T + b``.
The driver hands the same arrays to the program (fused where the
program fuses) and to the reference; neither makes weights of its own.
"""
import functools

import numpy as np


def leaf_shapes(cfg):
    """``{name: shape}`` of every leaf, in a fixed order."""
    v, d, f = cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]
    shapes = {"word_embed": (v, d),
              "pos_embed": (cfg["max_position_embeddings"], d)}
    if cfg.get("use_token_type"):
        shapes["type_embed"] = (cfg.get("type_vocab_size", 2), d)
    shapes["embed_ln_g"] = (d,)
    shapes["embed_ln_b"] = (d,)
    for i in range(cfg["num_hidden_layers"]):
        for name, shape in layer_shapes(d, f).items():
            shapes[f"l{i}.{name}"] = shape
    shapes["out_w"] = (v, d)
    shapes["out_b"] = (v,)
    return shapes


def layer_shapes(d, f):
    return {"q_w": (d, d), "q_b": (d,), "k_w": (d, d), "k_b": (d,),
            "v_w": (d, d), "v_b": (d,), "proj_w": (d, d), "proj_b": (d,),
            "ln1_g": (d,), "ln1_b": (d,),
            "ffn1_w": (f, d), "ffn1_b": (f,), "ffn2_w": (d, f),
            "ffn2_b": (d,), "ln2_g": (d,), "ln2_b": (d,)}


def is_scale(name):
    return name.endswith("_g")


def key_words(seed):
    """Two uint32 words from a seed of any size (the driver's seeds pass
    2**31): never the seed itself through a 32-bit API."""
    return np.random.SeedSequence([int(seed), 7]).generate_state(2)


@functools.lru_cache(maxsize=4)
def _maker(cfg_items):
    import jax
    import jax.numpy as jnp
    cfg = dict(cfg_items)
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    n_layers = cfg["num_hidden_layers"]
    shapes = leaf_shapes(cfg)
    per_layer = layer_shapes(d, f)

    def draw(key, shape, scale):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
        return 1.0 + x if scale else x

    def make(words):
        key = jax.random.wrap_key_data(words)
        out = {}
        # one draw per kind of layer leaf, stacked over the layers, then
        # cut apart: a dozen generators and not three hundred
        for j, (name, shape) in enumerate(per_layer.items()):
            stacked = draw(jax.random.fold_in(key, 1000 + j),
                           (n_layers,) + shape, is_scale(name))
            for i in range(n_layers):
                out[f"l{i}.{name}"] = stacked[i]
        for j, (name, shape) in enumerate(shapes.items()):
            if name not in out:
                out[name] = draw(jax.random.fold_in(key, j), shape,
                                 is_scale(name))
        return {name: out[name] for name in shapes}

    return jax.jit(make)


_SIZE_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "max_position_embeddings",
              "use_token_type", "type_vocab_size")


def make(cfg, seed):
    """``{name: float32 device array}`` for ``cfg`` from ``seed``."""
    items = tuple((k, cfg[k]) for k in _SIZE_KEYS if k in cfg)
    return _maker(items)(key_words(seed))
