"""Weights of a ``granitemoehybrid`` configuration with no routed
experts, from a seed, on the device, in bfloat16.

The layout is the published checkpoint's, one leaf a tensor, nothing
fused: ``embed`` (tied to the head), per layer ``l7.norm1``, the mixer's
leaves, ``l7.norm2``, ``l7.mlp_in`` (gate and value halves stacked, as
``shared_mlp.input_linear``), ``l7.mlp_out``, and ``final_norm``.  A
Mamba layer's leaves are ``in_proj`` (z, xBC, dt stacked), ``conv_w``
(channels, kernel), ``conv_b``, ``dt_bias``, ``a_log``, ``d_skip``,
``ssm_norm``, ``out_proj``; an attention layer's ``q``, ``k``, ``v``,
``o``.  A matrix is (out, in), as ``y = x W^T``.

Matrices are normal(0, 0.02).  The state-space leaves follow Mamba-2's
published initialiser, so that the heads' decay rates span the range a
trained model's do (with every leaf at 0.02 all heads would decay alike
and the scan's arithmetic would not be exercised): ``a_log =
log(U(1, 16))``, ``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform
in [1e-3, 1e-1], ``d_skip = 1``, convolution weights and biases
U(+-1/sqrt(kernel)); norm weights are 1.  Every leaf is then rounded to
bfloat16, the checkpoint's dtype: the program is given these arrays and
the reference upcasts the SAME values.  Leaves are drawn layer by layer,
so making them never holds more than one layer in float32.
"""
import functools

import numpy as np

from .weights import key_words


def sizes(cfg):
    d = cfg["hidden_size"]
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n = cfg["mamba_d_state"] * cfg.get("mamba_n_groups", 1)
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"d": d, "f": cfg["shared_intermediate_size"],
            "v": cfg["vocab_size"], "heads": heads, "p": p, "n": n,
            "inner": heads * p, "channels": heads * p + 2 * n,
            "k": cfg["mamba_d_conv"], "hq": hq, "hk": hk, "dh": d // hq}


def layer_shapes(cfg, kind):
    """``{leaf: shape}`` of one layer of ``kind``, in a fixed order."""
    s = sizes(cfg)
    d, f = s["d"], s["f"]
    out = {"norm1": (d,)}
    if kind == "mamba":
        out.update({
            "in_proj": (s["inner"] + s["channels"] + s["heads"], d),
            "conv_w": (s["channels"], s["k"]), "conv_b": (s["channels"],),
            "dt_bias": (s["heads"],), "a_log": (s["heads"],),
            "d_skip": (s["heads"],), "ssm_norm": (s["inner"],),
            "out_proj": (d, s["inner"])})
    elif kind == "attention":
        out.update({"q": (s["hq"] * s["dh"], d), "k": (s["hk"] * s["dh"], d),
                    "v": (s["hk"] * s["dh"], d), "o": (d, s["hq"] * s["dh"])})
    else:
        raise ValueError(f"weights_granite: unknown layer type {kind!r}")
    out.update({"norm2": (d,), "mlp_in": (2 * f, d), "mlp_out": (d, f)})
    return out


def leaf_shapes(cfg):
    shapes = {"embed": (cfg["vocab_size"], cfg["hidden_size"])}
    for i, kind in enumerate(cfg["layer_types"]):
        for name, shape in layer_shapes(cfg, kind).items():
            shapes[f"l{i}.{name}"] = shape
    shapes["final_norm"] = (cfg["hidden_size"],)
    return shapes


def _draw(key, name, shape, kernel):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    if name in ("norm1", "norm2", "ssm_norm", "final_norm", "d_skip"):
        return jnp.ones(shape, f32)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, np.log(1e-3),
                                        np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))         # softplus^-1
    if name in ("conv_w", "conv_b"):
        r = 1.0 / np.sqrt(kernel)
        return jax.random.uniform(key, shape, f32, -r, r)
    return 0.02 * jax.random.normal(key, shape, f32)


@functools.lru_cache(maxsize=8)
def _maker(shape_items, kernel):
    """One jitted call that draws the leaves ``shape_items`` names."""
    import jax
    import jax.numpy as jnp

    def make(key):
        return {name: _draw(jax.random.fold_in(key, j), name, shape,
                            kernel).astype(jnp.bfloat16)
                for j, (name, shape) in enumerate(shape_items)}

    return jax.jit(make)


def make(cfg, seed):
    """``{name: bfloat16 device array}`` for ``cfg`` from ``seed``."""
    import jax
    key = jax.random.wrap_key_data(key_words(seed))
    kernel = cfg["mamba_d_conv"]
    d = cfg["hidden_size"]
    out = dict(_maker((("embed", (cfg["vocab_size"], d)),
                       ("final_norm", (d,))), kernel)(
        jax.random.fold_in(key, 0)))
    for i, kind in enumerate(cfg["layer_types"]):
        layer = _maker(tuple(layer_shapes(cfg, kind).items()), kernel)(
            jax.random.fold_in(key, 1 + i))
        out.update({f"l{i}.{name}": a for name, a in layer.items()})
    return {name: out[name] for name in leaf_shapes(cfg)}
