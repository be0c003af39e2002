"""Weights of an ``olmo_hybrid`` configuration, from a seed, on the
device, in bfloat16.

One leaf a tensor: ``embed``, per layer ``l7.<mixer leaves>``,
``l7.norm1`` (on the mixer's output), ``l7.mlp_in`` (``W_gate`` over
``W_up``), ``l7.mlp_out``, ``l7.norm2`` (on the MLP's output), then
``final_norm`` and ``head`` (untied).  A ``linear_attention`` layer's
leaves are ``q``, ``k`` (H dk, d), ``v``, ``g`` (H dv, d), ``a``, ``b``
(H, d), ``conv_w`` (2 H dk + H dv channels, kernel: q's, k's and v's
depthwise kernels one after the other), ``a_log``, ``dt_bias`` (H,),
``o_norm`` (dv,), ``o`` (d, H dv); a ``full_attention`` layer's ``q``,
``k``, ``v``, ``o`` (d, d) and ``q_norm``, ``k_norm`` (d,).  A matrix is
(out, in), as ``y = x W^T``.

Matrices are normal(0, 0.02).  The decay's leaves follow the Gated
DeltaNet (and Mamba-2) initialiser, so that the heads' decay rates span
the range a trained model's do: ``a_log = log(U(1, 16))``, ``dt_bias =
softplus^-1(dt)`` with ``dt`` log-uniform in [1e-3, 1e-1]; convolution
weights U(+-1/2) (= 1/sqrt(kernel) at the published kernel of 4); norm
weights are 1.  ``a`` (which gives the decay's data-dependent part) is
drawn at ``A_SCALE`` times 0.02: this block layout feeds the mixer the
residual stream itself, whose root mean square is 1 after the first
sublayer and grows with depth, so at 0.02 ``x W_a`` (deviation 0.02
sqrt(d) |x| >= 1.2) would swamp ``dt_bias`` and most heads would forget
their state within a token; at a sixteenth of it the decays stay in the
trained range and still depend on the token.  ``b`` (which gives beta)
stays at 0.02: ``2 sigmoid(x W_b)`` then covers (0, 2), half of the
positions above 1, where the transition has a negative eigenvalue
(``reference_olmo_hybrid.beta_share_above_one`` reads the share).  Every leaf is then
rounded to bfloat16, the checkpoint's dtype: the program is given these
arrays and the reference upcasts the SAME values.  Leaves are drawn
layer by layer, so making them never holds more than one layer in
float32.
"""
import functools

import numpy as np

from .weights import key_words

A_SCALE = 1.0 / 16.0


def sizes(cfg):
    d, heads = cfg["hidden_size"], cfg["linear_num_value_heads"]
    if cfg["linear_num_key_heads"] != heads:
        raise ValueError("weights_olmo_hybrid: as many key heads as value "
                         "heads only")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("weights_olmo_hybrid: full attention is "
                         "multi-head: as many key/value heads as heads")
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {"d": d, "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "heads": heads, "dk": dk, "dv": dv,
            "channels": 2 * heads * dk + heads * dv,
            "k": cfg["linear_conv_kernel_dim"],
            "hq": cfg["num_attention_heads"],
            "dh": d // cfg["num_attention_heads"]}


def layer_shapes(cfg, kind):
    """``{leaf: shape}`` of one layer of ``kind``, in a fixed order."""
    s = sizes(cfg)
    d, f, h = s["d"], s["f"], s["heads"]
    if kind == "linear_attention":
        out = {"q": (h * s["dk"], d), "k": (h * s["dk"], d),
               "v": (h * s["dv"], d), "g": (h * s["dv"], d),
               "a": (h, d), "b": (h, d),
               "conv_w": (s["channels"], s["k"]), "a_log": (h,),
               "dt_bias": (h,), "o_norm": (s["dv"],),
               "o": (d, h * s["dv"])}
    elif kind == "full_attention":
        out = {"q": (d, d), "k": (d, d), "v": (d, d), "o": (d, d),
               "q_norm": (d,), "k_norm": (d,)}
    else:
        raise ValueError(f"weights_olmo_hybrid: unknown layer type {kind!r}")
    out.update({"norm1": (d,), "mlp_in": (2 * f, d), "mlp_out": (d, f),
                "norm2": (d,)})
    return out


def leaf_shapes(cfg):
    d = cfg["hidden_size"]
    shapes = {"embed": (cfg["vocab_size"], d)}
    for i, kind in enumerate(cfg["layer_types"]):
        for name, shape in layer_shapes(cfg, kind).items():
            shapes[f"l{i}.{name}"] = shape
    shapes["final_norm"] = (d,)
    shapes["head"] = (cfg["vocab_size"], d)
    return shapes


def _draw(key, name, shape):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    if name.endswith("norm") or name in ("norm1", "norm2"):
        return jnp.ones(shape, f32)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, np.log(1e-3),
                                        np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))         # softplus^-1
    if name == "conv_w":
        return jax.random.uniform(key, shape, f32, -0.5, 0.5)
    scale = 0.02 * (A_SCALE if name == "a" else 1.0)
    return scale * jax.random.normal(key, shape, f32)


@functools.lru_cache(maxsize=8)
def _maker(shape_items):
    """One jitted call that draws the leaves ``shape_items`` names."""
    import jax
    import jax.numpy as jnp

    def make(key):
        return {name: _draw(jax.random.fold_in(key, j), name,
                            shape).astype(jnp.bfloat16)
                for j, (name, shape) in enumerate(shape_items)}

    return jax.jit(make)


def make(cfg, seed):
    """``{name: bfloat16 device array}`` for ``cfg`` from ``seed``.
    Layer i's leaves depend on the seed and on i alone, so a
    configuration cut to its first layers holds the same leaves as the
    whole model's first layers."""
    import jax
    key = jax.random.wrap_key_data(key_words(seed))
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    out = dict(_maker((("embed", (v, d)), ("final_norm", (d,)),
                       ("head", (v, d))))(jax.random.fold_in(key, 0)))
    for i, kind in enumerate(cfg["layer_types"]):
        layer = _maker(tuple(layer_shapes(cfg, kind).items()))(
            jax.random.fold_in(key, 1 + i))
        out.update({f"l{i}.{name}": a for name, a in layer.items()})
    return {name: out[name] for name in leaf_shapes(cfg)}
