"""Weights of an ``afmoe`` configuration, from a seed, on the device, in
bfloat16: this device's share of an expert-parallel layer.

One leaf a tensor: ``embed``; per layer ``l3.norm1``, ``l3.q`` (heads x
head size, d), ``l3.k``, ``l3.v`` (key/value heads x head size, d),
``l3.gate`` (heads x head size, d: the attention output's gate),
``l3.o`` (d, heads x head size), ``l3.q_norm``, ``l3.k_norm`` (head
size,), ``l3.post_norm1``, ``l3.norm2``, ``l3.post_norm2`` (d,) — the
sandwich's four norms — then either a dense gated MLP, ``l3.mlp_in``
(2 x dense width, d: gate over up) and ``l3.mlp_out`` (d, dense width),
or a routed layer: ``l3.router`` (d, experts routed over), ``l3.bias``
(experts routed over: the selection bias), ``l3.w_in`` (experts held,
d, 2 x expert width: each expert's gate beside its up projection),
``l3.w_out`` (experts held, expert width, d), ``l3.shared_in`` (d, 2 x
shared width) and ``l3.shared_out`` (shared width, d); then
``final_norm`` and ``head`` (untied).  A projection is (out, in), as
``y = x W^T``; the router and the experts are (in, out).

Matrices are normal(0, 0.02), the router too: its logits on a norm's
unit-RMS output spread by about 0.02 sqrt(3072) = 1.1, so a token's
four of 256 sigmoid scores are a choice and not a tie.  The embedding
is normal(0, ``EMBED_STD``) = normal(0, 0.1): the model multiplies a
row by sqrt(3072) (``mup_enabled``), so a token's row enters the stream
at an RMS of 5.5, above the RMS of 1 at which the sandwich's norms put
each sublayer's output.  Routing then follows the token: at 0.02 (RMS
1.1) the attention's output, alike at every position of a random
prompt, steered every token to the same few experts, and which those
were, and so how much of the load fell on the held experts, changed
from seed to seed by about a fifth, and with it the device time of a
step; at normal(0, 1) (RMS 55, SDAR's rows, which serve a model with no
multiplier) the layers' outputs would be a fiftieth of the stream and
the logits, and ``correct``, would hardly see them.  The selection bias
is the ``E`` mid-point quantiles of normal(0, ``BIAS_STD``) dealt so
that every share of ``held`` experts gets one value of each run of
``E / held`` neighbouring quantiles, in an order drawn from the seed (a
trainer's bias balances the load over the experts; one drawn whole at
random piles it on a few, and on some seeds on the held ones): it
moves a token's chosen four for most tokens (the share is counted by
``reference_trinity.bias_moves``) and its weights by a few percent
where the control ``bias_weights`` takes them from ``score + bias``.
Norm weights are 1.  Every leaf is rounded to bfloat16, the
checkpoint's dtype: the program is given these arrays and the reference
upcasts the SAME values.

A routed expert is drawn from its own index among ALL the experts
routed over, so a device that holds experts 32-63 holds what the whole
layer's experts 32-63 are, and the shares of eight devices add up to
the whole layer (``expert_share`` in the configuration says which this
one holds).  Leaves are drawn one at a time.
"""
import functools

from .weights import key_words

BIAS_STD = 0.05
EMBED_STD = 0.1


def sizes(cfg):
    share = cfg.get("expert_share") or {}
    return {"d": cfg["hidden_size"], "v": cfg["vocab_size"],
            "hq": cfg["num_attention_heads"],
            "hk": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "e": int(share.get("of", cfg["num_experts"])),
            "held": cfg["num_experts"], "first": int(share.get("first", 0)),
            "k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "shared": cfg["num_shared_experts"]
            * cfg["moe_intermediate_size"],
            "dense": cfg["intermediate_size"],
            "dense_layers": cfg["num_dense_layers"],
            "window": cfg["sliding_window"]}


def layer_shapes(cfg, i):
    """``{leaf: shape}`` of layer ``i``, in the program's order."""
    s = sizes(cfg)
    d, dh = s["d"], s["dh"]
    out = {"norm1": (d,), "q": (s["hq"] * dh, d), "k": (s["hk"] * dh, d),
           "v": (s["hk"] * dh, d), "gate": (s["hq"] * dh, d),
           "o": (d, s["hq"] * dh), "q_norm": (dh,), "k_norm": (dh,),
           "post_norm1": (d,), "norm2": (d,), "post_norm2": (d,)}
    if i < s["dense_layers"]:
        out.update(mlp_in=(2 * s["dense"], d), mlp_out=(d, s["dense"]))
    else:
        out.update(router=(d, s["e"]), bias=(s["e"],),
                   w_in=(s["held"], d, 2 * s["f"]),
                   w_out=(s["held"], s["f"], d),
                   shared_in=(d, 2 * s["shared"]),
                   shared_out=(s["shared"], d))
    return out


def leaf_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed": (v, d)}
    for i in range(len(cfg["layer_types"])):
        for name, shape in layer_shapes(cfg, i).items():
            shapes[f"l{i}.{name}"] = shape
    shapes["final_norm"] = (d,)
    shapes["head"] = (v, d)
    return shapes


# a leaf's place among its layer's, whatever kind of feed-forward the
# layer has: its key does not move when another leaf is added
LEAVES = ("norm1", "q", "k", "v", "gate", "o", "q_norm", "k_norm",
          "post_norm1", "norm2", "post_norm2", "mlp_in", "mlp_out",
          "router", "bias", "w_in", "w_out", "shared_in", "shared_out")


@functools.lru_cache(maxsize=32)
def _maker(shape, kind, held=None):
    """One jitted call that draws one leaf of ``shape``; a stack of
    experts ``(count, ...)`` from the key of each expert's own index
    (the call takes the first); the selection bias over ``shape[0]``
    experts routed over, in shares of ``held``."""
    import jax
    import jax.numpy as jnp
    if kind == "ones":
        return jax.jit(lambda key: jnp.ones(shape, jnp.bfloat16))
    if kind == "bias":
        e = shape[0]
        if e % held:
            raise ValueError(f"weights_trinity: {e} experts do not fall "
                             f"into shares of {held}")
        shares = e // held
        # quantile j * shares + s is the s-th of stratum j: a stratum a
        # held place, each share one value of every stratum
        q = BIAS_STD * jax.scipy.special.ndtri(
            (jnp.arange(e, dtype=jnp.float32) + 0.5) / e)

        def bias(key):
            deal, place = jax.random.split(key)
            # strata (held, shares) -> each stratum's values over the shares
            dealt = jax.vmap(jax.random.permutation)(
                jax.random.split(deal, held), q.reshape(held, shares))
            # each share's held values in an order of its own
            mine = jax.vmap(jax.random.permutation)(
                jax.random.split(place, shares), dealt.T)
            return mine.reshape(e).astype(jnp.bfloat16)
        return jax.jit(bias)
    scale = EMBED_STD if kind == "embed" else 0.02
    if kind == "experts":
        # one expert at a time: a whole stack in float32 (2.4 GB) would
        # not fit beside the tables when a run's weights are redrawn
        def stack(key, first):
            return jax.lax.map(lambda j: (0.02 * jax.random.normal(
                jax.random.fold_in(key, j), shape[1:], jnp.float32)).astype(
                    jnp.bfloat16), first + jnp.arange(shape[0]))
        return jax.jit(stack)
    return jax.jit(lambda key: (scale * jax.random.normal(
        key, shape, jnp.float32)).astype(jnp.bfloat16))


def make(cfg, seed):
    """``{name: bfloat16 device array}`` for ``cfg`` from ``seed``.  A
    leaf depends on the seed and its own place alone, and a routed
    expert on its own index among all the experts routed over."""
    import jax
    key = jax.random.wrap_key_data(key_words(seed))
    first, held = sizes(cfg)["first"], sizes(cfg)["held"]
    out = {}
    for name, shape in leaf_shapes(cfg).items():
        if name.startswith("l"):
            i, leaf = name[1:].split(".")
            at = jax.random.fold_in(jax.random.fold_in(key, 1 + int(i)),
                                    LEAVES.index(leaf))
        else:
            leaf = name
            at = jax.random.fold_in(
                jax.random.fold_in(key, 0),
                ("embed", "final_norm", "head").index(name))
        if "norm" in leaf:
            out[name] = _maker(tuple(shape), "ones")(at)
        elif leaf in ("w_in", "w_out"):
            out[name] = _maker(tuple(shape), "experts")(at, first)
        elif leaf == "bias":
            out[name] = _maker(tuple(shape), "bias", held)(at)
        else:
            out[name] = _maker(tuple(shape),
                               "embed" if leaf == "embed" else "normal")(at)
    return out
