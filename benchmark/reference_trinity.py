"""The plain reference of ``trinity_large_preview``: a decoder of sliding-
window and full grouped-query attention layers, gated and under sandwich
norms, each followed by a dense gated MLP (the first layers) or by this
device's share of a layer of sigmoid-routed SwiGLU experts beside a
shared expert, in ``jax.numpy``.

Imports nothing of the program and takes nothing the program made.
float32 throughout, every matrix product at ``precision=HIGHEST``; no
kernels, no cache, no ring (a whole (T, T) mask with both bounds), no
grouping of tokens by expert (every held expert in turn over every
token, with weight 0 where the token did not choose it), no batching
tricks.  The weights come in bfloat16 (``weights_trinity``) and are
upcast one layer at a time (one routed layer's float32 weights are
4.0 GB); queries run in blocks so that a sequence of 10,240 positions
fits.

The equations, from the published ``config.json`` (``model_type:
afmoe``) and the lineage's modelling code as known (listed under
``assumed`` in the configuration's file); written from knowledge, no
network here.  ``x`` is the float32 residual stream, ``n(u) = u /
sqrt(mean(u^2) + eps) * w``, no bias anywhere:

* ``x = E[tokens] sqrt(d)`` (``mup_enabled``).
* layer i, of kind ``layer_types[i]``: ``h = n_in(x)``; ``q = h W_q``
  (48 heads of 128), ``k = h W_k``, ``v = h W_v`` (8 heads of 128),
  ``g = h W_gate`` (48 x 128); each head of ``q`` and of ``k`` normed
  over its 128; a ``sliding_attention`` layer then rotates both at the
  absolute position ``p``, ``rot(u, p) = u cos(p f) + rotate_half(u)
  sin(p f)`` with ``f_j = theta^(-2j / 128)``, ``rotate_half(u) = [-u2,
  u1]`` over the halves and the 64 angles repeated over both; a
  ``full_attention`` layer takes no position.  Query head ``h`` reads
  key/value head ``h // 6``; scores ``q.k / sqrt(128)``, softmax over
  the keys at ``p' <= p`` (full) and ``p - p' < sliding_window``
  besides (sliding); ``a = att * sigmoid(g)``; ``x = x + n_post_attn(a
  W_o)``.
* ``h = n_pre_mlp(x)``; a dense layer: ``y = (silu(h W_g) * (h W_u))
  W_d``; a routed one: ``s = sigmoid(h W_r)`` over all E experts routed
  over; ``S`` the 4 largest of ``s + b`` (the lower index first among
  equals); ``w_e = route_scale s_e / sum_S s``; ``y = shared(h) +
  sum_{e in S, e held} w_e E_e(h)``, ``E_e`` and ``shared`` SwiGLU
  experts; ``x = x + n_post_mlp(y)``.
* ``logits = n_f(x) W_head`` (untied; the head holds the vocabulary's
  rows of this device).

``cast`` puts the reference in the program's place with something taken
away (the controls of ``correct``):

* ``"fp8"`` — both inputs of every matrix product rounded to
  float8_e4m3fn under a per-tensor scale; all else float32.
* ``"bias_weights"`` — the combine weights taken from ``s + b``, the
  scores that chose, in place of ``s``: the mistake the selection bias
  invites.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32
CASTS = (None, "fp8", "bias_weights")
KINDS = ("sliding_attention", "full_attention")
Q_BLOCK = 256


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _mm(cast, spec, a, b):
    if cast == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def rotate(u, theta):
    """``u`` (b, heads, t, dim) at positions 0..t-1."""
    dim = u.shape[-1]
    half = dim // 2
    freq = float(theta) ** (-2.0 * np.arange(half, dtype=np.float64) / dim)
    angle = jnp.arange(u.shape[2], dtype=F32)[:, None] \
        * jnp.asarray(freq, F32)[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    turned = jnp.concatenate([-u[..., half:], u[..., :half]], axis=-1)
    return u * cos + turned * sin


def attention(cast, h, lw, cfg, kind):
    b, t, _ = h.shape
    hq, hk, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    split = lambda z, n: z.reshape(b, t, n, dim).transpose(0, 2, 1, 3)
    q = rms_norm(split(_mm(cast, "btd,fd->btf", h, lw["q"]), hq),
                 lw["q_norm"], eps)
    k = rms_norm(split(_mm(cast, "btd,fd->btf", h, lw["k"]), hk),
                 lw["k_norm"], eps)
    v = split(_mm(cast, "btd,fd->btf", h, lw["v"]), hk)
    if kind == "sliding_attention":
        q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    k, v = (jnp.repeat(z, hq // hk, axis=1) for z in (k, v))
    window = cfg["sliding_window"] if kind == "sliding_attention" else t
    at_k = jnp.arange(t)

    def block(q_blk, first):
        at_q = first + jnp.arange(q_blk.shape[2])
        back = at_q[:, None] - at_k[None, :]
        s = _mm(cast, "bhqd,bhkd->bhqk", q_blk, k) / np.sqrt(dim)
        s = jnp.where((back >= 0) & (back < window), s, -jnp.inf)
        return _mm(cast, "bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    n = min(Q_BLOCK, t)
    if t % n:
        raise ValueError(f"reference_trinity: {t} positions in blocks of {n}")
    blocks = q.reshape(b, hq, t // n, n, dim).transpose(2, 0, 1, 3, 4)
    o = lax.map(lambda a: block(a[0], a[1]),
                (blocks, jnp.arange(t // n) * n))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, hq, t, dim)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, -1) \
        * jax.nn.sigmoid(_mm(cast, "btd,fd->btf", h, lw["gate"]))
    return _mm(cast, "btf,df->btd", o, lw["o"])


def routing(cast, h, lw, cfg):
    """``(weights (b, t, E), chosen (b, t, k), unbiased (b, t, k))``:
    each token's weight on every expert routed over, 0 where it did not
    choose it; the experts chosen; and those ``s`` alone would choose."""
    s = jax.nn.sigmoid(_mm(cast, "btd,de->bte", h, lw["router"]))
    _, top_e = lax.top_k(s + lw["bias"], cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(
        s + lw["bias"] if cast == "bias_weights" else s, top_e, axis=-1)
    if cfg.get("route_norm", True):
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    top_s = top_s * cfg["route_scale"]
    chosen = jax.nn.one_hot(top_e, s.shape[-1], dtype=F32)
    weights = jnp.einsum("btk,btke->bte", top_s, chosen, precision=HIGHEST)
    return weights, top_e, lax.top_k(s, cfg["num_experts_per_tok"])[1]


def swiglu(cast, h, w_in, w_out):
    g, u = jnp.split(_mm(cast, "btd,df->btf", h, w_in), 2, axis=-1)
    return _mm(cast, "btf,fd->btd", silu(g) * u, w_out)


def experts(cast, h, lw, cfg):
    """The shared expert and this device's routed experts, the held ones
    the slice ``first .. first + held`` of the experts routed over."""
    weights, _, _ = routing(cast, h, lw, cfg)
    first = (cfg.get("expert_share") or {}).get("first", 0)
    held = lax.dynamic_slice_in_dim(weights, first, lw["w_in"].shape[0],
                                    axis=-1)

    def one(acc, e):
        w_in, w_out, w_e = e
        return acc + w_e[..., None] * swiglu(cast, h, w_in, w_out), None

    out, _ = lax.scan(one, swiglu(cast, h, lw["shared_in"],
                                  lw["shared_out"]),
                      (lw["w_in"], lw["w_out"], jnp.moveaxis(held, -1, 0)))
    return out


def dense_mlp(cast, h, lw):
    g, u = jnp.split(_mm(cast, "btd,fd->btf", h, lw["mlp_in"]), 2, axis=-1)
    return _mm(cast, "btf,df->btd", silu(g) * u, lw["mlp_out"])


def _cfg_of(cfg_json):
    return json.loads(cfg_json)


@functools.partial(jax.jit, static_argnames=("kind", "cfg_json", "cast"))
def _layer(x, lw, *, kind, cfg_json, cast):
    cfg = _cfg_of(cfg_json)
    lw = {k: v.astype(F32) for k, v in lw.items()}
    eps = cfg["rms_norm_eps"]
    a = attention(cast, rms_norm(x, lw["norm1"], eps), lw, cfg, kind)
    x = x + rms_norm(a, lw["post_norm1"], eps)
    h = rms_norm(x, lw["norm2"], eps)
    y = dense_mlp(cast, h, lw) if "mlp_in" in lw else experts(cast, h, lw,
                                                              cfg)
    return x + rms_norm(y, lw["post_norm2"], eps)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(embed, tokens, *, scale):
    return embed[tokens].astype(F32) * scale


@functools.partial(jax.jit, static_argnames=("eps", "cast"))
def _head(x, norm_w, head_w, *, eps, cast):
    x = rms_norm(x, norm_w.astype(F32), eps)
    return _mm(cast, "btd,vd->btv", x, head_w.astype(F32))


def _static(cfg):
    keep = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "sliding_window", "num_experts_per_tok",
            "rope_theta", "route_norm", "route_scale", "expert_share")
    return json.dumps({k: cfg.get(k) for k in keep}, sort_keys=True)


def _layer_leaves(w, i):
    p = f"l{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def _embed_scale(cfg):
    return float(np.sqrt(cfg["hidden_size"])) if cfg.get("mup_enabled") \
        else 1.0


def hidden(cfg, w, tokens, *, cast=None):
    """The last layer's output (batch, seq, d), before the final norm,
    of integer ``tokens`` (batch, seq): a Python loop over the layers,
    each one jitted call on that layer's leaves."""
    if cast not in CASTS:
        raise ValueError(f"reference_trinity: unknown cast {cast!r}")
    static = _static(cfg)
    x = _embed(w["embed"], jnp.asarray(tokens, jnp.int32),
               scale=_embed_scale(cfg))
    for i, kind in enumerate(cfg["layer_types"]):
        if kind not in KINDS:
            raise ValueError(f"reference_trinity: unknown layer type "
                             f"{kind!r}")
        x = _layer(x, _layer_leaves(w, i), kind=kind, cfg_json=static,
                   cast=cast)
    return x


def forward(cfg, w, tokens, *, cast=None):
    """Logits (batch, seq, vocab) at every position."""
    return _head(hidden(cfg, w, tokens, cast=cast), w["final_norm"],
                 w["head"], eps=cfg["rms_norm_eps"], cast=cast)


@functools.partial(jax.jit, static_argnames=("kind", "cfg_json"))
def _moves(x, lw, *, kind, cfg_json):
    """Of each position, whether the bias changed its chosen set (as a
    set), from the stream before the layer."""
    cfg = _cfg_of(cfg_json)
    lw32 = {k: v.astype(F32) for k, v in lw.items()
            if k not in ("w_in", "w_out")}
    eps = cfg["rms_norm_eps"]
    a = attention(None, rms_norm(x, lw32["norm1"], eps), lw32, cfg, kind)
    h = rms_norm(x + rms_norm(a, lw32["post_norm1"], eps), lw32["norm2"],
                 eps)
    _, chosen, unbiased = routing(None, h, lw32, cfg)
    return jnp.any(jnp.sort(chosen, axis=-1) != jnp.sort(unbiased, axis=-1),
                   axis=-1)


def bias_moves(cfg, w, tokens, valid):
    """``(moved, total)`` over the routed layers of a forward over
    ``tokens`` (1, seq), of which the first ``valid`` count: the
    (position, layer) pairs whose chosen experts the selection bias
    changed — what the bias does to the choice."""
    static = _static(cfg)
    x = _embed(w["embed"], jnp.asarray(tokens, jnp.int32),
               scale=_embed_scale(cfg))
    moved = total = 0
    for i, kind in enumerate(cfg["layer_types"]):
        lw = _layer_leaves(w, i)
        if "router" in lw:
            m = np.asarray(_moves(x, lw, kind=kind, cfg_json=static))
            moved += int(m[0, :valid].sum())
            total += int(valid)
        x = _layer(x, lw, kind=kind, cfg_json=static, cast=None)
    return moved, total


# ----------------------------------------------------------------------
# serving: the logits at the positions that chose the served tokens
# ----------------------------------------------------------------------
def _round_up(n, to):
    return -(-n // to) * to


def served_logits(cfg, w, rows, casts, cols, *, pad_to=1024):
    """``{cast: [logits]}`` for several casts (``None``: the reference
    itself): for each of ``rows`` (prompt ids, served ids), the logits
    of the vocabulary columns ``cols`` at each position that chose a
    served token, ``(served tokens, len(cols))``.  A row runs alone,
    padded to a multiple of ``pad_to`` positions (of ``Q_BLOCK`` at
    least)."""
    eps = cfg["rms_norm_eps"]
    cols = jnp.asarray(cols, jnp.int32)
    out = {cast: [] for cast in casts}
    for prompt, served in rows:
        seq = list(prompt) + list(served)
        tokens = np.zeros((1, _round_up(len(seq), pad_to)), np.int32)
        tokens[0, :len(seq)] = seq
        # position p-1+j holds the logits that chose served[j]
        at = jnp.asarray(len(prompt) - 1 + np.arange(len(served)))[
            None, :, None]
        for cast in casts:
            x = jnp.take_along_axis(hidden(cfg, w, tokens, cast=cast), at,
                                    axis=1)
            logits = _head(x, w["final_norm"], w["head"], eps=eps, cast=cast)
            out[cast].append(np.asarray(jnp.take(logits[0], cols, axis=-1)))
    return out
