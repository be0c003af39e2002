"""The plain reference of ``mellum2_12b_a2_5b``: a decoder of sliding-
window and full grouped-query attention layers, each followed by a layer
of routed SwiGLU experts, in ``jax.numpy``.

Imports nothing of the program and takes nothing the program made.
float32 throughout, every matrix product at ``precision=HIGHEST``; no
kernels, no cache, no ring (a whole (T, T) mask with both bounds), no
grouping of tokens by expert (every expert in turn over every token,
with weight 0 where the token did not choose it), no batching tricks.
The weights come in bfloat16 (``weights_mellum2``) and are upcast one
layer at a time, so at the published widths one layer's float32 weights
(1.67 GB) are on the device at once; queries run in blocks so that a
sequence of 8,448 positions fits.

The equations, from the published ``config.json`` (``model_type:
mellum``) and the conventions of its lineage (listed under ``assumed``
in the configuration's file); written from knowledge, no network here.
``x`` is the float32 residual stream, ``n(u) = u / sqrt(mean(u^2) +
eps) * w``, no bias anywhere:

* ``x = E[tokens]``.
* layer i, of kind ``layer_types[i]``: ``h = n1(x)``; ``q = h W_q`` (32
  heads of 128), ``k = h W_k``, ``v = h W_v`` (4 heads of 128); each
  head of ``q`` and of ``k`` normed over its 128 (``q_norm``, ``k_norm``:
  one weight of 128 each); then rotated at its absolute position ``p``:
  ``rot(u, p) = u cos(p f) a + rotate_half(u) sin(p f) a`` with
  ``rotate_half(u) = [-u2, u1]`` over the halves of the 128 and the 64
  angles repeated over both.  ``sliding_attention``: ``f_j = theta^(-2j
  / 128)``, ``a = 1``.  ``full_attention`` (YaRN, applied at every
  length): ``e_j = theta^(-2j / 128)``; ``dim(r) = 128 ln(L0 / (2 pi
  r)) / (2 ln theta)`` with ``L0`` the original length; ``lo =
  floor(dim(beta_fast))``, ``hi = ceil(dim(beta_slow))``, clipped to
  [0, 127]; ``ramp_j = clip((j - lo) / (hi - lo), 0, 1)``; ``f_j = (e_j
  / factor) ramp_j + e_j (1 - ramp_j)``; ``a = attention_factor``.
  Query head ``h`` reads key/value head ``h // 8``; scores ``q.k /
  sqrt(128)``, softmax over the keys at ``p' <= p`` (full) and
  ``p - p' < sliding_window`` besides (sliding); ``x = x + att W_o``.
* ``h = n2(x)``; ``g = softmax(h W_r)`` over the 64 experts; ``S`` its 8
  largest (the lower index first among equals); ``w_e = g_e / sum_S g``;
  ``x = x + sum_{e in S} w_e (silu(h W_gate_e) * (h W_up_e)) W_down_e``.
* ``logits = n_f(x) W_head`` (untied).

``cast`` puts the reference in the program's place with something taken
away (the controls of ``correct``):

* ``"fp8"`` — both inputs of every matrix product rounded to
  float8_e4m3fn under a per-tensor scale; all else float32.
* ``"top7"`` — each token's eighth expert left out and the other seven
  NOT renormalised for it: a piece of the mathematics missing.
* ``"window_off"`` — sliding layers read the whole prefix: whether the
  comparison sees the window at all.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32
CASTS = (None, "fp8", "top7", "window_off")
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


def rotary_table(cfg, kind):
    """``(frequencies (head_dim / 2,) float64, a)`` of a kind of layer."""
    r = cfg["rope_parameters"][kind]
    dim, theta = cfg["head_dim"], float(r["rope_theta"])
    j = np.arange(dim // 2, dtype=np.float64)
    e = theta ** (-2.0 * j / dim)
    if r.get("rope_type", "default") == "default":
        return e, 1.0
    if r["rope_type"] != "yarn":
        raise ValueError(f"reference_mellum2: rope_type {r['rope_type']!r}")

    def dim_of(turns):
        return dim * np.log(r["original_max_position_embeddings"]
                            / (2.0 * np.pi * turns)) / (2.0 * np.log(theta))

    lo = max(int(np.floor(dim_of(r["beta_fast"]))), 0)
    hi = min(int(np.ceil(dim_of(r["beta_slow"]))), dim - 1)
    ramp = np.clip((j - lo) / (hi - lo), 0.0, 1.0)
    return e / r["factor"] * ramp + e * (1.0 - ramp), \
        float(r["attention_factor"])


def rotate(u, freq, a):
    """``u`` (b, heads, t, dim) at positions 0..t-1."""
    half = u.shape[-1] // 2
    angle = jnp.arange(u.shape[2], dtype=F32)[:, None] \
        * jnp.asarray(freq, F32)[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1) * a
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1) * a
    turned = jnp.concatenate([-u[..., half:], u[..., :half]], axis=-1)
    return u * cos + turned * sin


def attention(cast, h, lw, cfg, kind):
    b, t, _ = h.shape
    hq, hk, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    freq, a = rotary_table(cfg, kind)
    split = lambda z, n: z.reshape(b, t, n, dim).transpose(0, 2, 1, 3)
    q = split(_mm(cast, "btd,fd->btf", h, lw["q"]), hq)
    k = split(_mm(cast, "btd,fd->btf", h, lw["k"]), hk)
    v = split(_mm(cast, "btd,fd->btf", h, lw["v"]), hk)
    q = rotate(rms_norm(q, lw["q_norm"], eps), freq, a)
    k = rotate(rms_norm(k, lw["k_norm"], eps), freq, a)
    # query head h reads key/value head h // (hq / hk)
    k, v = (jnp.repeat(z, hq // hk, axis=1) for z in (k, v))
    window = cfg["sliding_window"] \
        if kind == "sliding_attention" and cast != "window_off" else t
    at_k = jnp.arange(t)

    def block(q_blk, first):
        at_q = first + jnp.arange(q_blk.shape[2])
        back = at_q[:, None] - at_k[None, :]
        s = _mm(cast, "bhqd,bhkd->bhqk", q_blk, k) / np.sqrt(dim)
        s = jnp.where((back >= 0) & (back < window), s, -jnp.inf)
        return _mm(cast, "bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    n = min(Q_BLOCK, t)
    if t % n:
        raise ValueError(f"reference_mellum2: {t} positions in blocks of {n}")
    blocks = q.reshape(b, hq, t // n, n, dim).transpose(2, 0, 1, 3, 4)
    o = lax.map(lambda a: block(a[0], a[1]),
                (blocks, jnp.arange(t // n) * n))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, hq, t, dim)
    return _mm(cast, "btf,df->btd", o.transpose(0, 2, 1, 3).reshape(b, t, -1),
               lw["o"])


def routing(cast, h, router, top_k):
    """``(weights (b, t, E), probabilities)``: each token's weight on
    every expert, 0 where it did not choose it."""
    g = jax.nn.softmax(_mm(cast, "btd,de->bte", h, router), axis=-1)
    top_p, top_e = lax.top_k(g, top_k)
    total = jnp.sum(top_p, axis=-1, keepdims=True)
    if cast == "top7":
        top_p = top_p.at[..., -1].set(0.0)
    chosen = jax.nn.one_hot(top_e, g.shape[-1], dtype=F32)
    return jnp.einsum("btk,btke->bte", top_p / total, chosen,
                      precision=HIGHEST), g


def experts(cast, h, lw, top_k):
    weights, _ = routing(cast, h, lw["router"], top_k)

    def one(acc, e):
        w_in, w_out, w_e = e
        g, u = jnp.split(_mm(cast, "btd,df->btf", h, w_in), 2, axis=-1)
        y = _mm(cast, "btf,fd->btd", silu(g) * u, w_out)
        return acc + w_e[..., None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(h),
                      (lw["w_in"], lw["w_out"],
                       jnp.moveaxis(weights, -1, 0)))
    return out


def _cfg_of(cfg_json):
    import json
    return json.loads(cfg_json)


@functools.partial(jax.jit, static_argnames=("kind", "cfg_json", "cast"))
def _layer(x, lw, *, kind, cfg_json, cast):
    cfg = _cfg_of(cfg_json)
    lw = {k: v.astype(F32) for k, v in lw.items()}
    eps = cfg["rms_norm_eps"]
    x = x + attention(cast, rms_norm(x, lw["norm1"], eps), lw, cfg, kind)
    return x + experts(cast, rms_norm(x, lw["norm2"], eps), lw,
                       cfg["num_experts_per_tok"])


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "cast"))
def _head(x, norm_w, head_w, *, eps, cast):
    x = rms_norm(x, norm_w.astype(F32), eps)
    return _mm(cast, "btd,vd->btv", x, head_w.astype(F32))


def _static(cfg):
    import json
    keep = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "sliding_window", "num_experts_per_tok",
            "rope_parameters")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def _layer_leaves(w, i):
    p = f"l{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def hidden(cfg, w, tokens, *, cast=None):
    """The last layer's output (batch, seq, d), before the final norm,
    of integer ``tokens`` (batch, seq): a Python loop over the layers,
    each one jitted call on that layer's leaves."""
    if cast not in CASTS:
        raise ValueError(f"reference_mellum2: unknown cast {cast!r}")
    static = _static(cfg)
    x = _embed(w["embed"], jnp.asarray(tokens, jnp.int32))
    for i, kind in enumerate(cfg["layer_types"]):
        if kind not in KINDS:
            raise ValueError(f"reference_mellum2: unknown layer type "
                             f"{kind!r}")
        x = _layer(x, _layer_leaves(w, i), kind=kind, cfg_json=static,
                   cast=cast)
    return x


def forward(cfg, w, tokens, *, cast=None):
    """Logits (batch, seq, vocab) at every position."""
    return _head(hidden(cfg, w, tokens, cast=cast), w["final_norm"],
                 w["head"], eps=cfg["rms_norm_eps"], cast=cast)


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def _margins(x, norm_w, router, *, top_k, eps):
    h = rms_norm(x, norm_w.astype(F32), eps)
    g = jax.nn.softmax(_mm(None, "btd,de->bte", h, router.astype(F32)),
                       axis=-1)
    top = lax.top_k(g, top_k + 1)[0]
    return (top[..., -2] - top[..., -1]) / top[..., -2]


def near_ties(cfg, w, tokens, valid, margin=2.0 ** -8):
    """``(near, total)`` over every layer of a forward over ``tokens``
    (1, seq), of which the first ``valid`` count: the (position, layer)
    pairs at which the eighth and the ninth expert's probabilities lie
    within ``margin`` of each other, relatively (one bfloat16 rounding
    by default: where a program that rounds differently may choose the
    other).  The router reads the stream before its layer's experts, so
    each layer's margins come from the reference's own stream: the
    attention half of the layer is applied first."""
    static = _static(cfg)
    eps, top_k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    x = _embed(w["embed"], jnp.asarray(tokens, jnp.int32))
    near = total = 0
    for i, kind in enumerate(cfg["layer_types"]):
        lw = _layer_leaves(w, i)
        mid = _attention_half(x, lw, kind=kind, cfg_json=static)
        m = np.asarray(_margins(mid, lw["norm2"], lw["router"],
                                top_k=top_k, eps=eps))[0, :valid]
        near, total = near + int((m < margin).sum()), total + int(m.size)
        x = _layer(x, lw, kind=kind, cfg_json=static, cast=None)
    return near, total


@functools.partial(jax.jit, static_argnames=("kind", "cfg_json"))
def _attention_half(x, lw, *, kind, cfg_json):
    cfg = _cfg_of(cfg_json)
    lw = {k: v.astype(F32) for k, v in lw.items()
          if k not in ("w_in", "w_out")}
    return x + attention(None, rms_norm(x, lw["norm1"], cfg["rms_norm_eps"]),
                         lw, cfg, kind)


# ----------------------------------------------------------------------
# serving: how far below the reference's best a chosen token lies
# ----------------------------------------------------------------------
def _round_up(n, to):
    return -(-n // to) * to


def token_gaps_of(cfg, w, rows, casts, cols, *, block=1, pad_to=1024):
    """``{cast: [(gaps, logits)]}`` for several casts (``None``: the
    served tokens) over ONE exact forward, as
    ``reference_granite.token_gaps_of``.  ``rows`` is a list of (prompt
    ids, served ids).  ``gaps``: for every served token, how far its
    reference logit lies below the reference's best at that position
    (with a cast: the token the control puts first, in the served one's
    place).  ``logits`` (served tokens, ``len(cols)``): at the position
    that chose each served token, the logits of the vocabulary columns
    ``cols`` — the reference's own under ``None``, the control's under a
    cast — for whoever holds a program's logits against them.  Rows run
    ``block`` at a time, the shortest first, each block padded to a
    multiple of ``pad_to`` positions (of ``Q_BLOCK`` at least); logits
    are taken at the served positions only."""
    eps = cfg["rms_norm_eps"]
    cols = jnp.asarray(cols, jnp.int32)
    order = sorted(range(len(rows)),
                   key=lambda i: len(rows[i][0]) + len(rows[i][1]))
    out = {cast: [None] * len(rows) for cast in casts}
    for lo in range(0, len(order), block):
        part = [rows[i] for i in order[lo:lo + block]]
        t_max = _round_up(max(len(p) + len(s) for p, s in part), pad_to)
        n_max = max(len(s) for _, s in part)
        tokens = np.zeros((block, t_max), np.int32)
        where = np.zeros((block, n_max), np.int32)
        served_ids = np.zeros((block, n_max), np.int32)
        for r, (prompt, served) in enumerate(part):
            seq = list(prompt) + list(served)
            tokens[r, :len(seq)] = seq
            # position p-1+j holds the logits that chose served[j]
            where[r, :len(served)] = len(prompt) - 1 + np.arange(len(served))
            served_ids[r, :len(served)] = served
        at = jnp.asarray(where)[..., None]
        x = jnp.take_along_axis(hidden(cfg, w, tokens), at, axis=1)
        logits = _head(x, w["final_norm"], w["head"], eps=eps, cast=None)
        for cast in casts:
            chosen, seen = jnp.asarray(served_ids), logits
            if cast is not None:
                low = jnp.take_along_axis(hidden(cfg, w, tokens, cast=cast),
                                          at, axis=1)
                seen = _head(low, w["final_norm"], w["head"], eps=eps,
                             cast=cast)
                chosen = jnp.argmax(seen, axis=-1)
            gaps = np.asarray(_gaps(logits, chosen))
            kept = np.asarray(jnp.take(seen, cols, axis=-1))
            for r, (_, served) in enumerate(part):
                out[cast][order[lo + r]] = (gaps[r, :len(served)],
                                            kept[r, :len(served)])
    return out


@jax.jit
def _gaps(logits, chosen):
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, chosen[..., None],
                                      axis=-1)[..., 0]
