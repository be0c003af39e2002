"""The plain reference of ``olmo_hybrid_7b``: a hybrid decoder of Gated
DeltaNet (gated delta-rule linear attention) layers and full multi-head
attention layers with QK-norm, in ``jax.numpy``.

Imports nothing of the program and takes nothing the program made.
float32 throughout, every matrix product at ``precision=HIGHEST``; no
kernels, no cache, no chunking, no batching tricks: the delta rule is a
plain ``lax.scan`` over positions.  The weights come in bfloat16
(``weights_olmo_hybrid``) and are upcast one layer at a time, so at the
published widths one layer's float32 weights (862 MB) are on the device
at once.

The equations, from the published ``config.json`` (``model_type:
olmo_hybrid``), the Gated DeltaNet layer (Yang, Kautz & Hatamizadeh
2024, arXiv:2412.06464) with the negative-eigenvalue range of Grazzi et
al. 2024 (arXiv:2411.12537), and the OLMo 2 block (arXiv:2501.00656);
written from knowledge, no network here.  d = hidden, eps =
``rms_norm_eps``, no biases anywhere (``attention_bias: false``):

* ``x = E[tokens]`` (no multiplier).  No position embedding and no
  rotation of queries or keys (``rope_parameters.rope_theta`` is null).
* layer i, of kind ``layer_types[i]``: ``x = x + RMSNorm(mixer_i(x))``,
  then ``x = x + RMSNorm(mlp(x))``: the norm sits on the sublayer's
  OUTPUT, inside the residual.  ``RMSNorm(u) = u / sqrt(mean(u^2) +
  eps) * w``.
* ``mlp``: ``(silu(x W_gate) * (x W_up)) W_down``.
* ``full_attention``: ``q = RMSNorm_q(x W_q)``, ``k = RMSNorm_k(x
  W_k)``, each norm over ALL the projection's outputs before the split
  into heads; ``v = x W_v``; ``num_attention_heads`` heads of ``d /
  heads`` and as many key/value heads; scores ``q.k^T / sqrt(head
  size)``, causal softmax; ``W_o``.
* ``linear_attention`` (H = ``linear_num_value_heads`` heads, key size
  dk, value size dv, kernel K): ``q~ = x W_q``, ``k~ = x W_k`` (d -> H
  dk), ``v~ = x W_v`` (d -> H dv), each through a causal depthwise
  convolution and silu, ``u_t = silu(sum_{j<K} w[:, j] * u~_{t-K+1+j})``
  (zeros before the sequence, no bias).  Per head ``q = q / |q|_2 *
  dk^-1/2``, ``k = k / |k|_2`` (1e-6 under the root).  ``beta_t =
  sigmoid(x_t W_b)``, doubled where ``linear_allow_neg_eigval`` is set
  (beta in (0, 2): the transition ``I - beta k k^T`` then has an
  eigenvalue in (-1, 1)).  ``g_t = -exp(A_log) * softplus(x_t W_a +
  dt_bias)``, ``alpha_t = exp(g_t)`` in (0, 1), one scalar a head.
  State ``S`` (dk x dv) a head, from ``S_{-1} = 0``:
  ``S' = alpha_t S_{t-1}``; ``r_t = v_t - S'^T k_t``;
  ``S_t = S' + beta_t k_t r_t^T``; ``o_t = S_t^T q_t``.
  Output ``RMSNorm_head(o) * silu(x W_g)`` (the norm over each head's dv
  values with one weight of dv shared by the heads), then ``W_o``.
* ``logits = RMSNorm(x) W_head`` (untied).

Departures from the source: none known in the mathematics.  In the
LAYOUT of the leaves two tensors are stacked that the checkpoint keeps
apart: ``conv_w`` is the three depthwise kernels of q, k and v one
after the other (H dk + H dk + H dv channels), and ``mlp_in`` is
``W_gate`` over ``W_up``.  What the published config has no key for
(no rotation, the norm's place, the QK-norm's extent, no convolution
bias) is the family's convention and is listed under ``assumed`` in the
configuration file.

``cast`` puts the reference in the program's place one precision down
(the controls of ``correct``):

* ``"delta_bfloat16"`` — the delta-rule state ``S`` is kept in
  bfloat16: rounded after every position, as a bfloat16 table would
  hold it.
* ``"fp8"`` — both inputs of every matrix product rounded to
  float8_e4m3fn under a per-tensor scale; all else float32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32
CASTS = (None, "delta_bfloat16", "fp8")
KINDS = ("linear_attention", "full_attention")


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


def l2_normalised(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def mlp(cast, h, w_in, w_out):
    g, u = jnp.split(_mm(cast, "btd,fd->btf", h, w_in), 2, axis=-1)
    return _mm(cast, "btf,df->btd", silu(g) * u, w_out)


def attention(cast, h, lw, heads, eps):
    b, t, _ = h.shape
    split = lambda z: z.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)
    q = split(rms_norm(_mm(cast, "btd,fd->btf", h, lw["q"]), lw["q_norm"],
                       eps))
    k = split(rms_norm(_mm(cast, "btd,fd->btf", h, lw["k"]), lw["k_norm"],
                       eps))
    v = split(_mm(cast, "btd,fd->btf", h, lw["v"]))
    s = _mm(cast, "bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = _mm(cast, "bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    return _mm(cast, "btf,df->btd", o.transpose(0, 2, 1, 3).reshape(b, t, -1),
               lw["o"])


def causal_conv(x, w):
    """``y_t = sum_j w[:, j] x_{t-K+1+j}``, zeros before t = 0."""
    k, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[:, j] for j in range(k))


def delta_rule(cast, q, k, v, g, beta):
    """The recurrence, one position at a time.  ``q``, ``k`` (b, t, H,
    dk) already normalised, ``v`` (b, t, H, dv), ``g`` (log decay) and
    ``beta`` (b, t, H); returns ``o`` (b, t, H, dv)."""
    b, _, heads, dk = k.shape
    keep = jnp.bfloat16 if cast == "delta_bfloat16" else F32

    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        s = s.astype(F32) * jnp.exp(g_t)[..., None, None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=HIGHEST)
        s = s + b_t[..., None, None] * k_t[..., :, None] * r[..., None, :]
        s = s.astype(keep)
        o = jnp.einsum("bhkv,bhk->bhv", s.astype(F32), q_t,
                       precision=HIGHEST)
        return s, o

    t_first = lambda z: jnp.moveaxis(z, 1, 0)
    s0 = jnp.zeros((b, heads, dk, v.shape[-1]), keep)
    _, o = lax.scan(step, s0, tuple(t_first(z) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def beta_of(cast, h, b_w, neg_eigval):
    """The correction's strength (b, t, H): in (0, 1), or doubled."""
    beta = jax.nn.sigmoid(_mm(cast, "btd,hd->bth", h, b_w))
    return 2.0 * beta if neg_eigval else beta


def log_decay(cast, h, lw):
    """``g`` (b, t, H), the log of the decay: negative."""
    return -jnp.exp(lw["a_log"]) * jax.nn.softplus(
        _mm(cast, "btd,hd->bth", h, lw["a"]) + lw["dt_bias"])


def delta_net(cast, h, lw, heads, dk, dv, neg_eigval, eps):
    b, t, _ = h.shape
    qkv = jnp.concatenate([_mm(cast, "btd,fd->btf", h, lw[n])
                           for n in ("q", "k", "v")], axis=-1)
    qkv = silu(causal_conv(qkv, lw["conv_w"]))
    q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
    q = l2_normalised(q.reshape(b, t, heads, dk)) / np.sqrt(dk)
    k = l2_normalised(k.reshape(b, t, heads, dk))
    o = delta_rule(cast, q, k, v.reshape(b, t, heads, dv),
                   log_decay(cast, h, lw),
                   beta_of(cast, h, lw["b"], neg_eigval))
    o = rms_norm(o, lw["o_norm"], eps).reshape(b, t, heads * dv)
    return _mm(cast, "btf,df->btd",
               o * silu(_mm(cast, "btd,fd->btf", h, lw["g"])), lw["o"])


@functools.partial(jax.jit, static_argnames=("kind", "cfg_items", "cast"))
def _layer(x, lw, *, kind, cfg_items, cast):
    cfg = dict(cfg_items)
    lw = {k: v.astype(F32) for k, v in lw.items()}
    eps = cfg["rms_norm_eps"]
    if kind == "full_attention":
        h = attention(cast, x, lw, cfg["num_attention_heads"], eps)
    else:
        h = delta_net(cast, x, lw, cfg["linear_num_value_heads"],
                      cfg["linear_key_head_dim"],
                      cfg["linear_value_head_dim"],
                      cfg["linear_allow_neg_eigval"], eps)
    x = x + rms_norm(h, lw["norm1"], eps)
    return x + rms_norm(mlp(cast, x, lw["mlp_in"], lw["mlp_out"]),
                        lw["norm2"], eps)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "cast"))
def _head(x, norm_w, head_w, *, eps, cast):
    x = rms_norm(x, norm_w.astype(F32), eps)
    return _mm(cast, "btd,vd->btv", x, head_w.astype(F32))


def _static(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool))))


def hidden(cfg, w, tokens, *, cast=None):
    """The last layer's output (batch, seq, d), before the final norm,
    of integer ``tokens`` (batch, seq): a Python loop over the layers,
    each one jitted call on that layer's leaves."""
    if cast not in CASTS:
        raise ValueError(f"reference_olmo_hybrid: unknown cast {cast!r}")
    items = _static(cfg)
    x = _embed(w["embed"], jnp.asarray(tokens, jnp.int32))
    for i, kind in enumerate(cfg["layer_types"]):
        if kind not in KINDS:
            raise ValueError(f"reference_olmo_hybrid: unknown layer type "
                             f"{kind!r}")
        p = f"l{i}."
        lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
        x = _layer(x, lw, kind=kind, cfg_items=items, cast=cast)
    return x


@functools.partial(jax.jit, static_argnames=("neg_eigval",))
def _beta_above_one(x, b_w, *, neg_eigval):
    beta = beta_of(None, x, b_w.astype(F32), neg_eigval)
    return jnp.sum(beta > 1.0), beta.size


def beta_share_above_one(cfg, w, tokens):
    """The share of (position, head) pairs, over every
    ``linear_attention`` layer of a forward over ``tokens``, at which
    ``beta > 1``: where the transition ``I - beta k k^T`` has a negative
    eigenvalue.  0 where ``linear_allow_neg_eigval`` is off."""
    items = _static(cfg)
    x = _embed(w["embed"], jnp.asarray(tokens, jnp.int32))
    above = total = 0
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"l{i}."
        lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
        if kind == "linear_attention":
            n, of = _beta_above_one(
                x, lw["b"], neg_eigval=bool(cfg["linear_allow_neg_eigval"]))
            above, total = above + int(n), total + int(of)
        x = _layer(x, lw, kind=kind, cfg_items=items, cast=None)
    return above / total if total else 0.0


def forward(cfg, w, tokens, *, cast=None):
    """Logits (batch, seq, vocab) at every position."""
    return _head(hidden(cfg, w, tokens, cast=cast), w["final_norm"],
                 w["head"], eps=cfg["rms_norm_eps"], cast=cast)


# ----------------------------------------------------------------------
# serving: how far below the reference's best a chosen token lies
# ----------------------------------------------------------------------
def _round_up(n, to):
    return -(-n // to) * to


def token_gaps_of(cfg, w, rows, casts, *, block=8, pad_to=256):
    """``{cast: gaps}`` for several casts (``None``: the served tokens)
    over ONE exact forward, as ``reference_granite.token_gaps_of``.
    ``rows`` is a list of (prompt ids, served ids); for every served
    token, how far its reference logit lies below the reference's best
    at that position (with a cast: the token the lower precision puts
    first, in the served one's place).  Rows run ``block`` at a time,
    the shortest first, each block padded to a multiple of ``pad_to``
    positions; logits are taken at the served positions only."""
    eps = cfg["rms_norm_eps"]
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
            chosen = jnp.asarray(served_ids)
            if cast is not None:
                low = jnp.take_along_axis(hidden(cfg, w, tokens, cast=cast),
                                          at, axis=1)
                chosen = jnp.argmax(_head(low, w["final_norm"], w["head"],
                                          eps=eps, cast=cast), axis=-1)
            gaps = np.asarray(_gaps(logits, chosen))
            for r, (_, served) in enumerate(part):
                out[cast][order[lo + r]] = gaps[r, :len(served)]
    return out


@jax.jit
def _gaps(logits, chosen):
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, chosen[..., None],
                                      axis=-1)[..., 0]
