"""The plain reference of ``granite_4_0_h_micro``: a hybrid decoder of
Mamba-2 and grouped-query attention layers in ``jax.numpy``.

Imports nothing of the program and takes nothing the program made.
float32 throughout, every matrix product at ``precision=HIGHEST``; no
kernels, no cache, no chunking, no batching tricks: the state-space
recurrence is a plain ``lax.scan`` over positions.  The weights come in
bfloat16 (``weights_granite``) and are upcast one layer at a time, so at
the published widths one layer's float32 weights (305 MB) are on the
device at once.

The equations, from the published ``config.json`` and the
``GraniteMoeHybrid`` model (Mamba-2 mixer as in Dao & Gu 2024,
arXiv:2405.21060; written from knowledge, no network here).  d = hidden,
eps = ``rms_norm_eps``:

* ``x = E[tokens] * embedding_multiplier``; no position signal of any
  kind (``position_embedding_type: nope``).
* layer i, of kind ``layer_types[i]``:
  ``x = x + residual_multiplier * mixer_i(RMSNorm(x))``, then
  ``x = x + residual_multiplier * mlp(RMSNorm(x))``, with
  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``.
* ``mlp`` (no routed experts: only the shared MLP):
  ``[g, v] = split(h W_in, 2)``, ``(silu(g) * v) W_out``, no biases.
* ``attention``: ``num_attention_heads`` query heads of ``d / heads``,
  ``num_key_value_heads`` key and value heads, no biases; query head h
  reads key/value head ``h // (heads / kv_heads)``; scores
  ``q.k^T * attention_multiplier`` (not 1/sqrt(head size)); causal
  softmax; ``W_o``.
* ``mamba`` (inner = heads x head size, one group, state N, kernel K):
  ``[z, xBC, dt] = split(h W_in; inner, inner + 2N, heads)``;
  ``xBC_t = silu(b + sum_{j<K} w[:, j] * xBC_{t-K+1+j})`` (depthwise,
  causal, zeros before the sequence); ``[x, B, C] = split(xBC)``;
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per head
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` from ``S_{-1} = 0``,
  ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y * silu(z))`` over all of
  inner with its weight; ``W_out``.
* ``logits = RMSNorm(x) E^T / logits_scaling`` (tied embedding).

Departures from the source: none known.  Not confirmed against the
source here (the configuration file lists them under ``assumed``): the
gated norm normalises over the whole inner width (one group) after the
gate; ``dt`` is not clamped (``time_step_limit`` is (0, inf) by
default); the convolution's bias is on (``mamba_conv_bias``).

``cast`` puts the reference in the program's place one precision down
(the controls of ``correct``):

* ``"ssm_bfloat16"`` — the recurrent state ``S`` is kept in bfloat16:
  rounded after every position, as a bfloat16 state table would hold it.
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
CASTS = (None, "ssm_bfloat16", "fp8")


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


def mlp(cast, h, w_in, w_out):
    g, v = jnp.split(_mm(cast, "btd,fd->btf", h, w_in), 2, axis=-1)
    return _mm(cast, "btf,df->btd", silu(g) * v, w_out)


def attention(cast, h, lw, heads, kv_heads, scale):
    b, t, _ = h.shape
    split = lambda z, n: z.reshape(b, t, n, -1).transpose(0, 2, 1, 3)
    q = split(_mm(cast, "btd,fd->btf", h, lw["q"]), heads)
    k = split(_mm(cast, "btd,fd->btf", h, lw["k"]), kv_heads)
    v = split(_mm(cast, "btd,fd->btf", h, lw["v"]), kv_heads)
    # the repeated-heads form: each key/value head copied for its group
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    s = _mm(cast, "bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = _mm(cast, "bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    return _mm(cast, "btf,df->btd", o.transpose(0, 2, 1, 3).reshape(b, t, -1),
               lw["o"])


def causal_conv(x, w, bias):
    """``y_t = bias + sum_j w[:, j] x_{t-K+1+j}``, zeros before t = 0."""
    k, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(xp[:, j:j + t] * w[:, j] for j in range(k))


def selective_scan(cast, x, dt, a_head, b_mat, c_mat):
    """The recurrence, one position at a time.  ``x`` (b, t, H, P),
    ``dt`` (b, t, H), ``a_head`` (H,), ``b_mat``/``c_mat`` (b, t, N);
    returns ``S_t C_t`` (b, t, H, P)."""
    b, _, heads, p = x.shape
    keep = jnp.bfloat16 if cast == "ssm_bfloat16" else F32

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = s.astype(F32) * jnp.exp(dt_t * a_head)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        s = s.astype(keep)
        y = jnp.sum(s.astype(F32) * c_t[:, None, None, :], axis=-1)
        return s, y

    t_first = lambda z: jnp.moveaxis(z, 1, 0)
    s0 = jnp.zeros((b, heads, p, b_mat.shape[-1]), keep)
    _, y = lax.scan(step, s0, (t_first(x), t_first(dt), t_first(b_mat),
                               t_first(c_mat)))
    return jnp.moveaxis(y, 0, 1)


def mamba(cast, h, lw, heads, p, n, eps):
    b, t, _ = h.shape
    inner = heads * p
    z, xbc, dt = jnp.split(_mm(cast, "btd,fd->btf", h, lw["in_proj"]),
                           [inner, 2 * inner + 2 * n], axis=-1)
    xbc = silu(causal_conv(xbc, lw["conv_w"], lw["conv_b"]))
    x, b_mat, c_mat = jnp.split(xbc, [inner, inner + n], axis=-1)
    x = x.reshape(b, t, heads, p)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    y = selective_scan(cast, x, dt, -jnp.exp(lw["a_log"]), b_mat, c_mat)
    y = (y + lw["d_skip"][:, None] * x).reshape(b, t, inner)
    y = rms_norm(y * silu(z), lw["ssm_norm"], eps)
    return _mm(cast, "btf,df->btd", y, lw["out_proj"])


@functools.partial(jax.jit, static_argnames=("kind", "cfg_items", "cast"))
def _layer(x, lw, *, kind, cfg_items, cast):
    cfg = dict(cfg_items)
    lw = {k: v.astype(F32) for k, v in lw.items()}
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = rms_norm(x, lw["norm1"], eps)
    if kind == "attention":
        h = attention(cast, h, lw, cfg["num_attention_heads"],
                      cfg["num_key_value_heads"],
                      cfg["attention_multiplier"])
    else:
        h = mamba(cast, h, lw, cfg["mamba_n_heads"], cfg["mamba_d_head"],
                  cfg["mamba_d_state"], eps)
    x = x + r * h
    return x + r * mlp(cast, rms_norm(x, lw["norm2"], eps), lw["mlp_in"],
                       lw["mlp_out"])


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(embed, tokens, *, scale):
    return embed[tokens].astype(F32) * scale


@functools.partial(jax.jit, static_argnames=("cfg_items", "cast"))
def _head(x, norm_w, embed, *, cfg_items, cast):
    cfg = dict(cfg_items)
    x = rms_norm(x, norm_w.astype(F32), cfg["rms_norm_eps"])
    return _mm(cast, "btd,vd->btv", x, embed.astype(F32)) \
        / cfg["logits_scaling"]


def _static(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool))))


def hidden(cfg, w, tokens, *, cast=None):
    """The last layer's output (batch, seq, d), before the final norm,
    of integer ``tokens`` (batch, seq): a Python loop over the layers,
    each one jitted call on that layer's leaves."""
    if cast not in CASTS:
        raise ValueError(f"reference_granite: unknown cast {cast!r}")
    items = _static(cfg)
    x = _embed(w["embed"], jnp.asarray(tokens, jnp.int32),
               scale=float(cfg["embedding_multiplier"]))
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"l{i}."
        lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
        x = _layer(x, lw, kind=kind, cfg_items=items, cast=cast)
    return x


def forward(cfg, w, tokens, *, cast=None):
    """Logits (batch, seq, vocab) at every position."""
    return _head(hidden(cfg, w, tokens, cast=cast), w["final_norm"],
                 w["embed"], cfg_items=_static(cfg), cast=cast)


# ----------------------------------------------------------------------
# serving: how far below the reference's best a chosen token lies
# ----------------------------------------------------------------------
def _round_up(n, to):
    return -(-n // to) * to


def token_gaps(cfg, w, rows, *, block=8, cast=None, pad_to=256):
    """``rows`` is a list of (prompt ids, served ids).  One forward over
    each prompt with its served tokens; for every served token, how far
    its reference logit lies below the reference's best at that
    position.  With ``cast`` the token judged at each position is the
    one the lower precision puts first, not the served one.  Returns a
    list (one per row, in the rows' order) of float arrays, one entry
    per served token."""
    return token_gaps_of(cfg, w, rows, (cast,), block=block,
                         pad_to=pad_to)[cast]


def token_gaps_of(cfg, w, rows, casts, *, block=8, pad_to=256):
    """``{cast: gaps}`` as ``token_gaps`` gives them, for several casts
    (``None``: the served tokens) over ONE exact forward.  Rows run
    ``block`` at a time, the shortest first, each block padded to a
    multiple of ``pad_to`` positions; logits are taken at the served
    positions only (at 100,352 words a whole block's would be
    gigabytes)."""
    items = _static(cfg)
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
        logits = _head(x, w["final_norm"], w["embed"], cfg_items=items,
                       cast=None)
        for cast in casts:
            chosen = jnp.asarray(served_ids)
            if cast is not None:
                low = jnp.take_along_axis(hidden(cfg, w, tokens, cast=cast),
                                          at, axis=1)
                chosen = jnp.argmax(_head(low, w["final_norm"], w["embed"],
                                          cfg_items=items, cast=cast),
                                    axis=-1)
            gaps = np.asarray(_gaps(logits, chosen))
            for r, (_, served) in enumerate(part):
                out[cast][order[lo + r]] = gaps[r, :len(served)]
    return out


@jax.jit
def _gaps(logits, chosen):
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, chosen[..., None],
                                      axis=-1)[..., 0]
