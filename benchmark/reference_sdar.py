"""The plain reference of ``sdar_30b_a3b_chat``: a decoder of full
grouped-query attention layers under the block-causal mask, each
followed by a layer of routed SwiGLU experts, in ``jax.numpy``.

Imports nothing of the program and takes nothing the program made.
float32 throughout, every matrix product at ``precision=HIGHEST``; no
kernels, no cache, no grouping of tokens by expert (every expert in turn
over every token, with weight 0 where the token did not choose it: the
routed block is ``reference_mellum2``'s, which is the same Qwen3-MoE
block), no batching tricks.  The weights come in bfloat16
(``weights_sdar``) and are upcast one layer at a time.

The equations, from the published ``config.json`` (``model_type:
sdar_moe``) and the SDAR release as known (block length and schedule are
listed under ``assumed`` in the configuration's file); written from
knowledge, no network here.  ``x`` is the float32 residual stream at
absolute positions ``p``, ``n(u) = u / sqrt(mean(u^2) + eps) * w``, no
bias anywhere, ``b`` the block length:

* ``x = E[tokens]``.
* every layer: ``h = n1(x)``; ``q = h W_q`` (32 heads of 128), ``k = h
  W_k``, ``v = h W_v`` (4 heads of 128); each head of ``q`` and of ``k``
  normed over its 128 (``q_norm``, ``k_norm``), then rotated at ``p``:
  ``rot(u, p) = u cos(p f) + rotate_half(u) sin(p f)``, ``f_j =
  theta^(-2j / 128)``, ``rotate_half(u) = [-u2, u1]`` over the halves and
  the 64 angles repeated over both.  Query head ``h`` reads key/value
  head ``h // 8``; scores ``q.k / sqrt(128)``, softmax over the keys at
  ``p'`` with ``p' // b <= p // b`` (the block-causal mask: every earlier
  block and the query's own whole block); ``x = x + att W_o``.
* ``h = n2(x)``; ``g = softmax(h W_r)`` over the 128 experts; ``S`` its
  8 largest; ``w_e = g_e / sum_S g``; ``x = x + sum_{e in S} w_e
  (silu(h W_gate_e) * (h W_up_e)) W_down_e``.
* ``logits = n_f(x) W_head`` (untied).

Checking a served request: under the block mask a position's keys and
values depend on its own block and the blocks before it, and every block
of a finished request is clean.  So ONE forward of the final sequence
(``prompt + served tokens``) gives every layer's keys and values as the
program had stored them, and a pass that fed ``tokens`` from ``start`` is
a forward of those positions alone against that prefix (``columns <
start``) and themselves under the mask (``pass_logits``): what a whole
forward of ``[prefix ; the pass's tokens]`` gives at those positions.

``cast`` puts the reference in the program's place with something taken
away (the controls of ``correct``):

* ``"fp8"`` — both inputs of every matrix product rounded to
  float8_e4m3fn under a per-tensor scale; all else float32.
* ``"causal_block"`` — a block's positions attend causally within the
  block (a position does not see the later ones of its own block), as an
  autoregressive decoder's do and a block-diffusion model's do not.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .reference_mellum2 import _mm, experts, rms_norm

F32 = jnp.float32
CASTS = (None, "fp8", "causal_block")
Q_BLOCK = 256


def _static(cfg):
    keep = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "num_experts_per_tok", "rope_theta",
            "block_length")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def rotate(u, pos, theta):
    """``u`` (b, heads, t, dim) at positions ``pos`` (b, t)."""
    dim = u.shape[-1]
    half = dim // 2
    freq = theta ** (-2.0 * np.arange(half, dtype=np.float64) / dim)
    angle = pos.astype(F32)[:, None, :, None] * jnp.asarray(freq, F32)
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    turned = jnp.concatenate([-u[..., half:], u[..., :half]], axis=-1)
    return u * cos + turned * sin


def sees(q_pos, k_pos, block, cast):
    """Whether a query at ``q_pos`` reads the key at ``k_pos``
    (broadcast): the block-causal mask, or under ``causal_block`` the
    causal one."""
    if cast == "causal_block":
        return k_pos <= q_pos
    return k_pos // block <= q_pos // block


def _qkv(cast, h, lw, cfg, pos):
    """Rotated queries (b, hq, t, dim) and keys, values (b, hk, t, dim)
    of ``h`` (b, t, d) at ``pos`` (b, t)."""
    b, t, _ = h.shape
    hq, hk, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    split = lambda z, n: z.reshape(b, t, n, dim).transpose(0, 2, 1, 3)
    q = split(_mm(cast, "btd,fd->btf", h, lw["q"]), hq)
    k = split(_mm(cast, "btd,fd->btf", h, lw["k"]), hk)
    v = split(_mm(cast, "btd,fd->btf", h, lw["v"]), hk)
    return rotate(rms_norm(q, lw["q_norm"], eps), pos, theta), \
        rotate(rms_norm(k, lw["k_norm"], eps), pos, theta), v


def _grouped(z, hq):
    """Keys or values of the key/value heads laid out a query head each."""
    return jnp.repeat(z, hq // z.shape[1], axis=1)


def _out(cast, o, lw):
    b, hq, t, dim = o.shape
    return _mm(cast, "btf,df->btd",
               o.transpose(0, 2, 1, 3).reshape(b, t, hq * dim), lw["o"])


@functools.partial(jax.jit, static_argnames=("cfg_json", "cast"))
def _layer(x, lw, *, cfg_json, cast):
    """One layer over whole sequences from position 0: ``(x', k, v)``."""
    cfg = json.loads(cfg_json)
    lw = {k: v.astype(F32) for k, v in lw.items()}
    eps, dim = cfg["rms_norm_eps"], cfg["head_dim"]
    b, t, _ = x.shape
    at = jnp.arange(t)
    q, k, v = _qkv(cast, rms_norm(x, lw["norm1"], eps), lw, cfg,
                   jnp.broadcast_to(at, (b, t)))
    hq = q.shape[1]
    kg, vg = _grouped(k, hq), _grouped(v, hq)

    def block(q_blk, first):
        at_q = first + jnp.arange(q_blk.shape[2])
        s = _mm(cast, "bhqd,bhkd->bhqk", q_blk, kg) / np.sqrt(dim)
        s = jnp.where(sees(at_q[:, None], at[None, :],
                           cfg["block_length"], cast), s, -jnp.inf)
        return _mm(cast, "bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vg)

    n = min(Q_BLOCK, t)
    if t % n:
        raise ValueError(f"reference_sdar: {t} positions in blocks of {n}")
    blocks = q.reshape(b, hq, t // n, n, dim).transpose(2, 0, 1, 3, 4)
    o = lax.map(lambda a: block(a[0], a[1]), (blocks, jnp.arange(t // n) * n))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, hq, t, dim)
    x = x + _out(cast, o, lw)
    x = x + experts(cast, rms_norm(x, lw["norm2"], eps), lw,
                    cfg["num_experts_per_tok"])
    return x, k, v


@functools.partial(jax.jit, static_argnames=("cfg_json", "cast"))
def _pass_layer(x, lw, k_pre, v_pre, starts, lengths, *, cfg_json, cast):
    """One layer over passes: ``x`` (P, W, d) the fed positions of P
    passes, pass i's from ``starts[i]``, its first ``lengths[i]`` valid;
    ``k_pre``, ``v_pre`` (1, hk, T, dim) the request's stored keys and
    values, of which pass i reads the columns before ``starts[i]``."""
    cfg = json.loads(cfg_json)
    lw = {k: v.astype(F32) for k, v in lw.items()}
    eps, dim, blk = cfg["rms_norm_eps"], cfg["head_dim"], \
        cfg["block_length"]
    P, W, _ = x.shape
    pos = starts[:, None] + jnp.arange(W)[None, :]
    q, k, v = _qkv(cast, rms_norm(x, lw["norm1"], eps), lw, cfg, pos)
    hq = q.shape[1]
    before = jnp.arange(k_pre.shape[2])[None, :] < starts[:, None]
    s_pre = _mm(cast, "phqd,hkd->phqk", q, _grouped(k_pre, hq)[0]) \
        / np.sqrt(dim)
    s_pre = jnp.where(before[:, None, None, :], s_pre, -jnp.inf)
    s_own = _mm(cast, "phqd,phkd->phqk", q, _grouped(k, hq)) / np.sqrt(dim)
    own = sees(pos[:, :, None], pos[:, None, :], blk, cast) \
        & (jnp.arange(W)[None, None, :] < lengths[:, None, None])
    s_own = jnp.where(own[:, None], s_own, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([s_pre, s_own], axis=-1), axis=-1)
    T = k_pre.shape[2]
    o = _mm(cast, "phqk,hkd->phqd", p[..., :T], _grouped(v_pre, hq)[0]) \
        + _mm(cast, "phqk,phkd->phqd", p[..., T:], _grouped(v, hq))
    x = x + _out(cast, o, lw)
    return x + experts(cast, rms_norm(x, lw["norm2"], eps), lw,
                       cfg["num_experts_per_tok"])


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "cast"))
def _head(x, norm_w, head_w, *, eps, cast):
    x = rms_norm(x, norm_w.astype(F32), eps)
    return _mm(cast, "btd,vd->btv", x, head_w.astype(F32))


def _layer_leaves(w, i):
    p = f"l{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def _check_cast(cast):
    if cast not in CASTS:
        raise ValueError(f"reference_sdar: unknown cast {cast!r}")


def stored(cfg, w, tokens, *, cast=None):
    """``(x, [(k, v)] a layer)`` of a forward over integer ``tokens``
    (batch, seq) from position 0: the last layer's output before the
    final norm and every layer's rotated keys and values (batch, hk,
    seq, dim)."""
    _check_cast(cast)
    static = _static(cfg)
    x = _embed(w["embed"], jnp.asarray(tokens, jnp.int32))
    kv = []
    for i in range(cfg["num_hidden_layers"]):
        x, k, v = _layer(x, _layer_leaves(w, i), cfg_json=static, cast=cast)
        kv.append((k, v))
    return x, kv


def forward(cfg, w, tokens, *, cast=None):
    """Logits (batch, seq, vocab) at every position of a whole forward."""
    x, _ = stored(cfg, w, tokens, cast=cast)
    return _head(x, w["final_norm"], w["head"], eps=cfg["rms_norm_eps"],
                 cast=cast)


def _round_up(n, to):
    return -(-n // to) * to


def pass_logits(cfg, w, sequence, passes, cols, *, cast=None, chunk=64,
                pad_to=256):
    """The logits at vocabulary columns ``cols`` of every pass of one
    request, ``(passes, block, len(cols))``: ``sequence`` is the request's
    final token ids (prompt and served tokens, every block clean);
    ``passes`` a list of ``(start, fed tokens)``, each the positions one
    pass fed from ``start``, whole blocks; the logits are each pass's
    last ``block`` positions, as the program hands them back.  One
    forward of ``sequence`` (past ``Q_BLOCK`` positions padded to a
    multiple of ``pad_to``, itself one of ``Q_BLOCK``) stores
    the keys and values; the passes then run ``chunk`` at a time."""
    _check_cast(cast)
    static, eps = _static(cfg), cfg["rms_norm_eps"]
    blk = int(cfg["block_length"])
    width = max(len(t) for _, t in passes)
    n = len(sequence)
    padded = np.zeros((1, n if n <= Q_BLOCK else _round_up(n, pad_to)),
                      np.int32)
    padded[0, :len(sequence)] = sequence
    _, kv = stored(cfg, w, padded, cast=cast)
    cols = jnp.asarray(cols, jnp.int32)
    out = []
    for lo in range(0, len(passes), chunk):
        part = passes[lo:lo + chunk]
        fed = np.zeros((chunk, width), np.int32)
        starts = np.zeros(chunk, np.int32)
        lengths = np.full(chunk, blk, np.int32)
        for i, (start, toks) in enumerate(part):
            fed[i, :len(toks)] = toks
            starts[i], lengths[i] = start, len(toks)
        x = _embed(w["embed"], jnp.asarray(fed))
        for i in range(cfg["num_hidden_layers"]):
            x = _pass_layer(x, _layer_leaves(w, i), *kv[i],
                            jnp.asarray(starts), jnp.asarray(lengths),
                            cfg_json=static, cast=cast)
        at = jnp.asarray(lengths)[:, None] - blk + jnp.arange(blk)[None, :]
        last = jnp.take_along_axis(x, at[..., None], axis=1)
        logits = _head(last, w["final_norm"], w["head"], eps=eps, cast=cast)
        out.append(np.asarray(jnp.take(logits, cols, axis=-1))[:len(part)])
    return np.concatenate(out)
