"""The plain reference: one post-LN transformer in ``jax.numpy``.

It serves both configurations (``bert_large`` bidirectional and trained,
``bertgen_large`` causal and served), imports nothing of the program and
takes nothing the program made.  float32 throughout, every matrix
product at ``precision=HIGHEST``; no kernels, no cache, no batching
tricks.  Layers run under ``lax.scan`` with ``jax.checkpoint`` so that
the training step at the timed size fits beside nothing else on a chip.

Follows the source (Devlin et al. 2018; Rothe et al. 2020) with the
departures the configuration files list: tanh GELU, LayerNorm eps 1e-5,
an untied ``Dense(vocab)`` head over every position, no dropout on the
attention probabilities, no token types added.

``cast`` puts the reference in the program's place at a lower precision
(the control of ``correct``):

* ``"bfloat16"`` — weights and activations in bfloat16; LayerNorm and
  softmax take their statistics in float32 and hand back bfloat16.
* ``"fp8"`` — both inputs of every matrix product rounded to
  float8_e4m3fn under a per-tensor scale (straight-through gradient);
  all else float32.

Dropout is part of what the trained configuration computes, so the
reference draws the same masks from the same keys: a step's key is the
next ``jax.random.split`` of ``PRNGKey(rng_seed)``; site ``c`` of a step
uses ``fold_in(step_key, c)``; the embedding (site 0) takes
``jax.random.bernoulli``; the two residual epilogues of layer ``i``
(sites 1+2i and 2+2i) keep element ``n`` (row-major over rows x hidden)
when the first word of threefry2x32(key, (n, 0)) is below keep * 2**32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights as weights_mod

HIGHEST = lax.Precision.HIGHEST
LN_EPS = 1e-5


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------
def _fp8(x):
    """Round to float8_e4m3fn under a per-tensor scale; the gradient
    passes straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + lax.stop_gradient(q - x)


def _mm(cast, spec, a, b):
    if cast == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def layer_norm(x, g, b):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * lax.rsqrt(var + LN_EPS) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)
    return y.astype(x.dtype)


def gelu(x):
    c = np.sqrt(2.0 / np.pi).astype(np.float32)
    x32 = x.astype(jnp.float32)
    y = 0.5 * x32 * (1.0 + jnp.tanh(c * (x32 + 0.044715 * x32 ** 3)))
    return y.astype(x.dtype)


def attention(cast, q, k, v, heads, causal):
    b, t, d = q.shape
    dh = d // heads
    split = lambda z: z.reshape(b, t, heads, dh).transpose(0, 2, 1, 3)
    q, k, v = split(q), split(k), split(v)
    s = _mm(cast, "bhqd,bhkd->bhqk", q, k).astype(jnp.float32) \
        / np.float32(np.sqrt(dh))
    if causal:
        keep = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = _mm(cast, "bhqk,bhkd->bhqd", p, v)
    return o.transpose(0, 2, 1, 3).reshape(b, t, d)


# ----------------------------------------------------------------------
# dropout masks, as the trained configuration defines them
# ----------------------------------------------------------------------
_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32, 20 rounds (Salmon et al. 2011), on uint32."""
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(0x1BD11BDA))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for r in range(5):
        for rot in _ROT[r % 2]:
            x0 = x0 + x1
            x1 = (x1 << rot) | (x1 >> (32 - rot))
            x1 = x1 ^ x0
        x0 = x0 + ks[(r + 1) % 3]
        x1 = x1 + ks[(r + 2) % 3] + jnp.uint32(r + 1)
    return x0, x1


def epilogue_keep(key, shape, keep):
    """Boolean keep-mask of a residual epilogue over ``shape`` (...,
    hidden), element n row-major."""
    words = jax.random.key_data(key).reshape(-1).astype(jnp.uint32)
    n = int(np.prod(shape))
    ctr = lax.iota(jnp.uint32, n).reshape(shape)
    bits, _ = threefry2x32(words[0], words[1], ctr, jnp.zeros_like(ctr))
    thresh = min((1 << 32) - 1, int(round(keep * (1 << 32))))
    return bits < jnp.uint32(thresh)


def step_keys(rng_seed, n):
    """The keys of the first ``n`` training steps."""
    key = jax.random.PRNGKey(int(rng_seed))
    out = []
    for _ in range(n):
        key, sub = jax.random.split(key)
        out.append(sub)
    return out


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
_LAYER_LEAVES = tuple(weights_mod.layer_shapes(1, 1))


def _stack_layers(w, n_layers):
    return {name: jnp.stack([w[f"l{i}.{name}"] for i in range(n_layers)])
            for name in _LAYER_LEAVES}


def forward(w, tokens, cfg, *, cast=None, dropout_key=None):
    """Logits (batch, seq, vocab) of integer ``tokens`` (batch, seq).
    ``dropout_key`` (a step key) turns the trained configuration's
    dropout on."""
    heads = cfg["num_attention_heads"]
    causal = bool(cfg.get("causal"))
    p_drop = float(cfg.get("hidden_dropout_prob", 0.0)) \
        if dropout_key is not None else 0.0
    keep = 1.0 - p_drop
    n_layers = cfg["num_hidden_layers"]
    if cast == "bfloat16":
        w = {k: v.astype(jnp.bfloat16) for k, v in w.items()}
    b, t = tokens.shape

    x = w["word_embed"][tokens] + w["pos_embed"][:t][None]
    x = layer_norm(x, w["embed_ln_g"], w["embed_ln_b"])
    if p_drop:
        m = jax.random.bernoulli(jax.random.fold_in(dropout_key, 0), keep,
                                 x.shape)
        x = jnp.where(m, x / keep, 0.0).astype(x.dtype)

    def epilogue(h, bias, res, g, beta, site):
        h = h + bias
        if p_drop:
            m = epilogue_keep(jax.random.fold_in(dropout_key, site),
                              h.shape, keep)
            h = jnp.where(m, h * (1.0 / keep), 0.0).astype(h.dtype)
        return layer_norm(res + h, g, beta)

    def layer(x, lw_i):
        lw, i = lw_i
        dense = lambda z, name: _mm(cast, "btd,fd->btf", z,
                                    lw[name + "_w"]) + lw[name + "_b"]
        a = attention(cast, dense(x, "q"), dense(x, "k"), dense(x, "v"),
                      heads, causal)
        a = _mm(cast, "btd,fd->btf", a, lw["proj_w"])
        x = epilogue(a, lw["proj_b"], x, lw["ln1_g"], lw["ln1_b"],
                     1 + 2 * i)
        h = gelu(dense(x, "ffn1"))
        h = _mm(cast, "btf,df->btd", h, lw["ffn2_w"])
        x = epilogue(h, lw["ffn2_b"], x, lw["ln2_g"], lw["ln2_b"],
                     2 + 2 * i)
        return x, None

    x, _ = lax.scan(jax.checkpoint(layer), x,
                    (_stack_layers(w, n_layers), jnp.arange(n_layers)))
    return _mm(cast, "btd,vd->btv", x, w["out_w"]) + w["out_b"]


# ----------------------------------------------------------------------
# training: loss, gradients, MXNet's adam
# ----------------------------------------------------------------------
def mlm_loss(w, tokens, labels, cfg, cast, dropout_key):
    logits = forward(w, tokens, cfg, cast=cast,
                     dropout_key=dropout_key).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("cfg_items", "cast", "hp_items"),
                   donate_argnums=(0, 1, 2))
def _train_step(w, m, v, t, tokens, labels, key, *, cfg_items, cast,
                hp_items):
    cfg, hp = dict(cfg_items), dict(hp_items)
    loss, g = jax.value_and_grad(mlm_loss)(w, tokens, labels, cfg, cast, key)
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["epsilon"]
    lr = hp["learning_rate"] * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    m = {k: b1 * m[k] + (1.0 - b1) * g[k] for k in w}
    v = {k: b2 * v[k] + (1.0 - b2) * g[k] * g[k] for k in w}
    w = {k: w[k] - lr * m[k] / (jnp.sqrt(v[k]) + eps) for k in w}
    return loss, w, m, v, leaf_norms(g)


@jax.jit
def _change_norms(w, w0):
    return leaf_norms({k: w[k] - w0[k] for k in w})


def _static(cfg):
    """The configuration's numbers as a hashable jit argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool))))


ADAM = {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}


def train(cfg, seed, rng_seed, batches, learning_rate, *, cast=None):
    """Follow the first ``len(batches)`` steps from the seed's weights.
    ``batches`` is a list of (tokens, labels) integer arrays.  Returns
    ``{"losses": [...], "grad_norms": {leaf: norm of the FIRST step's
    gradient}, "change_norms": {leaf: norm of (w_after - w_before)}}``,
    all as Python floats."""
    hp = dict(ADAM, learning_rate=float(learning_rate))
    cfg_items = _static(cfg)
    w = weights_mod.make(cfg, seed)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    keys = step_keys(rng_seed, len(batches))
    losses, grad_norms = [], None
    for t, ((tokens, labels), key) in enumerate(zip(batches, keys), 1):
        loss, w, m, v, gn = _train_step(
            w, m, v, jnp.float32(t), jnp.asarray(tokens, jnp.int32),
            jnp.asarray(labels, jnp.int32), key, cfg_items=cfg_items,
            cast=cast, hp_items=tuple(sorted(hp.items())))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(x) for k, x in gn.items()}
    change = _change_norms(w, weights_mod.make(cfg, seed))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: float(x) for k, x in change.items()}}


# ----------------------------------------------------------------------
# serving: how far below the reference's best a chosen token lies
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _score(w, tokens, chosen, *, cfg_items):
    logits = forward(w, tokens, dict(cfg_items)).astype(jnp.float32)
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return best - at


@functools.partial(jax.jit, static_argnames=("cfg_items", "cast"))
def _first_choice(w, tokens, *, cfg_items, cast):
    logits = forward(w, tokens, dict(cfg_items), cast=cast)
    return jnp.argmax(logits.astype(jnp.float32), axis=-1)


def token_gaps(cfg, w, rows, *, block=8, cast=None):
    """``rows`` is a list of (prompt ids, served ids).  One forward over
    each prompt with its served tokens; for every served token, how far
    its reference logit lies below the reference's best at that
    position.  With ``cast`` the token judged at each position is the
    one the lower precision puts first, not the served one.  Returns a
    list (one per row) of float arrays, one entry per served token."""
    cfg_items = _static(cfg)
    t_max = cfg["max_position_embeddings"]
    out = []
    for lo in range(0, len(rows), block):
        part = rows[lo:lo + block]
        tokens = np.zeros((block, t_max), np.int32)
        chosen = np.zeros((block, t_max), np.int32)
        for r, (prompt, served) in enumerate(part):
            seq = list(prompt) + list(served)
            tokens[r, :len(seq)] = seq
            # position p-1+j holds the logits that chose served[j]
            chosen[r, len(prompt) - 1:len(seq) - 1] = served
        tokens = jnp.asarray(tokens)
        if cast is not None:
            chosen = _first_choice(w, tokens, cfg_items=cfg_items, cast=cast)
        gaps = np.asarray(_score(w, tokens, jnp.asarray(chosen),
                                 cfg_items=cfg_items))
        for r, (prompt, served) in enumerate(part):
            out.append(gaps[r, len(prompt) - 1:len(prompt) - 1 + len(served)])
    return out
