"""Hybrid decoders: recurrent or windowed layers beside full attention,
dense or routed feed-forward — the ``GraniteMoeHybrid`` family with no
routed experts (IBM granite-4.0-h), the ``OlmoHybrid`` family (Ai2
Olmo-Hybrid), the ``mellum`` family (JetBrains Mellum 2: sliding and
full attention, every feed-forward a layer of routed experts) and the
``sdar_moe`` family (JetLM SDAR: full attention under a block-causal
mask and routed experts, generating by diffusion over blocks).

A decoder whose layer pattern is read from a list.  Every layer is a
mixer and a feed-forward round a residual stream, with RMS norms placed
by ``layout``: ``"pre"`` is ``x + r * f(norm(x))`` (with a residual
multiplier ``r``), ``"post"`` is ``x + r * norm(f(x))``, the norm on
the sublayer's output (OLMo 2's reordered norm).  The mixer is one of

* a Mamba-2 block (``"mamba"``; Dao & Gu 2024, arXiv:2405.21060:
  depthwise causal convolution, selective state-space scan, gated RMS
  norm over the whole width);
* a Gated DeltaNet block (``"linear_attention"``; Yang, Kautz &
  Hatamizadeh 2024, arXiv:2412.06464: depthwise causal convolution, the
  gated delta rule on a matrix of state a head, RMS norm a head, then
  the gate);
* grouped-query attention over the whole prefix (``"attention"`` /
  ``"full_attention"``) or over a window of it
  (``"sliding_attention"``: a query reads itself and the ``window - 1``
  positions before it, and the layer's keys and values live on a ring
  of ``window + the largest chunk`` columns, not on ``max_len``).
  Position comes from nowhere (granite, Olmo: the recurrent layers
  carry it) or from a rotation of queries and keys by absolute position
  (``rope``: one frequency table a kind of layer, plain or YaRN), keys
  rotated before they are cached; queries and keys may pass an RMS norm
  first, over all heads' outputs (``qk_norm="all"``, OLMo 2) or over
  each head's own (``"head"``).

The feed-forward is a gated (SwiGLU) MLP or, with ``num_experts``, a
layer of routed SwiGLU experts: softmax router, ``experts_per_token`` a
token, no capacity and no dropped token (``routed_experts``).

The embedding is tied to the output head or not
(``tie_embeddings``), and both may be scaled (``embedding_multiplier``,
``1 / logits_scaling``).

The model is served, so it has ONE signature, the incremental one:

    logits, *tables[, counts] = net(tokens, step, length, *tables)

``tokens`` (B, T) are the T new tokens of each lane, of which row b's
first ``length_b`` are valid; ``step`` (B,) is each lane's frontier (0:
the lane starts from zero state whatever it held).  The state tables
are what :meth:`HybridDecoderModel.state_spec` declares, in its order:
``kv`` holds the full-attention layers' keys and values by position;
``kv_win`` the sliding layers' on their ring; ``rec`` (named ``ssm``
with Mamba layers, ``delta`` with Gated DeltaNet layers) and ``conv``
hold the recurrent layers' state, whose size does not depend on the
context.  A model declares the tables its kinds of layer need and no
other.  All are threaded whole through the layers and written in place
(``kv_cache_write``, ``ssm_conv``, ``ssm_scan``, ``delta_rule``).
``logits`` is (B, 1, V): one row a lane, at its last valid position.
A block-diffusion model (``diffusion``: block length ``b``) attends
under the block-causal mask and hands back (B, b, V), each row's last
``b`` valid positions: the block it denoises.  With routed experts a
last output ``counts`` (1,) int32 follows the tables: experts given a
token, summed over the layers
(:meth:`HybridDecoderModel.counter_spec`).  A full forward over a
sequence is the same call with ``step`` 0 and fresh tables.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock

__all__ = ["RMSNorm", "GatedMLP", "SparseMLP", "GroupedQueryAttention",
           "Mamba2Mixer",
           "GatedDeltaNetMixer", "HybridDecoderLayer", "HybridDecoderModel",
           "granite_4_0_h_micro", "olmo_hybrid_7b"]


def _dense(units, in_units):
    return nn.Dense(units, flatten=False, use_bias=False,
                    in_units=in_units)


class RMSNorm(HybridBlock):
    """``scope`` names where a trace finds the norm's work, where that
    is not under ``rms_norm``."""

    def __init__(self, units, eps=1e-5, scope=None, **kwargs):
        super().__init__(**kwargs)
        self._attrs = {"eps": float(eps)}
        if scope:
            self._attrs["scope"] = scope
        self.gamma = self.params.get("gamma", shape=(units,), init="ones")

    def hybrid_forward(self, F, x, gamma=None):
        return F.rms_norm(x, gamma, **self._attrs)


class GatedMLP(HybridBlock):
    """``(silu(g) * v) W_out`` with ``[g, v] = x W_in``, no biases."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        self._hidden = hidden_size
        self.w_in = _dense(2 * hidden_size, units)
        self.w_out = _dense(units, hidden_size)

    def hybrid_forward(self, F, x):
        h = self.w_in(x)
        g = F.slice_axis(h, axis=-1, begin=0, end=self._hidden)
        v = F.slice_axis(h, axis=-1, begin=self._hidden,
                         end=2 * self._hidden)
        return self.w_out(g * F.sigmoid(g) * v)


class SparseMLP(HybridBlock):
    """``num_experts`` SwiGLU experts of ``hidden_size``, of which each
    token takes ``top_k`` by a router (``score``: softmax, or a sigmoid
    a logit; renormalised over the chosen where ``norm_topk``, then
    times ``route_scale``); no capacity: no token is dropped
    (``routed_experts``).  ``selection_bias`` adds a leaf ``bias`` (E,)
    to the scores that choose and not to the weights; ``shared_size`` >
    0 adds a shared SwiGLU expert every token passes (``shared_in``
    (units, 2 shared_size), ``shared_out``).  ``held`` = (first, count)
    makes the layer this device's share of an expert-parallel one: the
    router scores all ``num_experts`` and the layer holds ``count`` of
    them.  Leaves stacked on the expert axis: ``router`` (units, E),
    ``w_in`` (held, units, 2 hidden) gate over up, ``w_out`` (held,
    hidden, units).  Takes the rows' ``length`` (padded tokens are
    routed nowhere) and hands back the count of held experts touched
    (and, with ``held``, of the assignments that landed on them) beside
    the output."""

    def __init__(self, units, hidden_size, num_experts, top_k,
                 norm_topk=True, score="softmax", route_scale=1.0,
                 selection_bias=False, shared_size=0, held=None, **kwargs):
        super().__init__(**kwargs)
        self._attrs = {"top_k": int(top_k), "norm_topk": bool(norm_topk)}
        # attributes a plain layer's op is not given: its graph is the
        # one it always was
        if score != "softmax":
            self._attrs["score"] = score
        if route_scale != 1.0:
            self._attrs["route_scale"] = float(route_scale)
        if held is not None:
            self._attrs.update(held_first=int(held[0]),
                               held_count=int(held[1]))
        if selection_bias:
            self._attrs["bias"] = True
        if shared_size:
            self._attrs["shared"] = True
        stacked = int(held[1]) if held is not None else num_experts
        self.router = self.params.get(
            "router", shape=(units, num_experts), init="normal")
        if selection_bias:
            self.bias = self.params.get("bias", shape=(num_experts,),
                                        init="zeros")
        self.w_in = self.params.get(
            "w_in", shape=(stacked, units, 2 * hidden_size), init="normal")
        self.w_out = self.params.get(
            "w_out", shape=(stacked, hidden_size, units), init="normal")
        if shared_size:
            self.shared_in = self.params.get(
                "shared_in", shape=(units, 2 * shared_size), init="normal")
            self.shared_out = self.params.get(
                "shared_out", shape=(shared_size, units), init="normal")

    def hybrid_forward(self, F, x, length, router=None, w_in=None,
                       w_out=None, bias=None, shared_in=None,
                       shared_out=None):
        extra = [t for t in (bias, shared_in, shared_out) if t is not None]
        return F.routed_experts(x, router, w_in, w_out, length, *extra,
                                **self._attrs)


class GroupedQueryAttention(HybridBlock):
    """Causal self-attention with ``num_heads`` query heads over
    ``num_kv_heads`` key/value heads, no biases; ``cache_layer`` names
    this block's planes of its table (``kv``, or ``kv_win`` for a
    windowed block).  ``qk_norm``: with ``"all"`` the projected queries
    and keys each pass an RMS norm over ALL their heads' outputs before
    they are cut into heads (OLMo 2's QK-norm); with ``"head"`` each
    head's ``head_dim`` outputs are normed alone, one weight of
    ``head_dim`` for the queries and one for the keys (scope
    ``qk_norm`` both).  ``rope`` = (frequencies, scale) rotates queries
    and keys by absolute position (after the norm, keys before they
    are cached); ``window`` > 0 bounds a query's context and makes the
    block's table a ring of columns; ``block`` > 1 replaces the causal
    mask by the block-causal one (a query sees its own whole block).
    ``gate`` multiplies the heads' outputs by ``sigmoid(x W_gate)``
    before ``o`` (``W_gate``: a leaf of ``num_heads * head_dim`` outputs
    over ``units``; scope ``attn_gate``)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 sm_scale, cache_layer=0, qk_norm=None, qk_norm_eps=1e-5,
                 rope=None, window=0, block=1, gate=False, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_heads} query heads do not divide "
                             f"into {num_kv_heads} key/value heads")
        if qk_norm not in (None, "all", "head"):
            raise MXNetError(f"unknown QK-norm form {qk_norm!r}")
        self._dims = (num_heads, num_kv_heads, head_dim)
        self._scale = float(sm_scale)
        self._cache_layer = int(cache_layer)
        self.q = _dense(num_heads * head_dim, units)
        self.k = _dense(num_kv_heads * head_dim, units)
        self.v = _dense(num_kv_heads * head_dim, units)
        self.o = _dense(units, num_heads * head_dim)
        self._gated = bool(gate)
        if self._gated:
            self.gate = _dense(num_heads * head_dim, units)
        self._qk_norm = qk_norm
        if qk_norm:
            per = 1 if qk_norm == "head" else None
            self.q_norm = RMSNorm(head_dim * (per or num_heads),
                                  qk_norm_eps, scope="qk_norm")
            self.k_norm = RMSNorm(head_dim * (per or num_kv_heads),
                                  qk_norm_eps, scope="qk_norm")
        self._rope = None if rope is None else {
            "inv_freq": tuple(float(f) for f in rope[0]),
            "scale": float(rope[1])}
        # attributes a plain block's ops are not given: its graph is the
        # one it always was
        self._write = {"ring": True} if window else {}
        self._read = {"window": int(window)} if window else {}
        if block > 1:
            self._read["block"] = int(block)

    def hybrid_forward(self, F, x, step, kv):
        hq, hk, d = self._dims
        at = self._cache_layer

        def heads(t, n):          # (B, T, n*d) -> (B, n, T, d)
            return F.transpose(F.reshape(t, shape=(0, -1, n, d)),
                               axes=(0, 2, 1, 3))

        def placed(t, n, norm):
            """Projected queries or keys, normed, cut into heads and
            rotated."""
            if self._qk_norm == "all":
                t = norm(t)
            t = heads(t, n)
            if self._qk_norm == "head":
                t = norm(t)
            if self._rope is not None:
                t = F.rope(t, step, **self._rope)
            return t

        k_norm, q_norm = (self.k_norm, self.q_norm) if self._qk_norm \
            else (None, None)
        kv = F.kv_cache_write(kv, placed(self.k(x), hk, k_norm), step,
                              layer=at, plane=0, **self._write)
        kv = F.kv_cache_write(kv, heads(self.v(x), hk), step, layer=at,
                              plane=1, **self._write)
        # the table whole, its planes named by ``layer``: a chunk's
        # attention kernel reads them where the table lies
        out = F.cached_attention(placed(self.q(x), hq, q_norm), kv, kv,
                                 step, sm_scale=self._scale, layer=at,
                                 **self._read)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(0, -1, hq * d))
        if self._gated:
            out = F.attn_gate(out, self.gate(x))
        return self.o(out), kv


class Mamba2Mixer(HybridBlock):
    """The Mamba-2 block with one group: ``[z, xBC, dt] = x W_in``;
    ``xBC`` through the causal depthwise convolution and silu; the
    selective scan over ``[x, B, C] = xBC``; ``rms_norm(y * silu(z))``;
    ``W_out``.  ``cache_layer`` names its planes of the ``ssm`` and
    ``conv`` tables."""

    def __init__(self, units, num_heads, head_dim, state_size, conv_kernel,
                 chunk, eps, cache_layer=0, **kwargs):
        super().__init__(**kwargs)
        inner = num_heads * head_dim
        self._sizes = (inner, state_size, num_heads)
        self._chunk, self._eps = int(chunk), float(eps)
        self._cache_layer = int(cache_layer)
        channels = inner + 2 * state_size
        self.in_proj = _dense(inner + channels + num_heads, units)
        self.conv_weight = self.params.get(
            "conv_weight", shape=(channels, conv_kernel), init="normal")
        self.conv_bias = self.params.get("conv_bias", shape=(channels,),
                                         init="zeros")
        self.dt_bias = self.params.get("dt_bias", shape=(num_heads,),
                                       init="zeros")
        self.a_log = self.params.get("a_log", shape=(num_heads,),
                                     init="zeros")
        self.d_skip = self.params.get("d_skip", shape=(num_heads,),
                                      init="ones")
        self.norm_gamma = self.params.get("norm_gamma", shape=(inner,),
                                          init="ones")
        self.out_proj = _dense(units, inner)

    def hybrid_forward(self, F, x, step, length, ssm, conv,
                       conv_weight=None, conv_bias=None, dt_bias=None,
                       a_log=None, d_skip=None, norm_gamma=None):
        inner, n, heads = self._sizes
        at = self._cache_layer
        cut = lambda t, lo, hi: F.slice_axis(t, axis=-1, begin=lo, end=hi)
        h = self.in_proj(x)
        z = cut(h, 0, inner)
        xbc = cut(h, inner, 2 * inner + 2 * n)
        dt = cut(h, 2 * inner + 2 * n, 2 * inner + 2 * n + heads)
        xbc, conv = F.ssm_conv(conv, xbc, conv_weight, conv_bias, step,
                               length, layer=at)
        y, ssm = F.ssm_scan(
            ssm, cut(xbc, 0, inner), dt, cut(xbc, inner, inner + n),
            cut(xbc, inner + n, inner + 2 * n), a_log, d_skip, dt_bias,
            step, length, layer=at, chunk=self._chunk)
        y = F.gated_rms_norm(y, z, norm_gamma, eps=self._eps)
        return self.out_proj(y), ssm, conv


class GatedDeltaNetMixer(HybridBlock):
    """The Gated DeltaNet block: ``q``, ``k`` (``num_heads`` of
    ``key_dim``) and ``v`` (``num_heads`` of ``value_dim``) projected,
    through ONE causal depthwise convolution over their channels side
    by side (no bias) and silu; ``beta = sigmoid(x W_b)``, doubled with
    ``neg_eigval``; ``g = -exp(a_log) * softplus(x W_a + dt_bias)``;
    the gated delta rule; ``rms_norm`` of each head's output, times
    ``silu(x W_g)``; ``W_o``.  ``cache_layer`` names its planes of the
    ``delta`` and ``conv`` tables."""

    def __init__(self, units, num_heads, key_dim, value_dim, conv_kernel,
                 chunk, eps, neg_eigval=False, cache_layer=0, **kwargs):
        super().__init__(**kwargs)
        self._sizes = (num_heads, key_dim, value_dim)
        self._chunk, self._eps = int(chunk), float(eps)
        self._beta_scale = 2.0 if neg_eigval else 1.0
        self._cache_layer = int(cache_layer)
        wide_k, wide_v = num_heads * key_dim, num_heads * value_dim
        self.q = _dense(wide_k, units)
        self.k = _dense(wide_k, units)
        self.v = _dense(wide_v, units)
        self.g = _dense(wide_v, units)
        self.a = _dense(num_heads, units)
        self.b = _dense(num_heads, units)
        self.conv_weight = self.params.get(
            "conv_weight", shape=(2 * wide_k + wide_v, conv_kernel),
            init="normal")
        self.dt_bias = self.params.get("dt_bias", shape=(num_heads,),
                                       init="zeros")
        self.a_log = self.params.get("a_log", shape=(num_heads,),
                                     init="zeros")
        self.norm_gamma = self.params.get("norm_gamma", shape=(value_dim,),
                                          init="ones")
        self.o = _dense(units, wide_v)

    def hybrid_forward(self, F, x, step, length, delta, conv,
                       conv_weight=None, dt_bias=None, a_log=None,
                       norm_gamma=None):
        heads, dk, dv = self._sizes
        at = self._cache_layer
        cut = lambda t, lo, hi: F.slice_axis(t, axis=-1, begin=lo, end=hi)
        # one number a head, in float32 however the leaves are staged
        a_head = lambda t: F.reshape(F.cast(t, dtype="float32"),
                                     shape=(1, 1, -1))
        qkv = F.concat(self.q(x), self.k(x), self.v(x), dim=2)
        qkv, conv = F.ssm_conv(conv, qkv, conv_weight, step, length,
                               layer=at, no_bias=True)
        beta = F.sigmoid(F.cast(self.b(x), dtype="float32")) \
            * self._beta_scale
        g = F.broadcast_mul(
            F.Activation(F.broadcast_add(
                F.cast(self.a(x), dtype="float32"), a_head(dt_bias)),
                act_type="softrelu"),
            F.negative(F.exp(a_head(a_log))))
        y, delta = F.delta_rule(
            delta, cut(qkv, 0, heads * dk),
            cut(qkv, heads * dk, 2 * heads * dk),
            cut(qkv, 2 * heads * dk, 2 * heads * dk + heads * dv), g, beta,
            step, length, layer=at, chunk=self._chunk)
        y = F.gated_rms_norm(y, self.g(x), norm_gamma, eps=self._eps,
                             group=dv, norm_before_gate=True)
        return self.o(y), delta, conv


class HybridDecoderLayer(HybridBlock):
    """A mixer and a feed-forward round the residual stream.
    ``tables`` names the state tables the mixer takes, in its order
    (``("kv",)``, ``("kv_win",)``, or the recurrent table and
    ``"conv"``): the layer is called with those and hands them back,
    ``(x, *tables)``; a ``SparseMLP`` for ``mlp`` adds its counts at
    the end.  ``layout`` puts each norm on its sublayer's input
    (``"pre"``), on its output (``"post"``), or one on each
    (``"sandwich"``: four norms a layer, ``norm1`` and ``norm2`` on the
    inputs, ``post_norm1`` and ``post_norm2`` on the outputs)."""

    def __init__(self, tables, mixer, units, mlp, residual, eps,
                 layout="pre", **kwargs):
        super().__init__(**kwargs)
        self.tables = tuple(tables)
        self._attention = len(self.tables) == 1
        self._residual = float(residual)
        self._pre = layout in ("pre", "sandwich")
        self._post = layout in ("post", "sandwich")
        self.sparse = isinstance(mlp, SparseMLP)
        self.norm1 = RMSNorm(units, eps)
        self.mixer = mixer
        self.norm2 = RMSNorm(units, eps)
        self.mlp = mlp
        # the post layout's one norm a sublayer is norm1 / norm2
        self._out = (self.norm1, self.norm2)
        if layout == "sandwich":
            self.post_norm1 = RMSNorm(units, eps)
            self.post_norm2 = RMSNorm(units, eps)
            self._out = (self.post_norm1, self.post_norm2)

    def hybrid_forward(self, F, x, step, length, *state):
        h = self.norm1(x) if self._pre else x
        if self._attention:
            h, *state = self.mixer(h, step, *state)
        else:
            h, *state = self.mixer(h, step, length, *state)
        x = x + (self._out[0](h) if self._post else h) * self._residual
        h = self.norm2(x) if self._pre else x
        counts = ()
        if self.sparse:
            h, touched = self.mlp(h, length)
            counts = (touched,)
        else:
            h = self.mlp(h)
        x = x + (self._out[1](h) if self._post else h) * self._residual
        return (x,) + tuple(state) + counts


class HybridDecoderModel(HybridBlock):
    """See the module text.  ``layer_types`` is a list of ``"mamba"``,
    ``"linear_attention"``, ``"attention"`` (or ``"full_attention"``)
    and ``"sliding_attention"``; a model has at most one recurrent
    kind.  The i-th full-attention layer owns planes ``[i]`` of ``kv``,
    the i-th sliding layer planes ``[i]`` of ``kv_win``, and the j-th
    recurrent layer planes ``[j]`` of the recurrent table and of
    ``conv``.  ``rope`` maps a kind of attention layer
    (``"full_attention"``, ``"sliding_attention"``) to its
    (frequencies, scale); a kind without a table takes no position.
    ``num_experts`` > 0 makes every feed-forward past the first
    ``dense_layers`` (gated MLPs of ``dense_hidden_size``) a
    ``SparseMLP`` of experts ``hidden_size`` wide, routed by
    ``router_score`` with ``route_scale``, a ``selection_bias`` and a
    shared expert of ``shared_expert_size`` where given;
    ``expert_share`` = (first, count) holds ``count`` of the
    ``num_experts`` routed over (one device's share of an
    expert-parallel layer).  ``attention_gate`` gates every attention
    layer's output.  ``diffusion``
    (``{"block", "mask_id", "steps", "threshold"}``) makes the model a
    block-diffusion decoder: every attention layer takes the block mask
    and the logits are each row's last ``block`` positions; the runner
    and the batcher read the rest (:meth:`diffusion_spec`)."""

    ATTENTION = ("attention", "full_attention")
    SLIDING = "sliding_attention"
    RECURRENT = {"mamba": "ssm", "linear_attention": "delta"}

    def __init__(self, vocab_size, units, hidden_size, layer_types,
                 num_heads, num_kv_heads, *, head_dim=None,
                 ssm_heads=None, ssm_head_dim=None, ssm_state=None,
                 delta_heads=None, delta_key_dim=None, delta_value_dim=None,
                 delta_neg_eigval=False, conv_kernel=4,
                 chunk=256, eps=1e-5, embedding_multiplier=1.0,
                 residual_multiplier=1.0, attention_multiplier=-1.0,
                 logits_scaling=1.0, layout="pre", qk_norm=None,
                 rope=None, sliding_window=0, num_experts=0,
                 experts_per_token=0, norm_topk=True,
                 tie_embeddings=True, diffusion=None, attention_gate=False,
                 dense_layers=0, dense_hidden_size=None,
                 router_score="softmax", route_scale=1.0,
                 selection_bias=False, shared_expert_size=0,
                 expert_share=None, **kwargs):
        super().__init__(**kwargs)
        bad = set(layer_types) - set(self.ATTENTION) - {self.SLIDING} \
            - set(self.RECURRENT)
        if bad:
            raise MXNetError(f"unknown layer types {sorted(bad)}")
        recurrent = sorted(set(layer_types) & set(self.RECURRENT))
        if len(recurrent) > 1:
            raise MXNetError(f"one recurrent kind of layer a model, not "
                             f"{recurrent}")
        if layout not in ("pre", "post", "sandwich"):
            raise MXNetError(f"unknown block layout {layout!r}")
        if self.SLIDING in layer_types and sliding_window < 1:
            raise MXNetError("sliding_attention layers need a "
                             "sliding_window")
        head_dim = units // num_heads if head_dim is None else head_dim
        # True is the form the first QK-normed family had
        qk_norm = {True: "all", False: None}.get(qk_norm, qk_norm)
        self._vocab, self._units = int(vocab_size), int(units)
        self._attn = (num_kv_heads, head_dim)
        self._window = int(sliding_window)
        self._top_k = int(experts_per_token) if num_experts else 0
        self._share = tuple(expert_share) if expert_share and num_experts \
            else None
        self._diffusion = dict(diffusion) if diffusion else None
        self._block = int(diffusion["block"]) if diffusion else 1
        # the recurrent table's name, a lane's shape in it, and the
        # channels of a lane's convolution window
        self._rec = None
        if recurrent == ["linear_attention"]:
            self._rec = ("delta",
                         (delta_heads, delta_key_dim, delta_value_dim),
                         delta_heads * (2 * delta_key_dim + delta_value_dim))
        elif recurrent:
            self._rec = ("ssm", (ssm_heads, ssm_head_dim, ssm_state),
                         ssm_heads * ssm_head_dim + 2 * ssm_state)
        self._conv_kernel = int(conv_kernel)
        self._embed_scale = float(embedding_multiplier)
        self._logit_scale = 1.0 / float(logits_scaling)
        self.layer_types = tuple(layer_types)
        self.embed = self.params.get("embed", shape=(vocab_size, units),
                                     init="normal")
        self._tied = bool(tie_embeddings)
        if not self._tied:
            self.head = self.params.get("head", shape=(vocab_size, units),
                                        init="normal")
        rope = rope or {}
        self.layers = nn.HybridSequential()
        n_attn = n_win = n_rec = 0
        for i, kind in enumerate(self.layer_types):
            if kind in self.ATTENTION or kind == self.SLIDING:
                sliding = kind == self.SLIDING
                mixer = GroupedQueryAttention(
                    units, num_heads, num_kv_heads, head_dim,
                    attention_multiplier,
                    cache_layer=n_win if sliding else n_attn,
                    qk_norm=qk_norm, qk_norm_eps=eps,
                    rope=rope.get(self.SLIDING if sliding
                                  else "full_attention"),
                    window=self._window if sliding else 0,
                    block=self._block, gate=attention_gate)
                if sliding:
                    tables, n_win = ("kv_win",), n_win + 1
                else:
                    tables, n_attn = ("kv",), n_attn + 1
            elif kind == "mamba":
                mixer = Mamba2Mixer(units, ssm_heads, ssm_head_dim,
                                    ssm_state, conv_kernel, chunk, eps,
                                    cache_layer=n_rec)
                tables = ("ssm", "conv")
                n_rec += 1
            else:
                mixer = GatedDeltaNetMixer(
                    units, delta_heads, delta_key_dim, delta_value_dim,
                    conv_kernel, chunk, eps, neg_eigval=delta_neg_eigval,
                    cache_layer=n_rec)
                tables = ("delta", "conv")
                n_rec += 1
            if i < dense_layers:
                mlp = GatedMLP(units, dense_hidden_size or hidden_size)
            elif num_experts:
                mlp = SparseMLP(units, hidden_size, num_experts,
                                experts_per_token, norm_topk,
                                score=router_score, route_scale=route_scale,
                                selection_bias=selection_bias,
                                shared_size=shared_expert_size,
                                held=self._share)
            else:
                mlp = GatedMLP(units, hidden_size)
            self.layers.add(HybridDecoderLayer(
                tables, mixer, units, mlp, residual_multiplier, eps,
                layout=layout))
        self._counts = (n_attn, n_win, n_rec)
        # the tables the model's kinds of layer need, in the spec's order
        self._tables = ("kv",) + (("kv_win",) if n_win else ()) \
            + ((self._rec[0], "conv") if n_rec else ())
        self.final_norm = RMSNorm(units, eps)

    @classmethod
    def from_config(cls, cfg):
        """The model of a published ``config.json`` (as a dict), by its
        ``model_type``: ``granitemoehybrid`` (also where the key is
        absent) that routes to no expert, ``olmo_hybrid``, ``mellum``,
        ``sdar_moe`` or ``afmoe``.  A configuration cut in depth
        (``layer_types`` shorter than the published list, or a smaller
        ``num_hidden_layers``) builds the model's first layers."""
        kind = cfg.get("model_type", "granitemoehybrid")
        if kind == "granitemoehybrid":
            return cls._from_granite(cfg)
        if kind == "olmo_hybrid":
            return cls._from_olmo_hybrid(cfg)
        if kind == "mellum":
            return cls._from_mellum(cfg)
        if kind == "sdar_moe":
            return cls._from_sdar_moe(cfg)
        if kind == "afmoe":
            return cls._from_afmoe(cfg)
        raise MXNetError(f"HybridDecoderModel: unknown model_type {kind!r} "
                         f"(granitemoehybrid, olmo_hybrid, mellum, "
                         f"sdar_moe, afmoe)")

    @classmethod
    def _from_granite(cls, cfg):
        if cfg.get("num_local_experts"):
            raise MXNetError("HybridDecoderModel: a granitemoehybrid "
                             "model with routed experts")
        if cfg.get("position_embedding_type", "nope") != "nope":
            raise MXNetError("HybridDecoderModel: a granitemoehybrid model "
                             "with positions (position_embedding_type "
                             f"{cfg['position_embedding_type']!r})")
        if cfg.get("mamba_n_groups", 1) != 1:
            raise MXNetError("HybridDecoderModel: a granitemoehybrid model "
                             "with more than one B/C group")
        cls._known_kinds(cfg, ("mamba", "attention"))
        return cls(
            cfg["vocab_size"], cfg["hidden_size"],
            cfg["shared_intermediate_size"], cfg["layer_types"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            ssm_heads=cfg["mamba_n_heads"],
            ssm_head_dim=cfg["mamba_d_head"],
            ssm_state=cfg["mamba_d_state"],
            conv_kernel=cfg["mamba_d_conv"],
            chunk=cfg["mamba_chunk_size"], eps=cfg["rms_norm_eps"],
            embedding_multiplier=cfg["embedding_multiplier"],
            residual_multiplier=cfg["residual_multiplier"],
            attention_multiplier=cfg["attention_multiplier"],
            logits_scaling=cfg["logits_scaling"])

    @classmethod
    def _from_olmo_hybrid(cls, cfg):
        if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
            raise MXNetError("HybridDecoderModel: an olmo_hybrid model with "
                             "rotary positions (rope_theta is set)")
        if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
            raise MXNetError("HybridDecoderModel: an olmo_hybrid model "
                             "whose linear layers have other key heads "
                             "than value heads")
        if cfg.get("attention_bias"):
            raise MXNetError("HybridDecoderModel: an olmo_hybrid model "
                             "with attention biases")
        cls._known_kinds(cfg, ("linear_attention", "full_attention"))
        return cls(
            cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"],
            cfg["layer_types"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"],
            delta_heads=cfg["linear_num_value_heads"],
            delta_key_dim=cfg["linear_key_head_dim"],
            delta_value_dim=cfg["linear_value_head_dim"],
            delta_neg_eigval=bool(cfg.get("linear_allow_neg_eigval")),
            conv_kernel=cfg["linear_conv_kernel_dim"],
            chunk=cfg.get("linear_chunk_size", 64),
            eps=cfg["rms_norm_eps"], layout="post", qk_norm="all",
            tie_embeddings=bool(cfg.get("tie_word_embeddings")))

    @classmethod
    def _from_mellum(cls, cfg):
        """Sliding and full attention in the published pattern, each
        kind with its own rotary table (``rope_parameters``: plain, or
        YaRN applied at every length with the config's own
        ``attention_factor``), per-head QK-norm, every feed-forward a
        layer of routed experts."""
        from ..ndarray.rnn_impl import rope_frequencies
        cls._known_kinds(cfg, ("sliding_attention", "full_attention"))
        if set(cfg.get("mlp_layer_types", ["sparse"])) != {"sparse"}:
            raise MXNetError("HybridDecoderModel: a mellum model with "
                             "dense feed-forward layers")
        if cfg.get("attention_bias"):
            raise MXNetError("HybridDecoderModel: a mellum model with "
                             "attention biases")
        if not cfg.get("use_sliding_window", True):
            raise MXNetError("HybridDecoderModel: a mellum model whose "
                             "sliding layers are switched off")
        rope = {}
        for kind, r in cfg["rope_parameters"].items():
            yarn = r.get("rope_type", "default") == "yarn"
            if not yarn and r.get("rope_type", "default") != "default":
                raise MXNetError(f"HybridDecoderModel: rope_type "
                                 f"{r['rope_type']!r}")
            rope[kind] = (rope_frequencies(cfg["head_dim"], r["rope_theta"],
                                           r if yarn else None),
                          r.get("attention_factor", 1.0) if yarn else 1.0)
        return cls(
            cfg["vocab_size"], cfg["hidden_size"],
            cfg["moe_intermediate_size"], cfg["layer_types"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], eps=cfg["rms_norm_eps"],
            qk_norm="head", rope=rope,
            sliding_window=cfg["sliding_window"],
            num_experts=cfg["num_experts"],
            experts_per_token=cfg["num_experts_per_tok"],
            norm_topk=bool(cfg.get("norm_topk_prob", True)),
            tie_embeddings=bool(cfg.get("tie_word_embeddings")))

    @classmethod
    def _from_sdar_moe(cls, cfg):
        """Full attention in every layer under the block-causal mask, per-
        head QK-norm and plain rotation, every feed-forward a layer of
        routed experts (the Qwen3-MoE block).  What a published
        ``config.json`` leaves to generation is read from the file too:
        ``block_length``, ``mask_token_id``, ``denoising_steps`` and
        ``confidence_threshold``."""
        from ..ndarray.rnn_impl import rope_frequencies
        if cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("mlp_only_layers"):
            raise MXNetError("HybridDecoderModel: an sdar_moe model with "
                             "dense feed-forward layers")
        if cfg.get("attention_bias"):
            raise MXNetError("HybridDecoderModel: an sdar_moe model with "
                             "attention biases")
        if cfg.get("use_sliding_window") or cfg.get("rope_scaling"):
            raise MXNetError("HybridDecoderModel: an sdar_moe model with a "
                             "sliding window or scaled rotation")
        block = int(cfg["block_length"])
        if block < 2:
            raise MXNetError("HybridDecoderModel: an sdar_moe model needs a "
                             "block_length of 2 or more")
        return cls(
            cfg["vocab_size"], cfg["hidden_size"],
            cfg["moe_intermediate_size"],
            ["full_attention"] * int(cfg["num_hidden_layers"]),
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], eps=cfg["rms_norm_eps"],
            qk_norm="head",
            rope={"full_attention": (rope_frequencies(
                cfg["head_dim"], cfg["rope_theta"]), 1.0)},
            num_experts=cfg["num_experts"],
            experts_per_token=cfg["num_experts_per_tok"],
            norm_topk=bool(cfg.get("norm_topk_prob", True)),
            tie_embeddings=bool(cfg.get("tie_word_embeddings")),
            diffusion={"block": block,
                       "mask_id": int(cfg["mask_token_id"]),
                       "steps": int(cfg["denoising_steps"]),
                       "threshold": float(cfg["confidence_threshold"])})

    @classmethod
    def _from_afmoe(cls, cfg):
        """Sliding and full attention in the published pattern (arcee's
        Trinity): per-head QK-norm, a rotary table for the sliding
        layers alone (the full ones take no position), every attention
        output gated by ``sigmoid(x W_gate)``; four RMS norms a layer
        (``sandwich``); the first ``num_dense_layers`` feed-forwards a
        gated MLP of ``intermediate_size``, the rest routed experts by a
        sigmoid a logit with a selection bias, renormalised
        (``route_norm``) and scaled (``route_scale``), beside
        ``num_shared_experts`` shared experts; the embedding times
        ``sqrt(hidden_size)`` where ``mup_enabled``.  ``expert_share``
        (``{"of": E, "first": f}``) makes ``num_experts`` the experts
        this device holds of the E routed over."""
        import math

        from ..ndarray.rnn_impl import rope_frequencies
        cls._known_kinds(cfg, ("sliding_attention", "full_attention"))
        if cfg.get("score_func", "sigmoid") not in ("sigmoid", "softmax"):
            raise MXNetError(f"HybridDecoderModel: an afmoe router that "
                             f"scores by {cfg['score_func']!r}")
        if max(cfg.get("n_group", 1), cfg.get("topk_group", 1)) != 1:
            raise MXNetError("HybridDecoderModel: an afmoe model that "
                             "routes by groups of experts")
        if cfg.get("rope_scaling") or cfg.get("attention_bias"):
            raise MXNetError("HybridDecoderModel: an afmoe model with "
                             "scaled rotation or attention biases")
        share = cfg.get("expert_share")
        routed = int(share["of"]) if share else int(cfg["num_experts"])
        held = (int(share["first"]), int(cfg["num_experts"])) \
            if share else None
        if held and not 0 <= held[0] <= routed - held[1]:
            raise MXNetError(f"HybridDecoderModel: experts {held[0]} to "
                             f"{held[0] + held[1] - 1} held of {routed}")
        return cls(
            cfg["vocab_size"], cfg["hidden_size"],
            cfg["moe_intermediate_size"], cfg["layer_types"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], eps=cfg["rms_norm_eps"],
            embedding_multiplier=math.sqrt(cfg["hidden_size"])
            if cfg.get("mup_enabled") else 1.0,
            layout="sandwich", qk_norm="head",
            rope={"sliding_attention": (rope_frequencies(
                cfg["head_dim"], cfg["rope_theta"]), 1.0)},
            sliding_window=cfg["sliding_window"], num_experts=routed,
            experts_per_token=cfg["num_experts_per_tok"],
            norm_topk=bool(cfg.get("route_norm", True)),
            tie_embeddings=bool(cfg.get("tie_word_embeddings")),
            attention_gate=True,
            dense_layers=int(cfg.get("num_dense_layers", 0)),
            dense_hidden_size=cfg["intermediate_size"],
            router_score=cfg.get("score_func", "sigmoid"),
            route_scale=float(cfg.get("route_scale", 1.0)),
            selection_bias=True,
            shared_expert_size=int(cfg.get("num_shared_experts", 0))
            * cfg["moe_intermediate_size"],
            expert_share=held)

    @staticmethod
    def _known_kinds(cfg, kinds):
        bad = sorted(set(cfg["layer_types"]) - set(kinds))
        if bad:
            raise MXNetError(
                f"HybridDecoderModel: a {cfg.get('model_type')} model has "
                f"no layer of kind {bad} (it has {list(kinds)})")

    def state_spec(self, lanes, max_len, kv_dtype="float32",
                   max_chunk=None):
        """The state tables of incremental mode, as
        ``GenerateRunner`` takes them: ``(name, shape, lane axis,
        dtype)`` each, those the model's kinds of layer need and no
        other.  ``kv`` grows with the context (``max_len`` positions a
        lane); ``kv_win``, the sliding layers' ring, holds
        ``sliding_window + max_chunk`` columns a lane whatever the
        context (``max_chunk``: the most tokens one call writes, the
        largest prompt bucket — so a chunk's first query still finds
        its window after the chunk is written); the recurrent table
        (``ssm``: heads x head size x state; ``delta``: heads x key
        size x value size) and ``conv`` (channels minor) do not grow
        either, and stay float32: a state is a sum over every token so
        far, so its rounding compounds where a key's does not."""
        n_attn, n_win, n_rec = self._counts
        hk, d = self._attn
        lanes = int(lanes)
        spec = [("kv", (n_attn, 2, lanes, hk, int(max_len), d), 2, kv_dtype)]
        if n_win:
            if max_chunk is None:
                raise MXNetError("state_spec: sliding layers' ring needs "
                                 "max_chunk, the largest prompt bucket")
            ring = min(self._window + int(max_chunk), int(max_len))
            spec.append(("kv_win", (n_win, 2, lanes, hk, ring, d), 2,
                         kv_dtype))
        if n_rec:
            name, lane, channels = self._rec
            spec += [(name, (n_rec, lanes) + tuple(lane), 1, "float32"),
                     ("conv", (n_rec, lanes, self._conv_kernel - 1,
                               channels), 1, "float32")]
        return tuple(spec)

    def counter_spec(self):
        """What a call of this model counts, for ``GenerateRunner``:
        ``device``: the names of the graph's ``counts`` output's
        entries (found on the device, one int32 each a call);
        ``per_token``: counts that are a multiple of a call's valid
        tokens, known on the host.  A model that holds a share of its
        experts counts, beside the experts touched, the assignments
        that landed on them (``moe_assignments_held``)."""
        if not self._top_k:
            return {"device": (), "per_token": {}}
        held = ("moe_assignments_held",) if self._share else ()
        return {"device": ("moe_experts_touched",) + held,
                "per_token": {"moe_assignments": self._top_k}}

    def diffusion_spec(self):
        """What a block-diffusion model tells ``GenerateRunner``: its
        block length, the mask id, how many passes denoise a block and
        the confidence that decides a position early; None for a model
        that generates a token a step."""
        return dict(self._diffusion) if self._diffusion else None

    def named_leaves(self):
        """``{reference leaf name: Parameter}``: the reference's leaves
        one to one."""
        out = {"embed": self.embed}
        for i, layer in enumerate(self.layers):
            p, m = f"l{i}.", layer.mixer
            out[p + "norm1"] = layer.norm1.gamma
            if isinstance(m, GroupedQueryAttention):
                out.update({p + "q": m.q.weight, p + "k": m.k.weight,
                            p + "v": m.v.weight, p + "o": m.o.weight})
                if m._gated:
                    out[p + "gate"] = m.gate.weight
                if m._qk_norm:
                    out.update({p + "q_norm": m.q_norm.gamma,
                                p + "k_norm": m.k_norm.gamma})
            elif isinstance(m, Mamba2Mixer):
                out.update({
                    p + "in_proj": m.in_proj.weight,
                    p + "conv_w": m.conv_weight, p + "conv_b": m.conv_bias,
                    p + "dt_bias": m.dt_bias, p + "a_log": m.a_log,
                    p + "d_skip": m.d_skip, p + "ssm_norm": m.norm_gamma,
                    p + "out_proj": m.out_proj.weight})
            else:
                out.update({p + n: getattr(m, n).weight for n in "qkvgabo"})
                out.update({
                    p + "conv_w": m.conv_weight, p + "a_log": m.a_log,
                    p + "dt_bias": m.dt_bias, p + "o_norm": m.norm_gamma})
            if layer._out[0] is not layer.norm1:
                out[p + "post_norm1"] = layer.post_norm1.gamma
            out[p + "norm2"] = layer.norm2.gamma
            if layer._out[1] is not layer.norm2:
                out[p + "post_norm2"] = layer.post_norm2.gamma
            if layer.sparse:
                mlp = layer.mlp
                out.update({p + "router": mlp.router, p + "w_in": mlp.w_in,
                            p + "w_out": mlp.w_out})
                out.update({p + n: getattr(mlp, n) for n in
                            ("bias", "shared_in", "shared_out")
                            if hasattr(mlp, n)})
            else:
                out[p + "mlp_in"] = layer.mlp.w_in.weight
                out[p + "mlp_out"] = layer.mlp.w_out.weight
        out["final_norm"] = self.final_norm.gamma
        if not self._tied:
            out["head"] = self.head
        return out

    def hybrid_forward(self, F, tokens, step, length, *tables, embed=None,
                       head=None):
        names = self._tables
        if len(tables) != len(names):
            raise MXNetError(f"HybridDecoderModel: {len(tables)} state "
                             f"tables given, the model has {list(names)}")
        state = dict(zip(names, tables))
        # rows gathered as the table holds them (bfloat16 when served
        # so), brought to float32 before they are scaled
        x = F.cast(F.Embedding(tokens, embed, input_dim=self._vocab,
                               output_dim=self._units),
                   dtype="float32") * self._embed_scale
        touched = None
        for layer in self.layers:
            x, *rest = layer(x, step, length,
                             *[state[n] for n in layer.tables])
            state.update(zip(layer.tables, rest))
            if layer.sparse:
                touched = rest[-1] if touched is None \
                    else touched + rest[-1]
        # one row a lane leaves the program: the last valid position's
        # (a block-diffusion model's: the last block's positions)
        if self._block > 1:
            last = F.concat(*[F.expand_dims(F.SequenceLast(
                x, length - back, use_sequence_length=True, axis=1), axis=1)
                for back in range(self._block - 1, -1, -1)], dim=1)
        else:
            last = F.expand_dims(F.SequenceLast(
                x, length, use_sequence_length=True, axis=1), axis=1)
        logits = F.FullyConnected(self.final_norm(last),
                                  embed if self._tied else head,
                                  no_bias=True, num_hidden=self._vocab,
                                  flatten=False) * self._logit_scale
        out = (logits,) + tuple(state[n] for n in names)
        return out if touched is None else out + (touched,)


def granite_4_0_h_micro():
    """ibm-granite/granite-4.0-h-micro: 40 layers (36 Mamba-2, attention
    at 5, 15, 25, 35), hidden 2048, 32 query over 8 key/value heads of
    64, Mamba 64 heads of 64 with state 128, gated MLP 8192, vocabulary
    100352 tied, no positions."""
    kinds = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]
    return HybridDecoderModel(
        100352, 2048, 8192, kinds, 32, 8, ssm_heads=64, ssm_head_dim=64,
        ssm_state=128, conv_kernel=4, chunk=256, eps=1e-5,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=8.0)


def olmo_hybrid_7b(num_layers=32):
    """allenai/Olmo-Hybrid-7B: 32 layers, every fourth full attention
    (30 heads of 128, QK-norm, no positions) and the rest Gated DeltaNet
    (30 heads, keys of 96, values of 192, kernel 4, beta in (0, 2)),
    hidden 3840, gated MLP 11008, norms on the sublayers' outputs,
    vocabulary 100352 untied.  ``num_layers`` cuts it to its first
    layers (a pipeline stage)."""
    kinds = ["full_attention" if i % 4 == 3 else "linear_attention"
             for i in range(32)][:num_layers]
    return HybridDecoderModel(
        100352, 3840, 11008, kinds, 30, 30, delta_heads=30,
        delta_key_dim=96, delta_value_dim=192, delta_neg_eigval=True,
        conv_kernel=4, chunk=64, eps=1e-6, layout="post", qk_norm="all",
        tie_embeddings=False)
