"""Hybrid recurrent / attention decoders: the ``GraniteMoeHybrid``
family with no routed experts (IBM granite-4.0-h) and the ``OlmoHybrid``
family (Ai2 Olmo-Hybrid).

A decoder whose layer pattern is read from a list.  Every layer is a
mixer and a gated (SwiGLU) MLP round a residual stream, with RMS norms
placed by ``layout``: ``"pre"`` is ``x + r * f(norm(x))`` (granite, with
a residual multiplier ``r``), ``"post"`` is ``x + r * norm(f(x))``, the
norm on the sublayer's output (OLMo 2's reordered norm).  The mixer is
one of

* a Mamba-2 block (``"mamba"``; Dao & Gu 2024, arXiv:2405.21060:
  depthwise causal convolution, selective state-space scan, gated RMS
  norm over the whole width);
* a Gated DeltaNet block (``"linear_attention"``; Yang, Kautz &
  Hatamizadeh 2024, arXiv:2412.06464: depthwise causal convolution, the
  gated delta rule on a matrix of state a head, RMS norm a head, then
  the gate);
* grouped-query attention with no position signal at all
  (``"attention"`` / ``"full_attention"``), with or without an RMS norm
  of queries and keys (``qk_norm``).

The embedding is tied to the output head or not
(``tie_embeddings``), and both may be scaled (``embedding_multiplier``,
``1 / logits_scaling``).

The model is served, so it has ONE signature, the incremental one:

    logits, kv, rec, conv = net(tokens, step, length, kv, rec, conv)

``tokens`` (B, T) are the T new tokens of each lane, of which row b's
first ``length_b`` are valid; ``step`` (B,) is each lane's frontier (0:
the lane starts from zero state whatever it held).  The three state
tables are what :meth:`HybridDecoderModel.state_spec` declares: ``kv``
holds the attention layers' keys and values by position; ``rec`` (named
``ssm`` with Mamba layers, ``delta`` with Gated DeltaNet layers) and
``conv`` hold the recurrent layers' state, whose size does not depend
on the context.  All are threaded whole through the layers and written
in place (``kv_cache_write``, ``ssm_conv``, ``ssm_scan``,
``delta_rule``).  ``logits`` is (B, 1, V): one row a lane, at its last
valid position.  A full forward over a sequence is the same call with
``step`` 0 and fresh tables.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock

__all__ = ["RMSNorm", "GatedMLP", "GroupedQueryAttention", "Mamba2Mixer",
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


class GroupedQueryAttention(HybridBlock):
    """Causal self-attention with ``num_heads`` query heads over
    ``num_kv_heads`` key/value heads, no biases, no positions;
    ``cache_layer`` names this block's planes of the ``kv`` table.
    With ``qk_norm_eps`` the projected queries and keys each pass an
    RMS norm over ALL their heads' outputs before they are cut into
    heads (OLMo 2's QK-norm; scope ``qk_norm``)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 sm_scale, cache_layer=0, qk_norm_eps=None, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise MXNetError(f"{num_heads} query heads do not divide "
                             f"into {num_kv_heads} key/value heads")
        self._dims = (num_heads, num_kv_heads, head_dim)
        self._scale = float(sm_scale)
        self._cache_layer = int(cache_layer)
        self.q = _dense(num_heads * head_dim, units)
        self.k = _dense(num_kv_heads * head_dim, units)
        self.v = _dense(num_kv_heads * head_dim, units)
        self.o = _dense(units, num_heads * head_dim)
        self._qk_norm = qk_norm_eps is not None
        if self._qk_norm:
            self.q_norm = RMSNorm(num_heads * head_dim, qk_norm_eps,
                                  scope="qk_norm")
            self.k_norm = RMSNorm(num_kv_heads * head_dim, qk_norm_eps,
                                  scope="qk_norm")

    def hybrid_forward(self, F, x, step, kv):
        hq, hk, d = self._dims
        at = self._cache_layer

        def heads(t, n):          # (B, T, n*d) -> (B, n, T, d)
            return F.transpose(F.reshape(t, shape=(0, -1, n, d)),
                               axes=(0, 2, 1, 3))

        k = self.k(x)
        if self._qk_norm:
            k = self.k_norm(k)
        kv = F.kv_cache_write(kv, heads(k, hk), step, layer=at, plane=0)
        kv = F.kv_cache_write(kv, heads(self.v(x), hk), step, layer=at,
                              plane=1)
        q = self.q(x)
        if self._qk_norm:
            q = self.q_norm(q)
        out = F.cached_attention(
            heads(q, hq), F.kv_cache_read(kv, layer=at, plane=0),
            F.kv_cache_read(kv, layer=at, plane=1), step,
            sm_scale=self._scale)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(0, -1, hq * d))
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
    """A mixer and an MLP round the residual stream.  ``kind`` is
    ``"attention"`` (the mixer takes ``kv``) or a recurrent kind (it
    takes ``rec`` and ``conv``); ``layout`` puts each norm on its
    sublayer's input (``"pre"``) or on its output (``"post"``)."""

    def __init__(self, kind, mixer, units, hidden_size, residual, eps,
                 layout="pre", **kwargs):
        super().__init__(**kwargs)
        self._kind, self._residual = kind, float(residual)
        self._post = layout == "post"
        self.norm1 = RMSNorm(units, eps)
        self.mixer = mixer
        self.norm2 = RMSNorm(units, eps)
        self.mlp = GatedMLP(units, hidden_size)

    def hybrid_forward(self, F, x, step, length, kv, rec, conv):
        h = x if self._post else self.norm1(x)
        if self._kind == "attention":
            h, kv = self.mixer(h, step, kv)
        else:
            h, rec, conv = self.mixer(h, step, length, rec, conv)
        if self._post:
            x = x + self.norm1(h) * self._residual
            x = x + self.norm2(self.mlp(x)) * self._residual
        else:
            x = x + h * self._residual
            x = x + self.mlp(self.norm2(x)) * self._residual
        return x, kv, rec, conv


class HybridDecoderModel(HybridBlock):
    """See the module text.  ``layer_types`` is a list of ``"mamba"``,
    ``"linear_attention"`` and ``"attention"`` (or ``"full_attention"``);
    a model has one recurrent kind.  The i-th attention layer owns
    planes ``[i]`` of ``kv`` and the j-th recurrent layer planes ``[j]``
    of the recurrent table and of ``conv``."""

    ATTENTION = ("attention", "full_attention")
    RECURRENT = {"mamba": "ssm", "linear_attention": "delta"}

    def __init__(self, vocab_size, units, hidden_size, layer_types,
                 num_heads, num_kv_heads, *, head_dim=None,
                 ssm_heads=None, ssm_head_dim=None, ssm_state=None,
                 delta_heads=None, delta_key_dim=None, delta_value_dim=None,
                 delta_neg_eigval=False, conv_kernel=4,
                 chunk=256, eps=1e-5, embedding_multiplier=1.0,
                 residual_multiplier=1.0, attention_multiplier=-1.0,
                 logits_scaling=1.0, layout="pre", qk_norm=False,
                 tie_embeddings=True, **kwargs):
        super().__init__(**kwargs)
        bad = set(layer_types) - set(self.ATTENTION) - set(self.RECURRENT)
        if bad:
            raise MXNetError(f"unknown layer types {sorted(bad)}")
        recurrent = sorted(set(layer_types) & set(self.RECURRENT))
        if len(recurrent) > 1:
            raise MXNetError(f"one recurrent kind of layer a model, not "
                             f"{recurrent}")
        if layout not in ("pre", "post"):
            raise MXNetError(f"unknown block layout {layout!r}")
        head_dim = units // num_heads if head_dim is None else head_dim
        self._vocab, self._units = int(vocab_size), int(units)
        self._attn = (num_kv_heads, head_dim)
        if not recurrent:
            raise MXNetError("a hybrid decoder has recurrent layers: "
                             f"{sorted(self.RECURRENT)}")
        # the recurrent table's name, a lane's shape in it, and the
        # channels of a lane's convolution window
        if recurrent == ["linear_attention"]:
            lane = (delta_heads, delta_key_dim, delta_value_dim)
            channels = delta_heads * (2 * delta_key_dim + delta_value_dim)
        else:
            lane = (ssm_heads, ssm_head_dim, ssm_state)
            channels = ssm_heads * ssm_head_dim + 2 * ssm_state
        self._rec = (self.RECURRENT[recurrent[0]], lane, channels)
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
        self.layers = nn.HybridSequential()
        n_attn = n_rec = 0
        for kind in self.layer_types:
            if kind in self.ATTENTION:
                kind = "attention"
                mixer = GroupedQueryAttention(
                    units, num_heads, num_kv_heads, head_dim,
                    attention_multiplier, cache_layer=n_attn,
                    qk_norm_eps=eps if qk_norm else None)
                n_attn += 1
            elif kind == "mamba":
                mixer = Mamba2Mixer(units, ssm_heads, ssm_head_dim,
                                    ssm_state, conv_kernel, chunk, eps,
                                    cache_layer=n_rec)
                n_rec += 1
            else:
                mixer = GatedDeltaNetMixer(
                    units, delta_heads, delta_key_dim, delta_value_dim,
                    conv_kernel, chunk, eps, neg_eigval=delta_neg_eigval,
                    cache_layer=n_rec)
                n_rec += 1
            self.layers.add(HybridDecoderLayer(
                kind, mixer, units, hidden_size, residual_multiplier, eps,
                layout=layout))
        self._counts = (n_attn, n_rec)
        self.final_norm = RMSNorm(units, eps)

    @classmethod
    def from_config(cls, cfg):
        """The model of a published ``config.json`` (as a dict), by its
        ``model_type``: ``granitemoehybrid`` (also where the key is
        absent) that routes to no expert, or ``olmo_hybrid``.  A
        configuration cut in depth (``layer_types`` shorter than the
        published list) builds the model's first layers."""
        kind = cfg.get("model_type", "granitemoehybrid")
        if kind == "granitemoehybrid":
            return cls._from_granite(cfg)
        if kind == "olmo_hybrid":
            return cls._from_olmo_hybrid(cfg)
        raise MXNetError(f"HybridDecoderModel: unknown model_type {kind!r} "
                         f"(granitemoehybrid, olmo_hybrid)")

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
            eps=cfg["rms_norm_eps"], layout="post", qk_norm=True,
            tie_embeddings=bool(cfg.get("tie_word_embeddings")))

    @staticmethod
    def _known_kinds(cfg, kinds):
        bad = sorted(set(cfg["layer_types"]) - set(kinds))
        if bad:
            raise MXNetError(
                f"HybridDecoderModel: a {cfg.get('model_type')} model has "
                f"no layer of kind {bad} (it has {list(kinds)})")

    def state_spec(self, lanes, max_len, kv_dtype="float32"):
        """The state tables of incremental mode, as
        ``GenerateRunner`` takes them: ``(name, shape, lane axis,
        dtype)`` each.  ``kv`` grows with the context (``max_len``
        positions a lane); the recurrent table (``ssm``: heads x head
        size x state; ``delta``: heads x key size x value size) and
        ``conv`` (channels minor) do not, and stay
        float32: a state is a sum over every token so far, so its
        rounding compounds where a key's does not."""
        n_attn, n_rec = self._counts
        hk, d = self._attn
        name, lane, channels = self._rec
        lanes = int(lanes)
        return (
            ("kv", (n_attn, 2, lanes, hk, int(max_len), d), 2, kv_dtype),
            (name, (n_rec, lanes) + tuple(lane), 1, "float32"),
            ("conv", (n_rec, lanes, self._conv_kernel - 1, channels), 1,
             "float32"))

    def named_leaves(self):
        """``{reference leaf name: Parameter}``: the reference's leaves
        one to one."""
        out = {"embed": self.embed}
        for i, layer in enumerate(self.layers):
            p, m = f"l{i}.", layer.mixer
            out[p + "norm1"] = layer.norm1.gamma
            if layer._kind == "attention":
                out.update({p + "q": m.q.weight, p + "k": m.k.weight,
                            p + "v": m.v.weight, p + "o": m.o.weight})
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
            out[p + "norm2"] = layer.norm2.gamma
            out[p + "mlp_in"] = layer.mlp.w_in.weight
            out[p + "mlp_out"] = layer.mlp.w_out.weight
        out["final_norm"] = self.final_norm.gamma
        if not self._tied:
            out["head"] = self.head
        return out

    def hybrid_forward(self, F, tokens, step, length, kv, rec, conv,
                       embed=None, head=None):
        # rows gathered as the table holds them (bfloat16 when served
        # so), brought to float32 before they are scaled
        x = F.cast(F.Embedding(tokens, embed, input_dim=self._vocab,
                               output_dim=self._units),
                   dtype="float32") * self._embed_scale
        for layer in self.layers:
            x, kv, rec, conv = layer(x, step, length, kv, rec, conv)
        # one row a lane leaves the program: the last valid position's
        last = F.expand_dims(F.SequenceLast(
            x, length, use_sequence_length=True, axis=1), axis=1)
        logits = F.FullyConnected(self.final_norm(last),
                                  embed if self._tied else head,
                                  no_bias=True, num_hidden=self._vocab,
                                  flatten=False) * self._logit_scale
        return logits, kv, rec, conv


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
        conv_kernel=4, chunk=64, eps=1e-6, layout="post", qk_norm=True,
        tie_embeddings=False)
