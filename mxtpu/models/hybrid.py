"""Hybrid state-space / attention decoder (the ``GraniteMoeHybrid``
family with no routed experts: IBM granite-4.0-h).

A decoder whose layer pattern is read from a list: each layer is
pre-norm (RMS), ``x + r * mixer(norm(x))`` then ``x + r * mlp(norm(x))``
with a residual multiplier ``r``, a gated (SwiGLU) MLP, and as mixer
either a Mamba-2 block (Dao & Gu 2024, arXiv:2405.21060: depthwise
causal convolution, selective state-space scan, gated RMS norm) or
grouped-query attention with no position signal at all (``nope``).
The embedding is tied to the output head and both are scaled
(``embedding_multiplier``, ``1 / logits_scaling``).

The model is served, so it has ONE signature, the incremental one:

    logits, kv, ssm, conv = net(tokens, step, length, kv, ssm, conv)

``tokens`` (B, T) are the T new tokens of each lane, of which row b's
first ``length_b`` are valid; ``step`` (B,) is each lane's frontier (0:
the lane starts from zero state whatever it held).  The three state
tables are what :meth:`HybridDecoderModel.state_spec` declares: ``kv``
holds the attention layers' keys and values by position, ``ssm`` and
``conv`` the Mamba layers' recurrent state, whose size does not depend
on the context.  All are threaded whole through the layers and written
in place (``kv_cache_write``, ``ssm_conv``, ``ssm_scan``).  ``logits``
is (B, 1, V): one row a lane, at its last valid position.  A full
forward over a sequence is the same call with ``step`` 0 and fresh
tables.
"""
from __future__ import annotations

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock

__all__ = ["RMSNorm", "GatedMLP", "GroupedQueryAttention", "Mamba2Mixer",
           "HybridDecoderLayer", "HybridDecoderModel",
           "granite_4_0_h_micro"]


def _dense(units, in_units):
    return nn.Dense(units, flatten=False, use_bias=False,
                    in_units=in_units)


class RMSNorm(HybridBlock):
    def __init__(self, units, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._eps = float(eps)
        self.gamma = self.params.get("gamma", shape=(units,), init="ones")

    def hybrid_forward(self, F, x, gamma=None):
        return F.rms_norm(x, gamma, eps=self._eps)


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
    ``cache_layer`` names this block's planes of the ``kv`` table."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 sm_scale, cache_layer=0, **kwargs):
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

    def hybrid_forward(self, F, x, step, kv):
        hq, hk, d = self._dims
        at = self._cache_layer

        def heads(t, n):          # (B, T, n*d) -> (B, n, T, d)
            return F.transpose(F.reshape(t, shape=(0, -1, n, d)),
                               axes=(0, 2, 1, 3))

        kv = F.kv_cache_write(kv, heads(self.k(x), hk), step, layer=at,
                              plane=0)
        kv = F.kv_cache_write(kv, heads(self.v(x), hk), step, layer=at,
                              plane=1)
        out = F.cached_attention(
            heads(self.q(x), hq), F.kv_cache_read(kv, layer=at, plane=0),
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


class HybridDecoderLayer(HybridBlock):
    def __init__(self, kind, mixer, units, hidden_size, residual, eps,
                 **kwargs):
        super().__init__(**kwargs)
        self._kind, self._residual = kind, float(residual)
        self.norm1 = RMSNorm(units, eps)
        self.mixer = mixer
        self.norm2 = RMSNorm(units, eps)
        self.mlp = GatedMLP(units, hidden_size)

    def hybrid_forward(self, F, x, step, length, kv, ssm, conv):
        h = self.norm1(x)
        if self._kind == "attention":
            h, kv = self.mixer(h, step, kv)
        else:
            h, ssm, conv = self.mixer(h, step, length, ssm, conv)
        x = x + h * self._residual
        x = x + self.mlp(self.norm2(x)) * self._residual
        return x, kv, ssm, conv


class HybridDecoderModel(HybridBlock):
    """See the module text.  ``layer_types`` is a list of ``"mamba"``
    and ``"attention"``; the i-th attention layer owns planes ``[i]`` of
    ``kv`` and the j-th Mamba layer planes ``[j]`` of ``ssm`` and
    ``conv``."""

    def __init__(self, vocab_size, units, hidden_size, layer_types,
                 num_heads, num_kv_heads, *, head_dim=None,
                 ssm_heads, ssm_head_dim, ssm_state, conv_kernel=4,
                 chunk=256, eps=1e-5, embedding_multiplier=1.0,
                 residual_multiplier=1.0, attention_multiplier=-1.0,
                 logits_scaling=1.0, **kwargs):
        super().__init__(**kwargs)
        bad = set(layer_types) - {"mamba", "attention"}
        if bad:
            raise MXNetError(f"unknown layer types {sorted(bad)}")
        head_dim = units // num_heads if head_dim is None else head_dim
        self._vocab, self._units = int(vocab_size), int(units)
        self._attn = (num_kv_heads, head_dim)
        self._ssm = (ssm_heads, ssm_head_dim, ssm_state, conv_kernel)
        self._embed_scale = float(embedding_multiplier)
        self._logit_scale = 1.0 / float(logits_scaling)
        self.layer_types = tuple(layer_types)
        self.embed = self.params.get("embed", shape=(vocab_size, units),
                                     init="normal")
        self.layers = nn.HybridSequential()
        n_attn = n_mamba = 0
        for kind in self.layer_types:
            if kind == "attention":
                mixer = GroupedQueryAttention(
                    units, num_heads, num_kv_heads, head_dim,
                    attention_multiplier, cache_layer=n_attn)
                n_attn += 1
            else:
                mixer = Mamba2Mixer(units, ssm_heads, ssm_head_dim,
                                    ssm_state, conv_kernel, chunk, eps,
                                    cache_layer=n_mamba)
                n_mamba += 1
            self.layers.add(HybridDecoderLayer(
                kind, mixer, units, hidden_size, residual_multiplier, eps))
        self._counts = (n_attn, n_mamba)
        self.final_norm = RMSNorm(units, eps)

    @classmethod
    def from_config(cls, cfg):
        """The model of a ``granitemoehybrid`` ``config.json`` (as a
        dict) that routes to no expert."""
        if cfg.get("num_local_experts"):
            raise MXNetError("HybridDecoderModel has no routed experts")
        if cfg.get("position_embedding_type", "nope") != "nope":
            raise MXNetError("HybridDecoderModel takes no positions")
        if cfg.get("mamba_n_groups", 1) != 1:
            raise MXNetError("HybridDecoderModel: one B/C group only")
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

    def state_spec(self, lanes, max_len, kv_dtype="float32"):
        """The state tables of incremental mode, as
        ``GenerateRunner`` takes them: ``(name, shape, lane axis,
        dtype)`` each.  ``kv`` grows with the context (``max_len``
        positions a lane); ``ssm`` and ``conv`` do not, and stay
        float32: a state is a sum over every token so far, so its
        rounding compounds where a key's does not."""
        n_attn, n_mamba = self._counts
        hk, d = self._attn
        heads, p, n, k = self._ssm
        lanes = int(lanes)
        return (
            ("kv", (n_attn, 2, lanes, hk, int(max_len), d), 2, kv_dtype),
            ("ssm", (n_mamba, lanes, heads, p, n), 1, "float32"),
            ("conv", (n_mamba, lanes, k - 1, heads * p + 2 * n), 1,
             "float32"))

    def named_leaves(self):
        """``{reference leaf name: Parameter}``: the published
        checkpoint's leaves one to one, nothing fused."""
        out = {"embed": self.embed}
        for i, layer in enumerate(self.layers):
            p, m = f"l{i}.", layer.mixer
            out[p + "norm1"] = layer.norm1.gamma
            if layer._kind == "attention":
                out.update({p + "q": m.q.weight, p + "k": m.k.weight,
                            p + "v": m.v.weight, p + "o": m.o.weight})
            else:
                out.update({
                    p + "in_proj": m.in_proj.weight,
                    p + "conv_w": m.conv_weight, p + "conv_b": m.conv_bias,
                    p + "dt_bias": m.dt_bias, p + "a_log": m.a_log,
                    p + "d_skip": m.d_skip, p + "ssm_norm": m.norm_gamma,
                    p + "out_proj": m.out_proj.weight})
            out[p + "norm2"] = layer.norm2.gamma
            out[p + "mlp_in"] = layer.mlp.w_in.weight
            out[p + "mlp_out"] = layer.mlp.w_out.weight
        out["final_norm"] = self.final_norm.gamma
        return out

    def hybrid_forward(self, F, tokens, step, length, kv, ssm, conv,
                       embed=None):
        # rows gathered as the table holds them (bfloat16 when served
        # so), brought to float32 before they are scaled
        x = F.cast(F.Embedding(tokens, embed, input_dim=self._vocab,
                               output_dim=self._units),
                   dtype="float32") * self._embed_scale
        for layer in self.layers:
            x, kv, ssm, conv = layer(x, step, length, kv, ssm, conv)
        # one row a lane leaves the program: the last valid position's
        last = F.expand_dims(F.SequenceLast(
            x, length, use_sequence_length=True, axis=1), axis=1)
        logits = F.FullyConnected(self.final_norm(last), embed,
                                  no_bias=True, num_hidden=self._vocab,
                                  flatten=False) * self._logit_scale
        return logits, kv, ssm, conv


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
