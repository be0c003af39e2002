"""Transformer / BERT model family — north-star workloads 3 & 4
(BASELINE.md: BERT-Large pretrain, Transformer-big WMT).

The reference repo itself carries no transformer (2018-era; BERT lived
in GluonNLP downstream) — this supplies the same capability class,
built on the framework's fused kernels: ``flash_attention`` for the
attention core and the Pallas fused ``LayerNorm``.  Everything is
HybridBlocks, so a full encoder stack hybridizes into one XLA program;
``mxtpu.parallel.build_train_step`` adds dp/tp sharding and bf16.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock

__all__ = ["MultiHeadAttention", "PositionwiseFFN",
           "TransformerEncoderCell", "TransformerEncoder",
           "TransformerDecoderCell", "TransformerDecoder",
           "TransformerModel", "BERTModel", "bert_base", "bert_large",
           "transformer_encoder", "transformer_base",
           "transformer_big"]


class MultiHeadAttention(HybridBlock):
    """Self- or cross-attention over (N, T, C) via the fused attention
    op.  Pass a second input (``memory``) at call time for
    cross-attention: queries come from ``x``, keys/values from
    ``memory`` (the decoder->encoder path of the seq2seq
    transformer).  ``cache_layer`` names the planes of the KV slot
    table this block writes and reads in incremental mode."""

    def __init__(self, units, num_heads, dropout=0.0, causal=False,
                 proj_bias=True, cache_layer=0, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by "
                             f"num_heads {num_heads}")
        self._units = units
        self._heads = num_heads
        self._causal = causal
        self._cache_layer = int(cache_layer)
        self.qkv = nn.Dense(3 * units, flatten=False, use_bias=True)
        # proj_bias=False when a FusedResidualLayerNorm epilogue folds
        # the output bias (and dropout) into its fused kernel
        self.proj = nn.Dense(units, flatten=False, use_bias=proj_bias)
        self.drop = nn.Dropout(dropout) if dropout else None

    def _split_heads(self, F, t):
        # (N, T, u) -> (N, h, T, u/h)
        t = F.reshape(t, shape=(0, -1, self._heads,
                                self._units // self._heads))
        return F.transpose(t, axes=(0, 2, 1, 3))

    def hybrid_forward(self, F, x, *args):
        u = self._units
        split = lambda t: self._split_heads(F, t)
        if len(args) == 2:
            # incremental decode: (x, step, cache) — x holds the T new
            # tokens, cache is the WHOLE slot table (layers, 2, B, H,
            # L, u/h), step (B,) is each lane's write frontier.  The
            # table is written in place and handed on, never taken
            # apart: returns (out, the same table).
            step, cache = args
            at = self._cache_layer
            qkv = self.qkv(x)
            q = split(F.slice_axis(qkv, axis=-1, begin=0, end=u))
            k = split(F.slice_axis(qkv, axis=-1, begin=u, end=2 * u))
            v = split(F.slice_axis(qkv, axis=-1, begin=2 * u,
                                   end=3 * u))
            cache = F.kv_cache_write(cache, k, step, layer=at, plane=0)
            cache = F.kv_cache_write(cache, v, step, layer=at, plane=1)
            out = F.cached_attention(
                q, F.kv_cache_read(cache, layer=at, plane=0),
                F.kv_cache_read(cache, layer=at, plane=1), step)
            out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                            shape=(0, -1, u))
            out = self.proj(out)
            if self.drop is not None:
                out = self.drop(out)
            return out, cache
        memory = args[0] if args else None
        if memory is None:
            qkv = self.qkv(x)
            q = split(F.slice_axis(qkv, axis=-1, begin=0, end=u))
            k = split(F.slice_axis(qkv, axis=-1, begin=u, end=2 * u))
            v = split(F.slice_axis(qkv, axis=-1, begin=2 * u,
                                   end=3 * u))
        else:
            # cross-attention reuses the fused qkv weights: the q rows
            # project x, the kv rows project memory (one GEMM each)
            qkv_x = self.qkv(x)
            qkv_m = self.qkv(memory)
            q = split(F.slice_axis(qkv_x, axis=-1, begin=0, end=u))
            k = split(F.slice_axis(qkv_m, axis=-1, begin=u, end=2 * u))
            v = split(F.slice_axis(qkv_m, axis=-1, begin=2 * u,
                                   end=3 * u))
        out = F.flash_attention(q, k, v, causal=self._causal)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(0, -1, u))
        out = self.proj(out)
        if self.drop is not None:
            out = self.drop(out)
        return out


class PositionwiseFFN(HybridBlock):
    """Dense → gelu → Dense (the transformer MLP)."""

    def __init__(self, units, hidden_size, dropout=0.0, out_bias=True,
                 **kwargs):
        super().__init__(**kwargs)
        self.ffn1 = nn.Dense(hidden_size, flatten=False)
        self.ffn2 = nn.Dense(units, flatten=False, use_bias=out_bias)
        self.drop = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        out = self.ffn2(F.LeakyReLU(self.ffn1(x), act_type="gelu"))
        if self.drop is not None:
            out = self.drop(out)
        return out


class TransformerEncoderCell(HybridBlock):
    """Post-LN encoder layer (BERT convention): LN(x + attn),
    LN(x + ffn)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 causal=False, cache_layer=0, **kwargs):
        super().__init__(**kwargs)
        # output bias + dropout + residual + LN run as ONE fused
        # epilogue (kernels/layer_norm.py), so the sub-blocks emit the
        # raw GEMM output: no proj bias, no separate Dropout
        self.attn = MultiHeadAttention(units, num_heads, 0.0, causal,
                                       proj_bias=False,
                                       cache_layer=cache_layer)
        self.ffn = PositionwiseFFN(units, hidden_size, 0.0,
                                   out_bias=False)
        self.ln1 = nn.FusedResidualLayerNorm(dropout)
        self.ln2 = nn.FusedResidualLayerNorm(dropout)

    def hybrid_forward(self, F, x, *args):
        if args:
            step, cache = args
            a, cache = self.attn(x, step, cache)
            x = self.ln1(a, x)
            x = self.ln2(self.ffn(x), x)
            return x, cache
        x = self.ln1(self.attn(x), x)
        x = self.ln2(self.ffn(x), x)
        return x


class TransformerEncoder(HybridBlock):
    """Stack of encoder cells."""

    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, causal=False, remat=False, **kwargs):
        super().__init__(**kwargs)
        self.layers = nn.HybridSequential()
        for i in range(num_layers):
            cell = TransformerEncoderCell(
                units, hidden_size, num_heads, dropout, causal,
                cache_layer=i)
            if remat:
                # per-layer activation rematerialization: O(sqrt)-style
                # memory for deep stacks (SURVEY §0)
                cell.set_remat(True)
            self.layers.add(cell)

    def hybrid_forward(self, F, x, *args):
        if args:
            # incremental: the slot table (num_layers, 2, B, H, L,
            # u/h) is threaded whole through the cells; cell i writes
            # and reads its own planes of it
            step, cache = args
            for cell in self.layers:
                x, cache = cell(x, step, cache)
            return x, cache
        return self.layers(x)


class TransformerDecoderCell(HybridBlock):
    """Post-LN decoder layer: causal self-attn, cross-attn over the
    encoder memory, FFN — the WMT transformer decoder (Vaswani et al.
    2017; capability class of the reference's ``example/nmt``†-era
    seq2seq line)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 cache_layer=0, **kwargs):
        super().__init__(**kwargs)
        self.self_attn = MultiHeadAttention(units, num_heads, 0.0,
                                            causal=True,
                                            proj_bias=False,
                                            cache_layer=cache_layer)
        self.cross_attn = MultiHeadAttention(units, num_heads, 0.0,
                                             proj_bias=False)
        self.ffn = PositionwiseFFN(units, hidden_size, 0.0,
                                   out_bias=False)
        self.ln1 = nn.FusedResidualLayerNorm(dropout)
        self.ln2 = nn.FusedResidualLayerNorm(dropout)
        self.ln3 = nn.FusedResidualLayerNorm(dropout)

    def hybrid_forward(self, F, x, memory, *args):
        if args:
            # incremental: only self-attention is cached; cross-attn
            # keys/values are recomputed from the (fixed) memory each
            # step — stateless and correct, at a small recompute cost
            step, cache = args
            a, cache = self.self_attn(x, step, cache)
            x = self.ln1(a, x)
            x = self.ln2(self.cross_attn(x, memory), x)
            x = self.ln3(self.ffn(x), x)
            return x, cache
        x = self.ln1(self.self_attn(x), x)
        x = self.ln2(self.cross_attn(x, memory), x)
        x = self.ln3(self.ffn(x), x)
        return x


class TransformerDecoder(HybridBlock):
    """Stack of decoder cells (memory threaded to every layer)."""

    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, remat=False, **kwargs):
        super().__init__(**kwargs)
        self.layers = nn.HybridSequential()
        for i in range(num_layers):
            cell = TransformerDecoderCell(units, hidden_size,
                                          num_heads, dropout,
                                          cache_layer=i)
            if remat:
                cell.set_remat(True)
            self.layers.add(cell)

    def hybrid_forward(self, F, x, memory, *args):
        if args:
            step, cache = args
            for cell in self.layers:
                x, cache = cell(x, memory, step, cache)
            return x, cache
        for cell in self.layers:
            x = cell(x, memory)
        return x


class TransformerModel(HybridBlock):
    """Encoder-decoder transformer for translation (WMT config):
    shared source/target vocabulary embedding, sinusoid-free learned
    positions, tied output projection left separate (the reference
    recipe's default)."""

    def __init__(self, vocab_size, units=1024, hidden_size=4096,
                 num_layers=6, num_heads=16, max_length=256,
                 dropout=0.1, remat=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._num_layers = num_layers
        self._num_heads = num_heads
        self._max_length = max_length
        self.embed = nn.Embedding(vocab_size, units)
        self.pos_embed = self.params.get(
            "pos_embed", shape=(max_length, units), init="normal")
        self.embed_ln = nn.LayerNorm()
        self.drop = nn.Dropout(dropout) if dropout else None
        self.encoder = TransformerEncoder(num_layers, units,
                                          hidden_size, num_heads,
                                          dropout, remat=remat)
        self.decoder = TransformerDecoder(num_layers, units,
                                          hidden_size, num_heads,
                                          dropout, remat=remat)
        self.out_proj = nn.Dense(vocab_size, flatten=False)

    def _embed(self, F, tokens, pos_embed):
        x = self.embed(tokens) * float(np.sqrt(self._units))
        # length-polymorphic position add: slice_like keyed on the
        # embedded activations instead of a static T makes ONE exported
        # graph valid for every sequence length <= max_length — what
        # bucketed serving (mxtpu.serving) relies on
        pe = F.slice_like(F.expand_dims(pos_embed, axis=0), x,
                          axes=(1,))
        x = x + pe
        x = self.embed_ln(x)
        if self.drop is not None:
            x = self.drop(x)
        return x

    def _embed_at(self, F, tokens, step, pos_embed, scale):
        """Embedding + position add for incremental decode: token t of
        lane b sits at absolute position ``step_b + t``, so positions
        are *gathered* from the table (``take``) instead of sliced —
        the dynamic-offset twin of the slice_like trick."""
        x = self.embed(tokens) if scale is None else \
            self.embed(tokens) * scale
        pos = F.slice_like(
            F.expand_dims(F._arange(start=0, stop=self._max_length),
                          axis=0), x, axes=(1,))
        pos = F.broadcast_add(pos, F.expand_dims(step, axis=1))
        x = x + F.take(pos_embed, pos, axis=0)
        x = self.embed_ln(x)
        if self.drop is not None:
            x = self.drop(x)
        return x

    def kv_cache_spec(self, batch_size, max_len=None):
        """Shape of the decoder self-attention KV slot table this model
        takes and hands back (written in place, layer by layer) in
        incremental mode: (num_layers, 2, B, num_heads, L,
        units // num_heads) — one array, plane ``[i, 0]`` layer i's
        keys and ``[i, 1]`` its values."""
        L = self._max_length if max_len is None else int(max_len)
        return (self._num_layers, 2, int(batch_size), self._num_heads,
                L, self._units // self._num_heads)

    def hybrid_forward(self, F, src, tgt, *args, pos_embed=None):
        if args:
            # incremental decode: (src, tgt_new, step, cache).  The
            # encoder runs full on src each call (prefill recomputes
            # it; the decode path feeds the same bucketed src), the
            # decoder writes its layers' planes of the one KV table.
            step, cache = args
            memory = self.encoder(self._embed(F, src, pos_embed))
            x = self._embed_at(F, tgt, step, pos_embed,
                               float(np.sqrt(self._units)))
            dec, cache = self.decoder(x, memory, step, cache)
            return self.out_proj(dec), cache
        memory = self.encoder(self._embed(F, src, pos_embed))
        dec = self.decoder(self._embed(F, tgt, pos_embed), memory)
        return self.out_proj(dec)


class BERTModel(HybridBlock):
    """BERT-style encoder LM: token + position (+ type) embeddings,
    encoder stack, MLM head over tied-or-separate projection."""

    def __init__(self, vocab_size, units, hidden_size, num_layers,
                 num_heads, max_length=512, dropout=0.1,
                 use_token_type=True, causal=False, remat=False,
                 **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._num_layers = num_layers
        self._num_heads = num_heads
        self._max_length = max_length
        self.word_embed = nn.Embedding(vocab_size, units)
        self.pos_embed = self.params.get(
            "pos_embed", shape=(max_length, units), init="normal")
        self.type_embed = nn.Embedding(2, units) \
            if use_token_type else None
        self.embed_ln = nn.LayerNorm()
        self.embed_drop = nn.Dropout(dropout) if dropout else None
        # causal=True turns the encoder stack into a decoder-only LM
        # (GPT-style) — the configuration mxtpu.serving.generate serves
        self.encoder = TransformerEncoder(num_layers, units,
                                          hidden_size, num_heads,
                                          dropout, causal=causal,
                                          remat=remat)
        self.mlm = nn.Dense(vocab_size, flatten=False)

    def kv_cache_spec(self, batch_size, max_len=None):
        """Shape of the KV slot table this model takes and hands back
        (written in place, layer by layer) in incremental mode:
        (num_layers, 2, B, num_heads, L, units // num_heads) — one
        array, plane ``[i, 0]`` layer i's keys and ``[i, 1]`` its
        values."""
        L = self._max_length if max_len is None else int(max_len)
        return (self._num_layers, 2, int(batch_size), self._num_heads,
                L, self._units // self._num_heads)

    def hybrid_forward(self, F, tokens, *args, pos_embed=None):
        if len(args) == 2:
            # incremental decode: (tokens, step, cache) — tokens are
            # the T new tokens per lane, positions step_b + t gathered
            # from the table; token-type embeddings don't apply to the
            # generation path.  Returns (logits, new_cache).
            step, cache = args
            x = self.word_embed(tokens)
            pos = F.slice_like(
                F.expand_dims(
                    F._arange(start=0, stop=self._max_length), axis=0),
                x, axes=(1,))
            pos = F.broadcast_add(pos, F.expand_dims(step, axis=1))
            x = x + F.take(pos_embed, pos, axis=0)
            x = self.embed_ln(x)
            if self.embed_drop is not None:
                x = self.embed_drop(x)
            x, cache = self.encoder(x, step, cache)
            return self.mlm(x), cache
        token_types = args[0] if args else None
        x = self.word_embed(tokens)
        # slice_like (not a static-T slice_axis) keeps the exported
        # graph valid for ANY sequence length <= max_length: the
        # position table is sliced against the activations at run/trace
        # time, which is what lets mxtpu.serving compile one export
        # into many sequence buckets
        pe = F.slice_like(F.expand_dims(pos_embed, axis=0), x,
                          axes=(1,))
        x = x + pe
        if self.type_embed is not None and token_types is not None:
            x = x + self.type_embed(token_types)
        x = self.embed_ln(x)
        if self.embed_drop is not None:
            x = self.embed_drop(x)
        x = self.encoder(x)
        return self.mlm(x)


def bert_base(vocab_size=30522, max_length=512, dropout=0.1):
    """BERT-Base: 12 layers, 768 units, 12 heads."""
    return BERTModel(vocab_size, 768, 3072, 12, 12, max_length, dropout)


def bert_large(vocab_size=30522, max_length=512, dropout=0.1,
               remat=False):
    """BERT-Large: 24 layers, 1024 units, 16 heads — north-star
    workload 3."""
    return BERTModel(vocab_size, 1024, 4096, 24, 16, max_length,
                     dropout, remat=remat)


def transformer_encoder(num_layers=6, units=512, hidden_size=2048,
                        num_heads=8, dropout=0.1, causal=False):
    """Transformer-base encoder stack (WMT-style config 4)."""
    return TransformerEncoder(num_layers, units, hidden_size, num_heads,
                              dropout, causal)


def transformer_big(vocab_size=32768, max_length=256, dropout=0.1,
                    remat=False):
    """Transformer-big WMT config (north-star workload 4, SURVEY M6):
    6+6 layers, 1024 units, 16 heads, 4096 FFN."""
    return TransformerModel(vocab_size, 1024, 4096, 6, 16, max_length,
                            dropout, remat=remat)


def transformer_base(vocab_size=32768, max_length=256, dropout=0.1):
    """Transformer-base WMT config: 6+6 layers, 512 units, 8 heads."""
    return TransformerModel(vocab_size, 512, 2048, 6, 8, max_length,
                            dropout)
