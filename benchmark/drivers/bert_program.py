"""The one place that knows how the program lays a BERT out.

Builds the program's model from a configuration file, and carries the
benchmark's weights (reference layout, ``weights.py``) into the
program's parameters: the program fuses query, key and value into one
``qkv`` Dense and lets each residual LayerNorm own the bias of the
projection before it.
"""
import functools


def build_net(cfg):
    """The program's model for ``cfg``, not yet initialized."""
    from mxtpu.models.transformer import BERTModel
    return BERTModel(cfg["vocab_size"], cfg["hidden_size"],
                     cfg["intermediate_size"], cfg["num_hidden_layers"],
                     cfg["num_attention_heads"],
                     max_length=cfg["max_position_embeddings"],
                     dropout=float(cfg.get("hidden_dropout_prob", 0.0)),
                     use_token_type=bool(cfg.get("use_token_type", True)),
                     causal=bool(cfg.get("causal", False)))


def param_map(net, cfg):
    """``[(program Parameter, [reference leaf names])]``: more than one
    name means the program's leaf is those leaves joined along axis 0."""
    out = [(net.word_embed.weight, ["word_embed"]),
           (net.pos_embed, ["pos_embed"])]
    if net.type_embed is not None:
        out.append((net.type_embed.weight, ["type_embed"]))
    out += [(net.embed_ln.gamma, ["embed_ln_g"]),
            (net.embed_ln.beta, ["embed_ln_b"])]
    for i in range(cfg["num_hidden_layers"]):
        cell = net.encoder.layers[i]
        p = f"l{i}."
        out += [
            (cell.attn.qkv.weight, [p + "q_w", p + "k_w", p + "v_w"]),
            (cell.attn.qkv.bias, [p + "q_b", p + "k_b", p + "v_b"]),
            (cell.attn.proj.weight, [p + "proj_w"]),
            (cell.ln1.bias, [p + "proj_b"]),
            (cell.ln1.gamma, [p + "ln1_g"]), (cell.ln1.beta, [p + "ln1_b"]),
            (cell.ffn.ffn1.weight, [p + "ffn1_w"]),
            (cell.ffn.ffn1.bias, [p + "ffn1_b"]),
            (cell.ffn.ffn2.weight, [p + "ffn2_w"]),
            (cell.ln2.bias, [p + "ffn2_b"]),
            (cell.ln2.gamma, [p + "ln2_g"]), (cell.ln2.beta, [p + "ln2_b"]),
        ]
    out += [(net.mlm.weight, ["out_w"]), (net.mlm.bias, ["out_b"])]
    return out


@functools.lru_cache(maxsize=8)
def _fuser(groups):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fuse(w):
        return [w[n[0]] if len(n) == 1 else
                jnp.concatenate([w[x] for x in n], axis=0) for n in groups]

    return fuse


def program_arrays(pmap, w):
    """The program's leaves made of the benchmark's weights ``w``, in
    one jitted call: ``[array per entry of pmap]``."""
    return _fuser(tuple(tuple(names) for _, names in pmap))(w)
