"""A second family that only the benchmark's tests use, to prove that a
model of another kind arrives as files alone: dense_gqa's attention in front
of a top-k switch FFN (router, GELU experts without gate, gates renormalised
over the chosen experts when k > 1), served drop-free.  The program's side is
horovod_tpu.models.moe_llama.  No configuration file, no cell, never on the
chip; training is left out (the program's training loss drops tokens by
capacity), so there is no ``loss``."""

from __future__ import annotations

import math

from perfbench.families import dense_gqa as G

EMBED, HEAD = G.EMBED, G.HEAD
embed, head = G.embed, G.head
cache_bytes_per_position = G.cache_bytes_per_position
attn_flops_per_position = G.attn_flops_per_position

def _experts(config):
    return (config["num_local_experts"], config["num_experts_per_tok"],
            config["moe_intermediate_size"])


def program(config, max_seq=None):
    from horovod_tpu.models import moe_llama
    # the attention half's sizes, as dense_gqa reads them
    _, a = G.program(dict(config, intermediate_size=1), max_seq)
    E, k, h = _experts(config)
    return moe_llama, moe_llama.MoeLlamaConfig(
        vocab=a.vocab, dim=a.dim, n_layers=a.n_layers, n_heads=a.n_heads,
        n_kv_heads=a.n_kv_heads, moe_hidden=h, n_experts=E,
        experts_per_token=k, max_seq=a.max_seq, rope_theta=a.rope_theta,
        dtype=a.dtype)


def leaf_specs(config):
    d, _, KV, hd = G.dims(config)
    E, _, h = _experts(config)
    s = 1.0 / math.sqrt(d)
    out = G.leaf_specs(dict(config, num_hidden_layers=0, intermediate_size=0))
    for i in range(config["num_hidden_layers"]):
        p = f"layers.{i}."
        out += [(p + "attn_norm.scale", (d,), None),
                (p + "wq.kernel", (d, d), s), (p + "wk.kernel", (d, KV * hd), s),
                (p + "wv.kernel", (d, KV * hd), s), (p + "wo.kernel", (d, d), s),
                (p + "ffn_norm.scale", (d,), None),
                (p + "moe.router", (d, E), s), (p + "moe.wi", (E, d, h), s),
                (p + "moe.wo", (E, h, d), 1.0 / math.sqrt(h))]
    return out


def layer_kinds(config):
    return ["switch"] * config["num_hidden_layers"]


def layer(kind, p, x, config, mm):
    import jax
    import jax.numpy as jnp
    E, k, _ = _experts(config)
    x = G.attention(p, x, config, mm)
    B, S, d = x.shape
    t = G.rmsnorm(x, p["ffn_norm.scale"], G.norm_eps(config)).reshape(B * S, d)
    top, idx = jax.lax.top_k(jax.nn.softmax(mm(t, p["moe.router"]), -1), k)
    if k > 1:
        top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], 1)      # [T, E]
    y = sum(gate[:, e, None] * mm(jax.nn.gelu(mm(t, p["moe.wi"][e])),
                                  p["moe.wo"][e]) for e in range(E))
    return x + y.reshape(B, S, d)


def tiny(config):
    return dict(config, hidden_size=64, moe_intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, num_local_experts=4, vocab_size=256,
                max_position_embeddings=256, torch_dtype="float32")


# the configuration the tests run: the family has no file under configs/
TOY = tiny({"family": "moe_switch", "num_experts_per_tok": 2,
            "rope_theta": 1e6, "rms_norm_eps": 1e-5,
            "assumed": {"rms_norm_eps": 1e-6}})


def param_counts(config):
    d, _, KV, hd = G.dims(config)
    E, k, h = _experts(config)
    L, head_ = config["num_hidden_layers"], d * config["vocab_size"]
    rest = 2 * d * d + 2 * d * KV * hd + d * E       # attention and router
    return {"matmul": L * (rest + k * 2 * d * h) + head_, "embed": head_,
            "total": L * (rest + E * 2 * d * h) + 2 * head_ + (2 * L + 1) * d}


def tick_weight_bytes(config, tokens, itemsize):
    """At the least the k experts one token chooses: every token of the
    tick may choose the same."""
    return itemsize * param_counts(config)["matmul"]


def train_flops_per_token(config, seq):
    return (6.0 * param_counts(config)["matmul"]
            + 6.0 * seq * config["hidden_size"] * config["num_hidden_layers"])
