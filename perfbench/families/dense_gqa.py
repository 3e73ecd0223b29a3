"""The dense GQA decoder: RMSNorm pre-norm, rotary embedding in the
rotate-half pairing, grouped-query attention, gated SiLU FFN, no biases,
untied head.  The program's side is horovod_tpu.models.llama; see
families/__init__.py for what each name is."""

from __future__ import annotations

import math

EMBED = ("embed.table",)
HEAD = ("final_norm.scale", "lm_head.kernel")


def dims(config):
    d = config["hidden_size"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    return d, H, KV, d // H


# --------------------------------------------------------------- the program
def program(config, max_seq=None):
    from horovod_tpu.models import llama
    from perfbench.lib import weights
    d, H, KV, _ = dims(config)
    return llama, llama.LlamaConfig(
        vocab=config["vocab_size"], dim=d,
        n_layers=config["num_hidden_layers"], n_heads=H, n_kv_heads=KV,
        ffn_dim=config["intermediate_size"],
        max_seq=max_seq or config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        dtype=weights.dtype_of(config))


def _attention_fn(name):
    """A training mix's "attention" -> the program's attn_fn."""
    if name == "xla-f32-scores":
        return None
    if name == "xla-input-scores":
        import functools
        from horovod_tpu.models import layers
        return functools.partial(layers.causal_attention, score_dtype=None)
    if name == "flash":
        from horovod_tpu.ops.flash_attention import flash_attention
        return flash_attention
    raise SystemExit(f"unknown attention {name!r}")


def loss(config, traffic):
    model, cfg = program(config, max_seq=max(traffic["seq"], 128))
    attn_fn = _attention_fn(traffic["attention"])

    def loss_fn(p, ids):
        return model.loss_fn(p, ids, cfg, attn_fn=attn_fn,
                             remat=traffic["remat"],
                             ce_chunks=traffic["ce_chunks"])
    return loss_fn


# --------------------------------------------------------------- the weights
def leaf_specs(config):
    d, _, KV, hd = dims(config)
    V, nq, nkv = config["vocab_size"], d, KV * hd
    f = config["intermediate_size"]
    s = 1.0 / math.sqrt(d)
    out = [("embed.table", (V, d), 0.02),
           ("final_norm.scale", (d,), None),
           ("lm_head.kernel", (d, V), s)]
    for i in range(config["num_hidden_layers"]):
        p = f"layers.{i}."
        out += [(p + "attn_norm.scale", (d,), None),
                (p + "wq.kernel", (d, nq), s), (p + "wk.kernel", (d, nkv), s),
                (p + "wv.kernel", (d, nkv), s), (p + "wo.kernel", (nq, d), s),
                (p + "ffn_norm.scale", (d,), None),
                (p + "w_gate.kernel", (d, f), s), (p + "w_up.kernel", (d, f), s),
                (p + "w_down.kernel", (f, d), 1.0 / math.sqrt(f))]
    return out


# ------------------------------------------------------------- the reference
def norm_eps(config):
    # the program's layers.rmsnorm fixes eps; see the configuration's `assumed`
    return float(config.get("assumed", {}).get("rms_norm_eps",
                                               config["rms_norm_eps"]))


def rmsnorm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: [B, S, heads, hd] at positions 0..S-1; rotate-half pairing."""
    import jax.numpy as jnp
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def layer_kinds(config):
    return ["dense"] * config["num_hidden_layers"]


def embed(p, ids, config):
    import jax.numpy as jnp
    return jnp.take(p["embed.table"], ids, axis=0)


def attention(p, x, config, mm):
    """The attention half of a layer, residual included."""
    import jax
    import jax.numpy as jnp
    d, H, KV, hd = dims(config)
    B, S, _ = x.shape
    theta = float(config["rope_theta"])
    h = rmsnorm(x, p["attn_norm.scale"], norm_eps(config))
    q = rope(mm(h, p["wq.kernel"]).reshape(B, S, H, hd), theta)
    k = rope(mm(h, p["wk.kernel"]).reshape(B, S, KV, hd), theta)
    v = mm(h, p["wv.kernel"]).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return x + mm(o.reshape(B, S, H * hd), p["wo.kernel"])


def layer(kind, p, x, config, mm):
    import jax
    x = attention(p, x, config, mm)
    h = rmsnorm(x, p["ffn_norm.scale"], norm_eps(config))
    g = jax.nn.silu(mm(h, p["w_gate.kernel"])) * mm(h, p["w_up.kernel"])
    return x + mm(g, p["w_down.kernel"])


def head(p, x, config, mm):
    return mm(rmsnorm(x, p["final_norm.scale"], norm_eps(config)),
              p["lm_head.kernel"])


# -------------------------------------------------------------- the toy copy
def tiny(config):
    return dict(config, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, vocab_size=256,
                max_position_embeddings=256, torch_dtype="float32")


# ------------------------------------------------------------- the yardstick
def param_counts(config):
    d, _, KV, hd = dims(config)
    L, kv = config["num_hidden_layers"], KV * hd
    per_layer = d * d + 2 * d * kv + d * d + 3 * d * config["intermediate_size"]
    head = d * config["vocab_size"]
    norms = (2 * L + 1) * d
    return {"matmul": L * per_layer + head, "embed": head,
            "total": L * per_layer + 2 * head + norms}


def tick_weight_bytes(config, tokens, itemsize):
    """Every matmul's weights once, however few the tokens."""
    return itemsize * param_counts(config)["matmul"]


def cache_bytes_per_position(config, itemsize):
    _, _, KV, hd = dims(config)
    return 2 * config["num_hidden_layers"] * KV * hd * itemsize


def attn_flops_per_position(config):
    return 4.0 * config["hidden_size"] * config["num_hidden_layers"]


def train_flops_per_token(config, seq):
    """6 per matmul parameter (2 forward, 4 backward) plus causal
    attention's score and value products, 6*seq*hidden per layer
    (2*seq*hidden forward at the causal half, tripled)."""
    n = param_counts(config)["matmul"]
    attn = 6.0 * seq * config["hidden_size"] * config["num_hidden_layers"]
    return 6.0 * n + attn
