"""The latent-attention sparse decoder: low-rank query and key-value
projections with inner norms, a rotary key part that all heads share, a norm
before AND after each sublayer, leading dense layers and then layers of a
shared expert beside sigmoid-routed experts of which this chip holds a share
(``n_routed_experts`` held, ``deployment.n_routed_experts_total`` routed
over).  The program's side is horovod_tpu.models.latent_moe; see
families/__init__.py for what each name is.  Served only: no ``loss``.

Every linear map is without bias; ``norm(x; g) = x rsqrt(mean(x^2) + eps)
g``.  The reference expands K and V per head and attends in query blocks, a
row at a time, so that 128 heads over 2,560 positions fit; the program
attends in the absorbed form over the cached latent.
"""

from __future__ import annotations

import math

EMBED = ("embed.table",)
HEAD = ("final_norm.scale", "lm_head.kernel")
QUERY_BLOCK = 256
ROUTED_CHUNK = 512


def dims(config):
    return {"d": config["hidden_size"], "H": config["num_attention_heads"],
            "qr": config["q_lora_rank"], "kvr": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "v": config["v_head_dim"],
            "f": config["intermediate_size"],
            "fe": config["moe_intermediate_size"],
            "held": config["n_routed_experts"],
            "total": config["deployment"]["n_routed_experts_total"],
            "first": config["deployment"]["first_expert_held"],
            "k": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "L": config["num_hidden_layers"],
            "dense": config["first_k_dense_replace"],
            "V": config["vocab_size"]}


# --------------------------------------------------------------- the program
def program(config, max_seq=None):
    from horovod_tpu.models import latent_moe
    from perfbench.lib import weights
    n = dims(config)
    engine = config.get("engine", {})
    return latent_moe, latent_moe.LatentMoeConfig(
        vocab=n["V"], dim=n["d"], n_layers=n["L"], n_dense=n["dense"],
        n_heads=n["H"], q_rank=n["qr"], kv_rank=n["kvr"],
        qk_nope_dim=n["nope"], qk_rope_dim=n["rope"], v_dim=n["v"],
        ffn_dim=n["f"], moe_hidden=n["fe"], n_experts=n["total"],
        experts_held=n["held"], first_expert=n["first"], top_k=n["k"],
        n_shared=n["shared"], route_scale=float(config["routed_scaling_factor"]),
        norm_eps=float(config["rms_norm_eps"]),
        # the rotary tables end where the engine's longest sequence does
        max_seq=max_seq or engine.get("max_seq_len",
                                      config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        dtype=weights.dtype_of(config))


# --------------------------------------------------------------- the weights
def _attention_leaves(p, n):
    d, H = n["d"], n["H"]
    s = 1.0 / math.sqrt(d)
    return [(p + "input_norm.scale", (d,), None),
            (p + "attn.wq_a.kernel", (d, n["qr"]), s),
            (p + "attn.q_a_norm.scale", (n["qr"],), None),
            (p + "attn.wq_b.kernel", (n["qr"], H * (n["nope"] + n["rope"])),
             1.0 / math.sqrt(n["qr"])),
            (p + "attn.wkv_a.kernel", (d, n["kvr"] + n["rope"]), s),
            (p + "attn.kv_a_norm.scale", (n["kvr"],), None),
            (p + "attn.wkv_b.kernel", (n["kvr"], H * (n["nope"] + n["v"])),
             1.0 / math.sqrt(n["kvr"])),
            (p + "attn.wo.kernel", (H * n["v"], d),
             1.0 / math.sqrt(H * n["v"])),
            (p + "post_attn_norm.scale", (d,), None),
            (p + "pre_mlp_norm.scale", (d,), None),
            (p + "post_mlp_norm.scale", (d,), None)]


def _gated_leaves(p, d, f):
    s = 1.0 / math.sqrt(d)
    return [(p + "w_gate.kernel", (d, f), s), (p + "w_up.kernel", (d, f), s),
            (p + "w_down.kernel", (f, d), 1.0 / math.sqrt(f))]


def leaf_specs(config):
    n = dims(config)
    d, fe, E = n["d"], n["fe"], n["held"]
    s = 1.0 / math.sqrt(d)
    out = [("embed.table", (n["V"], d), 0.02),
           ("final_norm.scale", (d,), None),
           ("lm_head.kernel", (d, n["V"]), s)]
    for i in range(n["L"]):
        p = f"layers.{i}."
        out += _attention_leaves(p, n)
        if i < n["dense"]:
            out += _gated_leaves(p + "ffn.", d, n["f"])
        else:
            out += [(p + "moe.router.kernel", (d, n["total"]), s),
                    (p + "moe.experts.w_gate", (E, d, fe), s),
                    (p + "moe.experts.w_up", (E, d, fe), s),
                    (p + "moe.experts.w_down", (E, fe, d),
                     1.0 / math.sqrt(fe))]
            out += _gated_leaves(p + "moe.shared.", d, fe * n["shared"])
    return out


# ------------------------------------------------------------- the reference
def norm(x, g, config):
    import jax
    import jax.numpy as jnp
    eps = float(config["rms_norm_eps"])
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x: [S, heads, hd] at positions 0..S-1; rotate-half pairing."""
    import jax.numpy as jnp
    S, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def gated(x, w_gate, w_up, w_down, mm):
    import jax
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def layer_kinds(config):
    n = dims(config)
    return ["dense"] * n["dense"] + ["routed"] * (n["L"] - n["dense"])


def embed(p, ids, config):
    import jax.numpy as jnp
    return jnp.take(p["embed.table"], ids, axis=0)


def attention_row(p, x, config, mm):
    """One row's attention sublayer on x [S, d], K and V expanded per head,
    queries a block of QUERY_BLOCK after another."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    S, H, theta = x.shape[0], n["H"], float(config["rope_theta"])
    h = norm(x, p["input_norm.scale"], config)
    c_q = norm(mm(h, p["attn.wq_a.kernel"]), p["attn.q_a_norm.scale"], config)
    q = mm(c_q, p["attn.wq_b.kernel"]).reshape(S, H, n["nope"] + n["rope"])
    q = jnp.concatenate([q[..., :n["nope"]], rope(q[..., n["nope"]:], theta)],
                        -1)
    kv = mm(h, p["attn.wkv_a.kernel"])
    c_kv = norm(kv[:, :n["kvr"]], p["attn.kv_a_norm.scale"], config)
    k_rope = rope(kv[:, None, n["kvr"]:], theta)        # one for all heads
    kv = mm(c_kv, p["attn.wkv_b.kernel"]).reshape(S, H, n["nope"] + n["v"])
    k = jnp.concatenate([kv[..., :n["nope"]],
                         jnp.broadcast_to(k_rope, (S, H, n["rope"]))], -1)
    v = kv[..., n["nope"]:]
    scale = 1.0 / math.sqrt(n["nope"] + n["rope"])
    key_pos = jnp.arange(S)

    def block(args):
        qb, pos = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        s = jnp.where(key_pos[None, None, :] <= pos[None, :, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, -1), v)

    nb = -(-S // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - S
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        nb, QUERY_BLOCK, H, -1)
    pos = jnp.arange(nb * QUERY_BLOCK).reshape(nb, QUERY_BLOCK)
    o = jax.lax.map(block, (qs, pos)).reshape(nb * QUERY_BLOCK, -1)[:S]
    return mm(o, p["attn.wo.kernel"])


def routed(p, t, config, mm):
    """The held experts' part for tokens t [T, d]: sigmoid scores over all
    the router's outputs, the k largest, gates renormalised and scaled; an
    expert that is not held here adds nothing.  An expert multiplies only
    the tokens routed to it, ROUTED_CHUNK of them at a time (a token that is
    not routed to it has gate 0 and would add 0: at 8 of 256 that is 31 of
    32, and in float32 at the highest precision the whole reference would
    take two minutes)."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    T = t.shape[0]
    s = jax.nn.sigmoid(mm(t, p["moe.router.kernel"]))
    top, idx = jax.lax.top_k(s, n["k"])
    g = float(config["routed_scaling_factor"]) * top / (
        jnp.sum(top, -1, keepdims=True) + 1e-20)
    gate = jnp.sum(jax.nn.one_hot(idx, n["total"]) * g[..., None], 1)  # [T, E]
    ch = min(ROUTED_CHUNK, T)
    y = jnp.zeros_like(t)
    for e in range(n["held"]):
        ge = gate[:, n["first"] + e]
        # this expert's tokens first, then the rest; past the end: row T,
        # which reads gate 0 and is dropped on the way back
        order = jnp.concatenate([jnp.argsort(ge == 0, stable=True),
                                 jnp.full((ch,), T)])

        def chunk(i, y, e=e, ge=ge, order=order):
            rows = jax.lax.dynamic_slice(order, (i * ch,), (ch,))
            out = gated(jnp.take(t, rows, axis=0, mode="clip"),
                        p["moe.experts.w_gate"][e], p["moe.experts.w_up"][e],
                        p["moe.experts.w_down"][e], mm)
            w = jnp.take(ge, rows, mode="fill", fill_value=0.0)
            return y.at[rows].add(w[:, None] * out, mode="drop")
        y = jax.lax.fori_loop(0, -(-jnp.sum(ge != 0) // ch), chunk, y)
    return y


def layer(kind, p, x, config, mm):
    import jax
    import jax.numpy as jnp
    B, S, d = x.shape
    a = jax.lax.map(lambda row: attention_row(p, row, config, mm), x)
    x = x + norm(a, p["post_attn_norm.scale"], config)
    h = norm(x, p["pre_mlp_norm.scale"], config)
    if kind == "dense":
        m = gated(h, p["ffn.w_gate.kernel"], p["ffn.w_up.kernel"],
                  p["ffn.w_down.kernel"], mm)
    else:
        m = gated(h, p["moe.shared.w_gate.kernel"], p["moe.shared.w_up.kernel"],
                  p["moe.shared.w_down.kernel"], mm)
        m = m + routed(p, h.reshape(B * S, d), config, mm).reshape(B, S, d)
    return x + norm(m, p["post_mlp_norm.scale"], config)


def head(p, x, config, mm):
    return mm(norm(x, p["final_norm.scale"], config), p["lm_head.kernel"])


# -------------------------------------------------------------- the toy copy
def tiny(config):
    return dict(config, hidden_size=64, intermediate_size=128,
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
                num_attention_heads=4, num_key_value_heads=4,
                num_hidden_layers=3, first_k_dense_replace=1,
                n_routed_experts=4, num_experts_per_tok=2, vocab_size=256,
                max_position_embeddings=256, torch_dtype="float32",
                deployment=dict(config["deployment"],
                                n_routed_experts_total=8, first_expert_held=2))


# ------------------------------------------------------------- the yardstick
def _counts(config):
    """(dims, matmul parameters outside the routed experts, one expert's
    parameters, routed layers)."""
    n = dims(config)
    d, H = n["d"], n["H"]
    attn = (d * n["qr"] + n["qr"] * H * (n["nope"] + n["rope"])
            + d * (n["kvr"] + n["rope"]) + n["kvr"] * H * (n["nope"] + n["v"])
            + H * n["v"] * d)
    expert = 3 * d * n["fe"]
    routed_layers = n["L"] - n["dense"]
    outside = (n["L"] * attn + n["dense"] * 3 * d * n["f"]
               + routed_layers * (d * n["total"] + n["shared"] * expert)
               + d * n["V"])
    return n, outside, expert, routed_layers


def param_counts(config):
    """``matmul`` is what a token is multiplied by HERE in expectation: of its
    k routed experts, held/total are held."""
    n, outside, expert, routed_layers = _counts(config)
    head_ = n["d"] * n["V"]
    vectors = n["L"] * (4 * n["d"] + n["qr"] + n["kvr"]) + n["d"]
    return {"matmul": outside + routed_layers * expert
            * n["k"] * n["held"] / n["total"],
            "embed": head_,
            "total": outside + routed_layers * n["held"] * expert + head_
            + vectors}


def experts_touched(config, tokens):
    """Held experts a layer that ``tokens`` tokens touch in expectation,
    each choosing k of the router's outputs evenly."""
    n = dims(config)
    return n["held"] * (1.0 - (1.0 - n["k"] / n["total"]) ** max(tokens, 0))


def tick_weight_bytes(config, tokens, itemsize):
    """Everything outside the routed experts once, plus the held experts
    that the tick's tokens touch in every routed layer."""
    _, outside, expert, routed_layers = _counts(config)
    return itemsize * (outside + routed_layers * expert
                       * experts_touched(config, tokens))


def cache_bytes_per_position(config, itemsize):
    n = dims(config)
    return n["L"] * (n["kvr"] + n["rope"]) * itemsize


def attn_flops_per_position(config):
    """Absorbed: a head's score against the latent and its rotary part
    (kvr + rope) and its value from the latent (kvr), 2 FLOPs each."""
    n = dims(config)
    return 2.0 * n["H"] * (2 * n["kvr"] + n["rope"]) * n["L"]


def train_flops_per_token(config, seq):
    """Not trained here (16 bytes a parameter fit no chip); the convention
    of the other families, for the contract's sake."""
    n = dims(config)
    attn = 6.0 * seq * n["H"] * (n["nope"] + n["rope"] + n["v"]) / 2 * n["L"]
    return 6.0 * param_counts(config)["matmul"] + attn


def expert_required_seconds(config, peaks, touched, assignments, itemsize=2):
    """Least seconds for the routed experts' work: reading ``touched``
    experts' weights once each and multiplying ``assignments`` rows by an
    expert's three matrices.  (seconds, which bound binds)."""
    expert = _counts(config)[2]
    t_bytes = touched * expert * itemsize / (peaks["hbm_gbps"] * 1e9)
    t_flops = 2.0 * assignments * expert / (peaks["bf16_tflops"] * 1e12)
    return max(t_bytes, t_flops), ("flops" if t_flops >= t_bytes else "bytes")


def expert_op_types(config):
    """The output types of the device ops that are one expert's tile of rows
    (the program's ``EXPERT_TILE`` rows by the expert's width or the model's):
    what a trace's short names show of ops inside the experts' loops, where
    no parameter's name reaches.  Empty where the program has no such module
    (the parent commit)."""
    try:
        from horovod_tpu.models.latent_moe import EXPERT_TILE
    except ImportError:
        return []
    n = dims(config)
    return [f"[{EXPERT_TILE},{n['fe']}]", f"[{EXPERT_TILE},{n['d']}]"]


def pool_op_types(config):
    """The shapes of device ops that make or move the latent pool or a
    gather of it: ``[..., block, kv_lora_rank + qk_rope_head_dim]``."""
    n = dims(config)
    e = config["engine"]
    return [f",{e['block_size']},{n['kvr'] + n['rope']}]",
            f",{-(-e['max_seq_len'] // e['block_size']) * e['block_size']},"
            f"{n['kvr'] + n['rope']}]"]


def window_counts(ctx):
    """What the engine's tick counters (``stats()["moe"]``) grew by between
    the window's marks, {name: delta}; None where the program counts no such
    thing (the parent commit) or no tick ran."""
    a, b = (ctx["marks"][k]["stats"].get("moe") for k in ("start", "end"))
    if not a or not b or b["ticks"] == a["ticks"]:
        return None
    return {k: b[k] - a[k] for k in b}
