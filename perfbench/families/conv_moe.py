"""The short-convolution-and-attention sparse decoder: every layer mixes its
tokens by ``layer_types[i]`` — a gated SHORT CONVOLUTION over the last
``conv_L_cache`` positions (``conv``) or grouped-query attention over the
whole causal context with a norm on each query and key head before the
rotary encoding (``full_attention``) — and then feeds them forward: the
first ``num_dense_layers`` through a dense gated FFN, the others through
``num_experts`` small SiLU-gated experts of which a token takes
``num_experts_per_tok``, chosen by a sigmoid router with a bias that picks
and does not weigh.  No shared expert; the head is the embedding.  The
program's side is horovod_tpu.models.conv_moe; see families/__init__.py for
what each name is.  Served only: no ``loss``.

Every linear map is without bias; ``norm(x; g) = x rsqrt(mean(x^2) + eps)
g``.  Layer i on x [T, d] (K = conv_L_cache):

  h = norm(x; op_norm)
  conv:  (B, C, X) = split3(h W_in);  u = B * X;
         v_t = sum_{j<K} w[:, j] * u_{t-K+1+j}   (u_s = 0 for s < 0)
         x = x + (C * v) W_out
  attn:  q, k, v = h W_q, h W_k, h W_v by head; q, k = norm over head_dim
         (q_norm, k_norm), THEN rotate-half rotary; scores q.k /
         sqrt(head_dim) over j <= t, softmax in float32
         x = x + concat(heads) W_o
  h2 = norm(x; ffn_norm)
  dense:  x = x + (silu(h2 W_1) * (h2 W_3)) W_2
  routed: s = sigmoid(h2 W_r); idx = the k largest of s + b;
          g_e = scale * s_e / (sum_{idx} s + 1e-6)
          x = x + sum_{e in idx} g_e (silu(h2 W_gate,e) * (h2 W_up,e)) W_down,e
  logits = norm(x_L; final_norm) E^T        (E the embedding)

The reference runs the convolution as K shifted products over a zero-padded
sequence, the attention a row at a time, the experts as a loop over all of
them (a token's gate is 0 where it was not chosen).
"""

from __future__ import annotations

import math

EMBED = ("embed.table",)
HEAD = ("final_norm.scale", "embed.table")
GATE_EPS = 1e-6


def dims(config):
    L = config["num_hidden_layers"]
    d, H = config["hidden_size"], config["num_attention_heads"]
    return {"d": d, "H": H, "kv": config["num_key_value_heads"],
            "hd": config.get("head_dim") or d // H,
            "f": config["intermediate_size"],
            "fe": config["moe_intermediate_size"],
            "E": config["num_experts"], "k": config["num_experts_per_tok"],
            "K": config["conv_L_cache"], "L": L, "V": config["vocab_size"],
            "dense": min(config["num_dense_layers"], L),
            # the published list is kept whole; the first L entries run
            "types": tuple(config["layer_types"][:L])}


def _layers(config):
    """(conv layers, attention layers, routed layers) of the L that run."""
    n = dims(config)
    conv = sum(t == "conv" for t in n["types"])
    return conv, n["L"] - conv, n["L"] - n["dense"]


# --------------------------------------------------------------- the program
def program(config, max_seq=None):
    from horovod_tpu.models import conv_moe
    from perfbench.lib import weights
    n = dims(config)
    engine = config.get("engine", {})
    return conv_moe, conv_moe.ConvMoeConfig(
        vocab=n["V"], dim=n["d"], n_layers=n["L"], n_heads=n["H"],
        n_kv_heads=n["kv"], head_dim=n["hd"],
        layer_types=tuple(config["layer_types"]), conv_taps=n["K"],
        n_dense_layers=n["dense"], ffn_dim=n["f"], moe_hidden=n["fe"],
        n_experts=n["E"], experts_held=n["E"], first_expert=0, top_k=n["k"],
        route_scale=float(config["routed_scaling_factor"]),
        norm_eps=float(config["norm_eps"]),
        # the rotary tables end where the engine's longest sequence does
        max_seq=max_seq or engine.get("max_seq_len",
                                      config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        dtype=weights.dtype_of(config))


# --------------------------------------------------------------- the weights
def leaf_specs(config):
    n = dims(config)
    d, f, fe, E, hd = n["d"], n["f"], n["fe"], n["E"], n["hd"]
    s = 1.0 / math.sqrt(d)
    out = [("embed.table", (n["V"], d), 0.02),
           ("final_norm.scale", (d,), None)]
    for i, kind in enumerate(n["types"]):
        p = f"layers.{i}."
        out.append((p + "op_norm.scale", (d,), None))
        if kind == "conv":
            out += [(p + "conv.in_proj.kernel", (d, 3 * d), s),
                    (p + "conv.taps", (d, n["K"]), 1.0 / math.sqrt(n["K"])),
                    (p + "conv.out_proj.kernel", (d, d), s)]
        else:
            out += [(p + "attn.wq.kernel", (d, n["H"] * hd), s),
                    (p + "attn.wk.kernel", (d, n["kv"] * hd), s),
                    (p + "attn.wv.kernel", (d, n["kv"] * hd), s),
                    (p + "attn.wo.kernel", (n["H"] * hd, d),
                     1.0 / math.sqrt(n["H"] * hd)),
                    (p + "attn.q_norm.scale", (hd,), None),
                    (p + "attn.k_norm.scale", (hd,), None)]
        out.append((p + "ffn_norm.scale", (d,), None))
        if i < n["dense"]:
            out += [(p + "ffn.w1.kernel", (d, f), s),
                    (p + "ffn.w3.kernel", (d, f), s),
                    (p + "ffn.w2.kernel", (f, d), 1.0 / math.sqrt(f))]
        else:
            out += [(p + "moe.router.kernel", (d, E), s),
                    (p + "moe.bias", (E,), 0.1),
                    (p + "moe.experts.w_gate", (E, d, fe), s),
                    (p + "moe.experts.w_up", (E, d, fe), s),
                    (p + "moe.experts.w_down", (E, fe, d),
                     1.0 / math.sqrt(fe))]
    return out


# ------------------------------------------------------------- the reference
def norm(x, g, config):
    import jax
    import jax.numpy as jnp
    eps = float(config["norm_eps"])
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


def layer_kinds(config):
    """A layer's kind is its mixer's and its feed-forward part's: the
    published list gives ``conv+dense`` (the leading layers), ``conv+routed``
    and ``attn+routed``."""
    n = dims(config)
    return [("conv" if t == "conv" else "attn")
            + ("+dense" if i < n["dense"] else "+routed")
            for i, t in enumerate(n["types"])]


def embed(p, ids, config):
    import jax.numpy as jnp
    return jnp.take(p["embed.table"], ids, axis=0)


def short_conv(p, h, config, mm, fault=None):
    """The conv operator on its normed input h [B, S, d]: K shifted products
    over the sequence padded with K - 1 zeros in front."""
    import jax.numpy as jnp
    K, S = dims(config)["K"], h.shape[1]
    b, c, x = jnp.split(mm(h, p["conv.in_proj.kernel"]), 3, axis=-1)
    if fault == "b_c_swapped":
        b, c = c, b
    u = b * x
    # one more zero in front moves every tap one position into the past
    front = K - 1 + (fault == "taps_moved")
    up = jnp.pad(u, ((0, 0), (front, 0), (0, 0)))
    w = p["conv.taps"]
    v = sum(w[:, j] * up[:, j:j + S] for j in range(K))
    return mm(c * v, p["conv.out_proj.kernel"])


def attention_row(p, h, config, mm, fault=None):
    """One row's attention on its normed input h [S, d]."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    S, H, KV, hd = h.shape[0], n["H"], n["kv"], n["hd"]
    theta = float(config["rope_theta"])
    q = mm(h, p["attn.wq.kernel"]).reshape(S, H, hd)
    k = mm(h, p["attn.wk.kernel"]).reshape(S, KV, hd)
    v = mm(h, p["attn.wv.kernel"]).reshape(S, KV, hd)
    qn = lambda t: norm(t, p["attn.q_norm.scale"], config)
    kn = lambda t: norm(t, p["attn.k_norm.scale"], config)
    if fault == "norm_after_rope":
        q, k = qn(rope(q, theta)), kn(rope(k, theta))
    else:
        q, k = rope(qn(q), theta), rope(kn(k), theta)
    q = q.reshape(S, KV, H // KV, hd)
    s = jnp.einsum("qhrd,khd->hrqk", q, k) / math.sqrt(hd)
    see = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(see[None, None], s, -jnp.inf)
    o = jnp.einsum("hrqk,khd->qhrd", jax.nn.softmax(s, -1), v)
    return mm(o.reshape(S, H * hd), p["attn.wo.kernel"])


def route(p, h, config, mm, fault=None):
    """[T, E] gates from h [T, d]: the k experts with the largest ``sigmoid
    + bias`` hold ``scale * sigmoid / (the chosen sigmoids' sum + 1e-6)``,
    the others 0."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    s = jax.nn.sigmoid(mm(h, p["moe.router.kernel"]))
    b = 0.0 if fault == "no_bias" else p["moe.bias"]
    _, idx = jax.lax.top_k(s + b, n["k"])
    top = jnp.take_along_axis(s + (b if fault == "bias_in_gates" else 0.0),
                              idx, -1)
    g = float(config["routed_scaling_factor"]) * top / (
        top.sum(-1, keepdims=True) + GATE_EPS)
    return jnp.sum(jax.nn.one_hot(idx, n["E"]) * g[..., None], 1)


def experts(p, t, gate, config, mm):
    """sum_e gate[:, e] * expert_e(t) for tokens t [T, d]: a loop over all
    the experts, each on every token."""
    import jax
    y = 0.0
    for e in range(dims(config)["E"]):
        out = mm(jax.nn.silu(mm(t, p["moe.experts.w_gate"][e]))
                 * mm(t, p["moe.experts.w_up"][e]),
                 p["moe.experts.w_down"][e])
        y = y + gate[:, e:e + 1] * out
    return y


def layer(kind, p, x, config, mm, fault=None):
    """``fault`` is the tests', what this model does NOT do: ``taps_moved``
    (every tap one position into the past), ``b_c_swapped``,
    ``norm_after_rope``, ``no_bias`` (the router picks without it),
    ``bias_in_gates`` (it weighs too)."""
    import jax
    B, S, d = x.shape
    mixer, ffn = kind.split("+")
    h = norm(x, p["op_norm.scale"], config)
    if mixer == "conv":
        x = x + short_conv(p, h, config, mm, fault)
    else:
        x = x + jax.lax.map(
            lambda row: attention_row(p, row, config, mm, fault), h)
    h2 = norm(x, p["ffn_norm.scale"], config)
    if ffn == "dense":
        return x + mm(jax.nn.silu(mm(h2, p["ffn.w1.kernel"]))
                      * mm(h2, p["ffn.w3.kernel"]), p["ffn.w2.kernel"])
    t = h2.reshape(B * S, d)
    return x + experts(p, t, route(p, t, config, mm, fault), config,
                       mm).reshape(B, S, d)


def head(p, x, config, mm):
    return mm(norm(x, p["final_norm.scale"], config), p["embed.table"].T)


# -------------------------------------------------------------- the toy copy
def tiny(config):
    """Toy widths; seven layers of the published list (two dense conv
    layers, then attention, three conv, attention), so that each cache kind
    has more than one layer."""
    return dict(config, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=96,
                moe_intermediate_size=32, num_experts=8,
                num_experts_per_tok=2, num_hidden_layers=7, vocab_size=256,
                max_position_embeddings=256, torch_dtype="float32")


# ------------------------------------------------------------- the yardstick
def _counts(config):
    """(dims, matrix parameters outside the experts — the embedding as the
    head among them —, one expert's)."""
    n = dims(config)
    d = n["d"]
    conv, attn, routed = _layers(config)
    outside = (conv * (4 * d * d + n["K"] * d)
               + attn * (2 * d * n["H"] * n["hd"] + 2 * d * n["kv"] * n["hd"])
               + n["dense"] * 3 * d * n["f"] + routed * d * n["E"]
               + d * n["V"])
    return n, outside, 3 * d * n["fe"]


def param_counts(config):
    """``matmul`` counts the embedding once, as the head's matrix (a token's
    own row of it is a lookup); ``total`` every leaf once (the head is
    tied)."""
    n, outside, expert = _counts(config)
    conv, attn, routed = _layers(config)
    vectors = (n["L"] * 2 * n["d"] + n["d"] + attn * 2 * n["hd"]
               + routed * n["E"])
    return {"matmul": outside + routed * n["k"] * expert,
            "embed": n["d"] * n["V"],
            "total": outside + routed * n["E"] * expert + vectors}


def experts_touched(config, tokens):
    """Experts a routed layer that ``tokens`` tokens touch in expectation,
    each choosing k of E evenly."""
    n = dims(config)
    return n["E"] * (1.0 - (1.0 - n["k"] / n["E"]) ** max(tokens, 0))


def tick_weight_bytes(config, tokens, itemsize):
    """Everything outside the experts once, plus the experts that the tick's
    tokens touch in every routed layer."""
    _, outside, expert = _counts(config)
    return itemsize * (outside + _layers(config)[2] * expert
                       * experts_touched(config, tokens))


def cache_bytes_per_position(config, itemsize):
    """K and V of one position that a new token READS: the attention layers
    alone.  A conv layer reads no context (its state is two columns a slot,
    :func:`state_bytes_per_slot`); counted here it would let a sound tick
    read over 100% of its roofline."""
    n = dims(config)
    return _layers(config)[1] * 2 * n["kv"] * n["hd"] * itemsize


def state_bytes_per_slot(config, columns, itemsize):
    """What the conv layers' state kind holds a slot at ``columns`` columns
    (the program's paged.state_columns: the K - 1 a tick reads back plus the
    verify row's)."""
    return _layers(config)[0] * columns * dims(config)["d"] * itemsize


def attn_flops_per_position(config):
    """Score and value FLOPs of one new token against one position of its
    context, the attention layers alone."""
    n = dims(config)
    return 4.0 * n["H"] * n["hd"] * _layers(config)[1]


def train_flops_per_token(config, seq):
    """Not trained here (what this configuration adds exists only where
    there is a cache); the convention of the other families, for the
    contract's sake."""
    n = dims(config)
    return (6.0 * param_counts(config)["matmul"]
            + 6.0 * seq * n["H"] * n["hd"] * _layers(config)[1])


def expert_required_seconds(config, peaks, touched, assignments, itemsize=2):
    """Least seconds for the experts' work: reading ``touched`` experts'
    weights once each and multiplying ``assignments`` rows by an expert's
    three matrices.  (seconds, which bound binds)."""
    expert = _counts(config)[2]
    t_bytes = touched * expert * itemsize / (peaks["hbm_gbps"] * 1e9)
    t_flops = 2.0 * assignments * expert / (peaks["bf16_tflops"] * 1e12)
    return max(t_bytes, t_flops), ("flops" if t_flops >= t_bytes else "bytes")


def expert_op_types(config):
    """The output types of the device ops that are one expert's tile of rows
    (the program's ``EXPERT_TILE`` rows by the expert's width or the
    model's).  Empty where the program has no such module (the parent)."""
    try:
        from horovod_tpu.models.conv_moe import EXPERT_TILE
    except ImportError:
        return []
    n = dims(config)
    return [f"[{EXPERT_TILE},{n['fe']}]", f"[{EXPERT_TILE},{n['d']}]"]


def window_counts(ctx):
    """What the engine's tick counters (``stats()["moe"]``) grew by between
    the window's marks, {name: delta}; None where the program counts no such
    thing (the parent commit) or no tick ran."""
    a, b = (ctx["marks"][k]["stats"].get("moe") for k in ("start", "end"))
    if not a or not b or b["ticks"] == a["ticks"]:
        return None
    return {k: b[k] - a[k] for k in b}


def state_counts(ctx, kind="conv"):
    """What the engine's counters of the state cache kind
    (``stats()["kv_pool"]["kinds"][kind]``) grew by between the window's
    marks, {name: delta}, with the ticks; None where the program has no such
    kind (the parent commit) or no tick ran."""
    a, b = (ctx["marks"][k]["stats"].get("kv_pool", {}).get("kinds", {})
            .get(kind) for k in ("start", "end"))
    ticks = ctx["marks"]["end"]["tick"] - ctx["marks"]["start"]["tick"]
    if not a or not b or not ticks:
        return None
    return dict({k: b[k] - a[k] for k in b if k.endswith("_ticks")},
                ticks=ticks)


def state_columns(config):
    """Columns a slot of the state kind's pool: the K - 1 a tick reads back
    plus a verify row's ``1 + spec_k`` (the program's paged.state_columns,
    reckoned here from the configuration file alone)."""
    e = config["engine"]
    return (dims(config)["K"] - 1
            + (1 + e.get("spec_k", 4) if e.get("spec_decode", True) else 1))


def pool_op_types(config, kind="conv"):
    """The output types of device ops that make or move the ``conv`` kind's
    pool (the one kind this family reads by its ops): the stacked state
    ``[conv layers, slots, columns, d]``, which its scatters return whole,
    and one layer of it.  The state's READ makes ``[rows, d]`` like a dozen
    other ops of a layer: :func:`pool_ops_ms` tells it by its name."""
    n, e = dims(config), config["engine"]
    tail = f"{e['max_slots']},{state_columns(config)},{n['d']}]"
    return [f"[{_layers(config)[0]},{tail}", f"[{tail}"]


def _ops(ctx):
    tr = ctx["trace"]
    return (tr.get("ops_s", {}), tr["module_count"]) \
        if tr and tr.get("module_count") else ({}, 0)


def _top(label, hits, ticks):
    top = sorted(hits.items(), key=lambda kv: -kv[1])[:5]
    print(f"perfbench: {label} ms/tick "
          + "; ".join(f"{n}={1e3 * s / ticks:.3f}" for n, s in top),
          flush=True)


def pool_ops_ms(ctx, kind="conv"):
    """Device self-time a tick, in ms, of the ops shaped like the state
    kind's pool (:func:`pool_op_types`) and of the gathers that make
    ``[rows, d]`` from no parameter (the state's read: the embedding's
    gather names its table); prints the five costliest.  None without a
    trace or such ops."""
    ops, ticks = _ops(ctx)
    types = pool_op_types(ctx["config"], kind)
    d = dims(ctx["config"])["d"]
    hits = {n: s for n, s in ops.items() if any(t in n for t in types)
            or (n.startswith("gather") and n.split()[1].endswith(f",{d}]")
                and "params" not in n)}
    if not hits:
        return None
    _top(f"{kind} pool ops", hits, ticks)
    return 1e3 * sum(hits.values()) / ticks


#: the leaves of the conv operator, as a trace's short names spell the first
#: parameter an op touches (lib/tracered.short_name)
MIXER_LEAVES = ("conv_in_proj", "conv_taps", "conv_out_proj")


def mixer_share(ctx):
    """Device self-time of the ops that are the conv operator's own over the
    tick program's device time, in %: those that make ``[.., 3 d]`` (the
    input projection and its split) and those whose first parameter is a
    leaf of the operator (:data:`MIXER_LEAVES`: the taps' fusion, the two
    projections).  What it cannot tell apart is left OUT, so the share errs
    low: a gate-and-tap fusion that the compiler cut off its parameters
    makes ``[rows, d]`` like the norms and the residual sums, and an output
    projection whose text names no parameter has the attention's ``wo``
    shape.  Prints the five costliest.  None without a trace or such
    ops."""
    ops, ticks = _ops(ctx)
    wide = f",{3 * dims(ctx['config'])['d']}]"
    hits = {n: s for n, s in ops.items()
            if wide in n or any(leaf in n for leaf in MIXER_LEAVES)}
    if not hits or not ctx["trace"].get("module_s"):
        return None
    _top("conv mixer ops", hits, ticks)
    return 100.0 * sum(hits.values()) / ctx["trace"]["module_s"]
