"""The window-and-global sparse decoder: grouped-query attention that is of
two kinds layer by layer (``sliding_window_layout``: a window of
``sliding_window_size`` keys, or the whole causal context), rotary only on
the layers of ``rope_layout`` (the others have no positional encoding at
all), and in every layer ``moe_num_primary_experts`` small ReLU-gated
experts of which a token takes ``moe_num_active_primary_experts``, chosen by
a router that reads the ATTENTION's input and applied to the attention's
output.  No dense layer, no shared expert.  The program's side is
horovod_tpu.models.swa_moe; see families/__init__.py for what each name is.
Served only: no ``loss``.

Every linear map is without bias; ``norm(x; g) = x rsqrt(mean(x^2) + eps)
g``.  Layer i on x [T, d]:

  h = norm(x; input_norm);  r = h W_r;  top, idx = the k largest of r;
  g = softmax(top)                      (float32, over the k chosen)
  q, k, v = h W_q, h W_k, h W_v by head; rotate-half rotary on q, k where
  rope_layout[i]; scores q.k / sqrt(head_dim) over j <= t, and on a window
  layer t - window < j <= t; softmax in float32
  x = x + concat(heads) W_o
  h2 = norm(x; post_attn_norm)
  x = x + sum_{e in idx} g_e (relu(h2 W_gate,e) * (h2 W_up,e)) W_down,e

The reference attends in query blocks, a row at a time, a window layer over
the keys its block can see only, so that a 14,848-position row fits; an
expert multiplies only the tokens routed to it.
"""

from __future__ import annotations

import math

EMBED = ("embed.table",)
HEAD = ("final_norm.scale", "lm_head.kernel")
QUERY_BLOCK = 256
ROUTED_CHUNK = 512
#: the context at which the contract's single numbers are reckoned
#: (cache_bytes_per_position, attn_flops_per_position): the mean live
#: context of the cell's traffic, a prompt of the log-normal's mean (about
#: 8,700 tokens) with half its answer served
MEAN_LIVE_CONTEXT = 8900


def dims(config):
    L = config["num_hidden_layers"]
    return {"d": config["hidden_size"], "H": config["num_attention_heads"],
            "kv": config["num_key_value_heads"], "hd": config["head_dim"],
            "fe": config["moe_ffn_hidden_size"],
            "E": config["moe_num_primary_experts"],
            "k": config["moe_num_active_primary_experts"],
            "L": L, "V": config["vocab_size"],
            "W": config["sliding_window_size"],
            # the published layouts are kept whole; the first L entries run
            "windowed": tuple(config["sliding_window_layout"][:L]),
            "rotary": tuple(config["rope_layout"][:L])}


# --------------------------------------------------------------- the program
def program(config, max_seq=None):
    from horovod_tpu.models import swa_moe
    from perfbench.lib import weights
    n = dims(config)
    engine = config.get("engine", {})
    return swa_moe, swa_moe.SwaMoeConfig(
        vocab=n["V"], dim=n["d"], n_layers=n["L"], n_heads=n["H"],
        n_kv_heads=n["kv"], head_dim=n["hd"], window=n["W"],
        window_layout=n["windowed"], rope_layout=n["rotary"],
        moe_hidden=n["fe"], n_experts=n["E"], experts_held=n["E"],
        first_expert=0, top_k=n["k"], norm_eps=float(config["rms_norm_eps"]),
        # the rotary tables end where the engine's longest sequence does
        max_seq=max_seq or engine.get("max_seq_len",
                                      config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        dtype=weights.dtype_of(config))


# --------------------------------------------------------------- the weights
def leaf_specs(config):
    n = dims(config)
    d, fe, E = n["d"], n["fe"], n["E"]
    s = 1.0 / math.sqrt(d)
    out = [("embed.table", (n["V"], d), 0.02),
           ("final_norm.scale", (d,), None),
           ("lm_head.kernel", (d, n["V"]), s)]
    for i in range(n["L"]):
        p = f"layers.{i}."
        out += [(p + "input_norm.scale", (d,), None),
                (p + "attn.wq.kernel", (d, n["H"] * n["hd"]), s),
                (p + "attn.wk.kernel", (d, n["kv"] * n["hd"]), s),
                (p + "attn.wv.kernel", (d, n["kv"] * n["hd"]), s),
                (p + "attn.wo.kernel", (n["H"] * n["hd"], d),
                 1.0 / math.sqrt(n["H"] * n["hd"])),
                (p + "post_attn_norm.scale", (d,), None),
                (p + "moe.router.kernel", (d, E), s),
                (p + "moe.experts.w_gate", (E, d, fe), s),
                (p + "moe.experts.w_up", (E, d, fe), s),
                (p + "moe.experts.w_down", (E, fe, d), 1.0 / math.sqrt(fe))]
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


def layer_kinds(config):
    """A layer's kind is its attention's: ``window`` or ``global``, with
    ``+rope`` where it has a rotary encoding (the published layouts give
    ``global`` and ``window+rope``)."""
    n = dims(config)
    return [("window" if w else "global") + ("+rope" if r else "")
            for w, r in zip(n["windowed"], n["rotary"])]


def embed(p, ids, config):
    import jax.numpy as jnp
    return jnp.take(p["embed.table"], ids, axis=0)


def attention_row(kind, p, h, config, mm):
    """One row's attention on its normed input h [S, d]: queries a block of
    QUERY_BLOCK after another; a window layer's block scores only the
    ``window + QUERY_BLOCK - 1`` keys that any of its queries can see."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    S, H, KV, hd, W = h.shape[0], n["H"], n["kv"], n["hd"], n["W"]
    q = mm(h, p["attn.wq.kernel"]).reshape(S, H, hd)
    k = mm(h, p["attn.wk.kernel"]).reshape(S, KV, hd)
    v = mm(h, p["attn.wv.kernel"]).reshape(S, KV, hd)
    if kind.endswith("+rope"):
        theta = float(config["rope_theta"])
        q, k = rope(q, theta), rope(k, theta)
    windowed = kind.startswith("window")
    scale = 1.0 / math.sqrt(hd)
    QB = QUERY_BLOCK
    nb = -(-S // QB)
    # keys a block sees: all of them, or those from its first query's
    # window on (the keys are padded in front so that every slice exists)
    span = min(W + QB - 1, nb * QB) if windowed else nb * QB
    front = span - QB if windowed else 0
    pad = lambda a: jnp.pad(a, ((front, nb * QB - S), (0, 0), (0, 0)))
    kp, vp = pad(k), pad(v)
    qs = jnp.pad(q, ((0, nb * QB - S), (0, 0), (0, 0))).reshape(
        nb, QB, KV, H // KV, hd)

    def block(args):
        qb, b = args
        q_pos = b * QB + jnp.arange(QB)
        first = b * QB - front if windowed else 0    # position of key 0
        start = first + front                        # its index in kp
        kb = jax.lax.dynamic_slice_in_dim(kp, start, span)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, span)
        k_pos = first + jnp.arange(span)
        d = q_pos[:, None] - k_pos[None, :]
        see = (d >= 0) & (k_pos[None, :] >= 0)
        if windowed:
            see = see & (d < W)
        s = jnp.einsum("qhrd,khd->hrqk", qb, kb) * scale
        s = jnp.where(see[None, None], s, -jnp.inf)
        return jnp.einsum("hrqk,khd->qhrd", jax.nn.softmax(s, -1), vb)

    o = jax.lax.map(block, (qs, jnp.arange(nb))).reshape(nb * QB, H * hd)[:S]
    return mm(o, p["attn.wo.kernel"])


def route(p, h, config, mm):
    """[T, E] gates from the attention's input h [T, d]: the k largest
    router logits, the softmax over those k, 0 elsewhere."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    top, idx = jax.lax.top_k(mm(h, p["moe.router.kernel"]), n["k"])
    g = jax.nn.softmax(top, -1)
    return jnp.sum(jax.nn.one_hot(idx, n["E"]) * g[..., None], 1)


def experts(p, t, gate, config, mm):
    """sum_e gate[:, e] * expert_e(t) for tokens t [T, d].  An expert
    multiplies only the tokens routed to it, ROUTED_CHUNK of them at a time
    (a token that is not routed to it has gate 0 and would add 0: at 6 of 64
    that is ten in eleven, and float32 at the highest precision is slow)."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    T = t.shape[0]
    ch = min(ROUTED_CHUNK, T)
    y = jnp.zeros_like(t)
    for e in range(n["E"]):
        ge = gate[:, e]
        # this expert's tokens first, then the rest; past the end: row T,
        # which reads gate 0 and is dropped on the way back
        order = jnp.concatenate([jnp.argsort(ge == 0, stable=True),
                                 jnp.full((ch,), T)])

        def chunk(i, y, e=e, ge=ge, order=order):
            rows = jax.lax.dynamic_slice(order, (i * ch,), (ch,))
            x = jnp.take(t, rows, axis=0, mode="clip")
            out = mm(jax.nn.relu(mm(x, p["moe.experts.w_gate"][e]))
                     * mm(x, p["moe.experts.w_up"][e]),
                     p["moe.experts.w_down"][e])
            w = jnp.take(ge, rows, mode="fill", fill_value=0.0)
            return y.at[rows].add(w[:, None] * out, mode="drop")
        y = jax.lax.fori_loop(0, -(-jnp.sum(ge != 0) // ch), chunk, y)
    return y


def layer(kind, p, x, config, mm, route_from="attention_input"):
    """``route_from`` is the tests': ``"attention_output"`` routes from h2,
    which is what this model does NOT do."""
    import jax
    B, S, d = x.shape
    h = norm(x, p["input_norm.scale"], config)
    x = x + jax.lax.map(
        lambda row: attention_row(kind, p, row, config, mm), h)
    h2 = norm(x, p["post_attn_norm.scale"], config)
    r = h if route_from == "attention_input" else h2
    gate = route(p, r.reshape(B * S, d), config, mm)
    return x + experts(p, h2.reshape(B * S, d), gate, config, mm).reshape(
        B, S, d)


def head(p, x, config, mm):
    return mm(norm(x, p["final_norm.scale"], config), p["lm_head.kernel"])


# -------------------------------------------------------------- the toy copy
def tiny(config):
    """Toy widths and a toy window of 16 under the toy engine's 128
    positions and chunk of 16 (lib/spec.tiny): the ring is 32 positions, so
    the rehearsal's prompts of 8-64 tokens pass the window and most wrap."""
    return dict(config, hidden_size=64, head_dim=16, num_attention_heads=4,
                num_key_value_heads=2, moe_ffn_hidden_size=32,
                moe_num_primary_experts=8, moe_num_active_primary_experts=2,
                num_hidden_layers=4, sliding_window_size=16, vocab_size=256,
                max_position_embeddings=256, torch_dtype="float32")


# ------------------------------------------------------------- the yardstick
def _counts(config):
    """(dims, matmul parameters outside the experts, one expert's)."""
    n = dims(config)
    d = n["d"]
    attn = 2 * d * n["H"] * n["hd"] + 2 * d * n["kv"] * n["hd"]
    return n, n["L"] * (attn + d * n["E"]) + d * n["V"], 3 * d * n["fe"]


def param_counts(config):
    n, outside, expert = _counts(config)
    table = n["d"] * n["V"]
    return {"matmul": outside + n["L"] * n["k"] * expert, "embed": table,
            "total": outside + n["L"] * n["E"] * expert + table
            + n["L"] * 2 * n["d"] + n["d"]}


def experts_touched(config, tokens):
    """Experts a layer that ``tokens`` tokens touch in expectation, each
    choosing k of E evenly."""
    n = dims(config)
    return n["E"] * (1.0 - (1.0 - n["k"] / n["E"]) ** max(tokens, 0))


def tick_weight_bytes(config, tokens, itemsize):
    """Everything outside the experts once, plus the experts that the tick's
    tokens touch in every layer."""
    n, outside, expert = _counts(config)
    return itemsize * (outside + n["L"] * expert
                       * experts_touched(config, tokens))


def _layers_read(config, context=MEAN_LIVE_CONTEXT):
    """Layers' worth of cached positions that a new token reads a position
    of context: a global layer 1, a window layer ``window / context`` once
    the context has passed its window."""
    n = dims(config)
    win = sum(n["windowed"])
    return (n["L"] - win) + win * min(1.0, n["W"] / context)


def cache_bytes_per_position(config, itemsize):
    """K and V of one position that a new token READS, all layers, reckoned
    at ``MEAN_LIVE_CONTEXT``: a window layer reads ``window`` of that many
    positions, so it counts for that share of a layer.  A single number by
    the contract: ``model_step.required_roofline_share.serve`` errs by what
    shorter or longer contexts differ (it reads high on shorter ones)."""
    n = dims(config)
    return 2 * n["kv"] * n["hd"] * itemsize * _layers_read(config)


def cache_bytes_per_position_per_layer(config, itemsize):
    """What one layer's pool holds of one cached position (K and V)."""
    n = dims(config)
    return 2 * n["kv"] * n["hd"] * itemsize


def attn_flops_per_position(config):
    """Score and value FLOPs of one new token against one position of its
    context, all layers, at ``MEAN_LIVE_CONTEXT`` as
    :func:`cache_bytes_per_position` reckons the window layers."""
    n = dims(config)
    return 4.0 * n["H"] * n["hd"] * _layers_read(config)


def train_flops_per_token(config, seq):
    """Not trained here (16 bytes a parameter fit no chip at a depth worth
    measuring, and at the training cells' 1,024 positions the window never
    closes); the convention of the other families, for the contract's
    sake."""
    n = dims(config)
    return (6.0 * param_counts(config)["matmul"]
            + 6.0 * seq * n["H"] * n["hd"] * n["L"])


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
        from horovod_tpu.models.swa_moe import EXPERT_TILE
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


def ring_positions(config):
    """Positions a slot's ring holds in a window layer's pool: the window
    plus one chunk, rounded up to blocks, at most a whole context's (the
    program's models/paged.py ``ring_blocks``, reckoned here from the
    configuration file alone)."""
    e = config["engine"]
    bs = e["block_size"]
    return bs * min(-(-(config["sliding_window_size"] + e["prefill_chunk"])
                      // bs), -(-e["max_seq_len"] // bs))


def pool_op_types(config, kind):
    """The output types of device ops that make or move ``kind``'s pool
    (``window`` or ``global``) or a gather of it: the stacked pool ``[layers,
    blocks, block, kv heads, head_dim]``; a gather by table, which the chip
    makes as ``[slots x entries, block, kv heads, head_dim]`` for all slots
    and ``[entries, block, ..]`` for the one slot that holds a chunk; and
    its forms by slot ``[.., entries, block, ..]`` and flat ``[..,
    positions, kv heads, head_dim]`` (first seen in PR 31's first trace)."""
    n, e = dims(config), config["engine"]
    bs = e["block_size"]
    tail = f"{n['kv']},{n['hd']}]"
    win = sum(n["windowed"])
    if kind == "window":
        keys = ring_positions(config)
        layers, blocks = win, e["max_slots"] * keys // bs
    else:
        keys = -(-e["max_seq_len"] // bs) * bs
        layers, blocks = n["L"] - win, e["cache_blocks"]
    entries = keys // bs
    return [f"[{layers},{blocks},{bs},{tail}",
            f"[{e['max_slots'] * entries},{bs},{tail}",
            f"[{entries},{bs},{tail}", f",{entries},{bs},{tail}",
            f",{keys},{tail}"]


def tick_columns(config):
    """The widths at which a tick attends: a decode row's ``1 + spec_k``,
    the program's ``NARROW_COLS`` (decode rows of a chunk-wide tick) and the
    chunk's; without the program (the parent) the first and the last."""
    e = config["engine"]
    cols = {e["prefill_chunk"],
            1 + e.get("spec_k", 4) if e.get("spec_decode", True) else 1}
    try:
        from horovod_tpu.models.swa_moe import NARROW_COLS
        cols.add(NARROW_COLS)
    except ImportError:
        pass
    return sorted(cols)


def window_attn_op_types(config):
    """The output types of the device ops that are the window layers'
    attention: scores, probabilities and masks (their last axis is the
    ring's length), the ring's gathers, and two that the GLOBAL layers'
    attention shares, so that those are counted too and the share errs low:
    the softmax's row reductions ``[.., kv heads, queries a kv head,
    columns]`` and the value product's output, which the chip lays out
    ``[.., kv heads, head_dim, queries a kv head, columns]``."""
    n = dims(config)
    kv, rep, hd = n["kv"], n["H"] // n["kv"], n["hd"]
    shared = [f"{lead}{kv},{rep},{c}]" for c in tick_columns(config)
              for lead in ("[", ",")]
    shared += [f"{lead}{kv},{hd},{rep}," for lead in ("[", ",")]
    return ([f",{ring_positions(config)}]"] + shared
            + pool_op_types(config, "window")[1:])


def window_attn_required_seconds(config, peaks, slot_ticks, new_tokens,
                                 window_positions, itemsize=2):
    """Least seconds for the window layers' attention: every live slot's
    window (K and V of ``window_positions`` positions summed over the
    ``slot_ticks`` slot-ticks) read once a layer, and each of ``new_tokens``
    new tokens scored against and summed over its window's keys.  (seconds,
    which bound binds)."""
    n = dims(config)
    win = sum(n["windowed"])
    t_bytes = (win * window_positions * 2 * n["kv"] * n["hd"] * itemsize
               / (peaks["hbm_gbps"] * 1e9))
    keys = window_positions / max(slot_ticks, 1)
    t_flops = (win * 4.0 * n["H"] * n["hd"] * new_tokens * keys
               / (peaks["bf16_tflops"] * 1e12))
    return max(t_bytes, t_flops), ("flops" if t_flops >= t_bytes else "bytes")


def pool_ops_ms(ctx, kind):
    """Device self-time a tick, in ms, of the ops shaped like ``kind``'s pool
    or a gather of it (:func:`pool_op_types`) in a traced run; prints the
    five costliest.  None without a trace or such ops."""
    tr = ctx["trace"]
    if not tr or not tr.get("module_count"):
        return None
    types = pool_op_types(ctx["config"], kind)
    hits = {n: s for n, s in tr.get("ops_s", {}).items()
            if any(t in n for t in types)}
    if not hits:
        return None
    top = sorted(hits.items(), key=lambda kv: -kv[1])[:5]
    print(f"perfbench: {kind} pool ops ms/tick "
          + "; ".join(f"{n}={1e3 * s / tr['module_count']:.3f}"
                      for n, s in top), flush=True)
    return 1e3 * sum(hits.values()) / tr["module_count"]


def ring_counts(ctx, kind="window"):
    """What the engine's counters of a window cache kind
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
