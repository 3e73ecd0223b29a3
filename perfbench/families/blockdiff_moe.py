"""The sparse decoder that generates by denoising a block of positions at a
time: every layer is grouped-query attention under a BLOCK-CAUSAL mask over
``num_experts`` small SiLU-gated experts of which a token takes
``num_experts_per_tok`` by a renormalised softmax router; no shared expert,
no dense layer; an untied head.  The program's side is
horovod_tpu.models.blockdiff_moe; see families/__init__.py for what each
name is.  Served only: no ``loss``.

Every linear map is without bias; ``norm(x; g) = x rsqrt(mean(x^2) + eps)
g``.  Layer i on x [T, d], B = ``assumed.block_length``:

  h = norm(x; attn_norm)
  q, k, v = h W_q, h W_k, h W_v by head; q, k = norm over head_dim (q_norm,
      k_norm), THEN rotate-half rotary; scores q.k / sqrt(head_dim) over the
      keys j with j // B <= t // B, softmax in float32
  x = x + concat(heads) W_o
  h2 = norm(x; ffn_norm)
  z = h2 W_r in float32; idx = the k largest of z; g = softmax(z[idx])
  x = x + sum_{e in idx} g_e (silu(h2 W_gate,e) * (h2 W_up,e)) W_down,e
  logits = norm(x_L; final_norm) W_head     (at a position: ITS OWN token)

Generation (``assumed``): a position not yet known holds ``mask_token_id``
(M).  A prompt of p tokens fills ``p // B`` whole blocks; its ``p % B`` last
tokens are known positions of the first generated block.  A pass reads the
logits at the block from everything before it and the block's own state; a
masked position's candidate is its best id other than M, its confidence that
id's softmax probability among the ids other than M; the pass fixes every
masked position whose confidence is at least ``unmask_threshold`` and in any
case the ``B // denoising_steps`` most confident.  Passes repeat until the
block is full; the last block is generated whole and the answer cut to
``max_new_tokens``.  The stream's done record holds ``tokens``, ``steps``
(the pass, 0-based and counted a block, in which each served token was
fixed) and ``tail`` ([token, pass] of what the last block holds behind the
cut).

The reference runs the attention a row at a time, the experts as a loop over
all of them (a token's gate is 0 where it was not chosen).  Its served-path
check (:func:`served_stats`) rebuilds every (block, pass) state that a done
record names, WITHOUT a cache: since no block sees a later one, a state's B
rows attend the finished row's earlier blocks and each other, so the states
of one request ride on one forward of its finished row.
"""

from __future__ import annotations

import math

EMBED = ("embed.table",)
HEAD = ("final_norm.scale", "head.kernel")
#: states a call of the check's layer step takes (a compiled shape)
STATE_CHUNK = 64


def dims(config):
    d, H = config["hidden_size"], config["num_attention_heads"]
    return {"d": d, "H": H, "kv": config["num_key_value_heads"],
            "hd": config.get("head_dim") or d // H,
            "fe": config["moe_intermediate_size"],
            "E": config["num_experts"], "k": config["num_experts_per_tok"],
            "L": config["num_hidden_layers"], "V": config["vocab_size"]}


def gen(config):
    """The generation rule's sizes, which the published config lacks
    (``assumed``): block_length, denoising_steps, unmask_threshold,
    unmask_margin, mask_token_id, head_std."""
    a = config["assumed"]
    return {"B": int(a["block_length"]), "steps": int(a["denoising_steps"]),
            "tau": float(a["unmask_threshold"]),
            "margin": float(a["unmask_margin"]),
            "M": int(a["mask_token_id"]),
            "head_std": float(a.get("head_std")
                              or 1.0 / math.sqrt(config["hidden_size"]))}


# --------------------------------------------------------------- the program
def program(config, max_seq=None):
    from horovod_tpu.models import blockdiff_moe
    from perfbench.lib import weights
    n, g = dims(config), gen(config)
    engine = config.get("engine", {})
    return blockdiff_moe, blockdiff_moe.BlockDiffMoeConfig(
        vocab=n["V"], dim=n["d"], n_layers=n["L"], n_heads=n["H"],
        n_kv_heads=n["kv"], head_dim=n["hd"], moe_hidden=n["fe"],
        n_experts=n["E"], experts_held=n["E"], first_expert=0, top_k=n["k"],
        norm_eps=float(config["rms_norm_eps"]),
        # the rotary tables end where the engine's longest sequence does
        max_seq=max_seq or engine.get("max_seq_len",
                                      config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        dtype=weights.dtype_of(config),
        block_length=g["B"], mask_token_id=g["M"], unmask_threshold=g["tau"],
        denoising_steps=g["steps"])


# --------------------------------------------------------------- the weights
def leaf_specs(config):
    n = dims(config)
    d, fe, E, hd = n["d"], n["fe"], n["E"], n["hd"]
    s = 1.0 / math.sqrt(d)
    out = [("embed.table", (n["V"], d), 0.02),
           ("final_norm.scale", (d,), None),
           ("head.kernel", (d, n["V"]), gen(config)["head_std"])]
    for i in range(n["L"]):
        p = f"layers.{i}."
        out += [(p + "attn_norm.scale", (d,), None),
                (p + "attn.wq.kernel", (d, n["H"] * hd), s),
                (p + "attn.wk.kernel", (d, n["kv"] * hd), s),
                (p + "attn.wv.kernel", (d, n["kv"] * hd), s),
                (p + "attn.wo.kernel", (n["H"] * hd, d),
                 1.0 / math.sqrt(n["H"] * hd)),
                (p + "attn.q_norm.scale", (hd,), None),
                (p + "attn.k_norm.scale", (hd,), None),
                (p + "ffn_norm.scale", (d,), None),
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


def rope(x, positions, theta):
    """x: [S, heads, hd] at ``positions`` [S]; rotate-half pairing."""
    import jax.numpy as jnp
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def layer_kinds(config):
    return ["routed"] * dims(config)["L"]


def embed(p, ids, config):
    import jax.numpy as jnp
    return jnp.take(p["embed.table"], ids, axis=0)


def qkv(p, h, positions, config, mm):
    """(q [S, H, hd], k, v [S, KV, hd]) of normed rows h [S, d] at
    ``positions`` [S]: the head norms, THEN the rotary."""
    n = dims(config)
    S, theta = h.shape[0], float(config["rope_theta"])
    q = mm(h, p["attn.wq.kernel"]).reshape(S, n["H"], n["hd"])
    k = mm(h, p["attn.wk.kernel"]).reshape(S, n["kv"], n["hd"])
    v = mm(h, p["attn.wv.kernel"]).reshape(S, n["kv"], n["hd"])
    return (rope(norm(q, p["attn.q_norm.scale"], config), positions, theta),
            rope(norm(k, p["attn.k_norm.scale"], config), positions, theta),
            v)


def scores(q, k, see):
    """[KV, rep, S, K] scaled scores of grouped queries q [S, H, hd] against
    keys k [K, KV, hd], minus infinity where ``see`` [S, K] hides a key."""
    import jax.numpy as jnp
    S, H, hd = q.shape
    KV = k.shape[1]
    s = jnp.einsum("qhrd,khd->hrqk", q.reshape(S, KV, H // KV, hd), k
                   ) / math.sqrt(hd)
    return jnp.where(see[None, None], s, -jnp.inf)


def weigh(pr, v):
    """[S, H * hd]: probabilities pr [KV, rep, S, K] over values v [K, KV,
    hd]."""
    import jax.numpy as jnp
    o = jnp.einsum("hrqk,khd->qhrd", pr, v)
    return o.reshape(o.shape[0], -1)


def attend(q, k, v, see):
    """Grouped-query attention of q [S, H, hd] over k, v [K, KV, hd] where
    ``see`` [S, K] lets it; float32 softmax.  [S, H * hd]."""
    import jax
    return weigh(jax.nn.softmax(scores(q, k, see), -1), v)


def block_mask(q_pos, k_pos, B):
    """[Q, K] bool: key position j is visible to query position i iff
    ``j // B <= i // B``."""
    return k_pos[None, :] // B <= q_pos[:, None] // B


def route(p, h, config, mm):
    """[T, E] gates from h [T, d]: the k experts with the largest router
    logits hold the softmax over those k logits, the others 0."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    top, idx = jax.lax.top_k(mm(h, p["moe.router.kernel"]), n["k"])
    g = jax.nn.softmax(top, -1)
    return jnp.sum(jax.nn.one_hot(idx, n["E"]) * g[..., None], 1)


def experts(p, t, gate, config, mm):
    """sum_e gate[:, e] * expert_e(t) for tokens t [T, d]: a loop over all
    the experts, each on every token (a loop of the compiler's, not of
    Python's: 128 experts unrolled cost the check minutes of compilation)."""
    import jax
    import jax.numpy as jnp

    def one(e, y):
        out = mm(jax.nn.silu(mm(t, p["moe.experts.w_gate"][e]))
                 * mm(t, p["moe.experts.w_up"][e]),
                 p["moe.experts.w_down"][e])
        return y + jax.lax.dynamic_slice_in_dim(gate, e, 1, axis=1) * out
    return jax.lax.fori_loop(0, dims(config)["E"], one, jnp.zeros_like(t))


def ffn(p, x, config, mm):
    """x [T, d] + its routed experts' sum."""
    h2 = norm(x, p["ffn_norm.scale"], config)
    return x + experts(p, h2, route(p, h2, config, mm), config, mm)


def layer(kind, p, x, config, mm, fault=None):
    """x [R, S, d] at positions 0..S-1 under the block-causal mask.
    ``fault`` is the tests', what this model does NOT do: ``causal`` (the
    plain causal mask inside a block)."""
    import jax
    import jax.numpy as jnp
    R, S, d = x.shape
    pos = jnp.arange(S)
    see = block_mask(pos, pos, 1 if fault == "causal" else gen(config)["B"])

    def row(xr):
        q, k, v = qkv(p, norm(xr, p["attn_norm.scale"], config), pos, config,
                      mm)
        return xr + mm(attend(q, k, v, see), p["attn.wo.kernel"])
    x = jax.lax.map(row, x)
    return ffn(p, x.reshape(R * S, d), config, mm).reshape(R, S, d)


def head(p, x, config, mm):
    return mm(norm(x, p["final_norm.scale"], config), p["head.kernel"])


def candidates(z, config):
    """(best id other than M, its probability among the ids other than M)
    for logits z [.., V]."""
    import jax
    import jax.numpy as jnp
    zz = jnp.where(jnp.arange(z.shape[-1]) == gen(config)["M"], -jnp.inf, z)
    return jnp.argmax(zz, -1), jnp.max(jax.nn.softmax(zz, -1), -1)


# --------------------------------------------------- the served-path check
def states(config, sample):
    """The states that the done records say the program went through, one a
    block and pass, requests in the sample's order: (finished rows [R, T]:
    prompt, served tokens and the last block's tail; [(request, block's
    first position P, the block's ids as the pass saw them [B], the served
    positions fixed in that pass, the positions masked in it)])."""
    import numpy as np
    g = gen(config)
    B, M = g["B"], g["M"]
    rows, at = [], []
    for r, (seq, (first, n), done) in enumerate(zip(
            sample["seqs"], sample["spans"], sample["done"])):
        p, end = first + 1, first + 1 + n
        tail = done.get("tail") or []
        row = np.array(list(seq) + [0] * B, np.int32)
        row[end:end + len(tail)] = [t for t, _ in tail]
        step = dict(zip(range(p, end + len(tail)),
                        list(done["steps"]) + [s for _, s in tail]))
        for P in range(p - p % B, end, B):
            mine = [j for j in range(P, P + B) if j in step]
            for s in sorted({step[j] for j in mine}):
                masked = [j for j in mine if step[j] >= s]
                ids = row[P:P + B].copy()
                ids[[j - P for j in masked]] = M
                at.append((r, P, ids,
                           [j for j in mine if step[j] == s and j < end],
                           masked))
        rows.append(row)
    # as far as the longest request's last block reaches, in whole tiles of
    # 64 positions: what lies behind is padding that nothing sees
    T = max(first + 1 + n for first, n in sample["spans"]) + B
    return np.stack(rows)[:, :min(-(-T // 64) * 64, len(rows[0]))], at


def served_stats(config, seed, sample, tokens_of, quant=None, fault=None):
    """For each state the records name, the reference's logits at the block;
    for each served position fixed in that pass, against those logits z:
    ``gap`` = (best over the ids other than M - z[token]) over the standard
    deviation of z (a served M: infinite), ``flip`` = the token is not that
    best.  ``numbers``: ``early_unmask_share``, the share of served tokens
    that the record fixed EARLY (:func:`early`): a program that fills a
    block in fewer passes than the rule allows serves, with honest steps,
    tokens whose gaps all pass.  ``fault`` is the tests' (see
    :func:`layer`)."""
    reads = token_reads(config, seed, sample, tokens_of, quant, fault)
    order = sorted(reads)       # by request, then by position
    return {"gap": [reads[k]["gap"] for k in order],
            "flip": [reads[k]["flip"] for k in order],
            "numbers": {"early_unmask_share":
                        early(reads.values(), config) / len(order)}}


def early(reads, config):
    """How many served tokens the record fixed before the rule allowed it,
    by the reference's confidences in the state of each pass: a pass may fix
    ONE position whatever its confidence (its surest) and any other only at
    ``unmask_threshold``, so of the served positions a pass fixed whose
    reference confidence lies under the threshold less ``unmask_margin``,
    all but one were early.  Which of them was the surest is not asked: the
    program's bfloat16 moves a confidence by the margin at a good share of
    positions, and two masked positions' order with it (PERF.md §6, PR 40)."""
    g = gen(config)
    under = {}
    for r in reads:
        under[r["state"]] = under.get(r["state"], 0) + (
            r["conf"] < g["tau"] - g["margin"])
    return sum(max(n - 1, 0) for n in under.values())


def token_reads(config, seed, sample, tokens_of, quant=None, fault=None):
    """{(request, position): {"gap", "flip", "conf", "state"}} for every
    served token: against the float32 reference's logits at the token's
    position in the state of the pass that fixed it, the token's gap and
    flip (:func:`served_stats`), the reference's confidence at the position,
    and which state that was (an index into :func:`states`).

    One forward a request: its finished row through the layers a row at a
    time (its keys and values kept), and beside it each state's B rows,
    ``STATE_CHUNK`` states a call, which attend the finished row's keys
    before their block and each other."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from perfbench.lib import reference as R
    n, g = dims(config), gen(config)
    B, M, G = g["B"], g["M"], STATE_CHUNK
    floor = g["tau"] - g["margin"]
    rows, at = states(config, sample)
    T = rows.shape[1]
    w = R.Weights(config, seed)
    passes = [None] + ([quant] if tokens_of == "quant" else [])
    # a request's states, padded with its last to whole chunks
    chunks = []         # (request, indices into ``at`` [G])
    for r in range(len(rows)):
        mine = [i for i, a in enumerate(at) if a[0] == r]
        for c in range(0, len(mine), G):
            part = mine[c:c + G]
            chunks.append((r, part + [part[-1]] * (G - len(part))))
    pos0 = [jnp.asarray(np.array([at[i][1] for i in part], np.int32))
            for _, part in chunks]
    main_pos = jnp.arange(T)
    Bm = 1 if fault == "causal" else B

    def steps(mm):
        @jax.jit
        @R.highest
        def main(p, x):
            """The finished row x [T, d]: (x after the attention, its keys,
            its values)."""
            q, k, v = qkv(p, norm(x, p["attn_norm.scale"], config), main_pos,
                          config, mm)
            o = attend(q, k, v, block_mask(main_pos, main_pos, Bm))
            return x + mm(o, p["attn.wo.kernel"]), k, v

        @jax.jit
        @R.highest
        def state(p, xs, P, km, vm):
            """G states' rows xs [G, B, d] at positions P[g] .. P[g] + B - 1
            over the finished row's keys km, vm [T, KV, hd] before position
            P[g] and their own."""
            def one(x, P):
                pos = P + jnp.arange(B)
                q, k, v = qkv(p, norm(x, p["attn_norm.scale"], config), pos,
                              config, mm)
                # one softmax over both groups of keys; the finished row's
                # are every state's alike and are not copied a state
                pr = jax.nn.softmax(jnp.concatenate(
                    [scores(q, km, jnp.broadcast_to(main_pos[None, :] < P,
                                                    (B, T))),
                     scores(q, k, block_mask(pos, pos, Bm))], -1), -1)
                o = weigh(pr[..., :T], vm) + weigh(pr[..., T:], v)
                return x + mm(o, p["attn.wo.kernel"])
            return jax.vmap(one)(xs, P)

        feed = jax.jit(R.highest(lambda p, x: ffn(p, x, config, mm)))
        return main, state, feed

    def hidden(q):
        """The last layer's output at every state's rows, [chunks, G, B, d],
        by the matmul ``q``."""
        main, state, feed = steps(R.MATMULS[q])
        p = w.part(EMBED)
        xm = [embed(p, jnp.asarray(row), config) for row in rows]
        xs = [embed(p, jnp.asarray(np.stack([at[i][2] for i in part])),
                    config) for _, part in chunks]
        for i in range(n["L"]):
            p = w.layer(i)
            kv = []
            for r in range(len(rows)):
                x, k, v = main(p, xm[r])
                xm[r] = feed(p, x)
                kv.append((k, v))
            xs = [feed(p, state(p, x, P, *kv[r]).reshape(G * B, -1)
                       ).reshape(G, B, -1)
                  for x, P, (r, _) in zip(xs, pos0, chunks)]
        return xs

    hid = {q: hidden(q) for q in passes}
    head_p = w.part(HEAD)

    @jax.jit
    @R.highest
    def block_stats(head_p, x_ref, x_alt, served):
        z = head(head_p, x_ref, config, R.plain_mm)
        best, conf = candidates(z, config)
        if tokens_of == "quant":
            tok, _ = candidates(head(head_p, x_alt, config, R.MATMULS[quant]),
                                config)
        else:
            tok = served
        top = jnp.take_along_axis(z, best[..., None], -1)[..., 0]
        mine = jnp.take_along_axis(z, tok[..., None], -1)[..., 0]
        gap = jnp.where(tok == M, jnp.inf, (top - mine) / jnp.std(z, -1))
        return gap, tok != best, conf

    reads, seen = {}, set()
    for c, (r, part) in enumerate(chunks):
        served = np.stack([rows[r][at[i][1]:at[i][1] + B] for i in part])
        gs, fs, confs = (np.asarray(a) for a in block_stats(
            head_p, hid[None][c], hid[passes[-1]][c], jnp.asarray(served)))
        for k, i in enumerate(part):
            if i in seen:       # the chunk's padding
                continue
            seen.add(i)
            P, filled = at[i][1], at[i][3]
            for j in filled:
                reads[r, j] = {
                    "gap": float(gs[k][j - P]), "flip": bool(fs[k][j - P]),
                    "conf": float(confs[k][j - P]), "state": i}
    return reads


# -------------------------------------------------------------- the toy copy
def tiny(config):
    """Toy widths, three layers; the generation rule's toy sizes: a
    vocabulary of 256 whose last id is M, and a head wide enough that a toy
    pass fixes one to four positions."""
    return dict(config, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, intermediate_size=96,
                moe_intermediate_size=32, num_experts=8,
                num_experts_per_tok=2, num_hidden_layers=3, vocab_size=256,
                max_position_embeddings=256, torch_dtype="float32",
                assumed=dict(config["assumed"], mask_token_id=255,
                             head_std=TOY_HEAD_STD))


#: the toy head's scale: at 64 wide and 255 ids, logits of std 6 put the
#: best id's probability over 0.9 at about a third of the masked positions
TOY_HEAD_STD = 0.75


# ------------------------------------------------------------- the yardstick
def _counts(config):
    """(dims, matrix parameters outside the experts — the head among them
    —, one expert's)."""
    n = dims(config)
    d = n["d"]
    outside = (n["L"] * (2 * d * n["H"] * n["hd"] + 2 * d * n["kv"] * n["hd"]
                         + d * n["E"]) + d * n["V"])
    return n, outside, 3 * d * n["fe"]


def param_counts(config):
    """``matmul``: what a token is multiplied by (the head, the attention's
    projections, the router, its k experts); ``embed`` the embedding table
    (a lookup); ``total`` every leaf."""
    n, outside, expert = _counts(config)
    vectors = n["L"] * (2 * n["d"] + 2 * n["hd"]) + n["d"]
    return {"matmul": outside + n["L"] * n["k"] * expert,
            "embed": n["d"] * n["V"],
            "total": (outside + n["d"] * n["V"]
                      + n["L"] * n["E"] * expert + vectors)}


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


def cache_bytes_per_position(config, itemsize):
    """K and V of one position, all layers."""
    n = dims(config)
    return n["L"] * 2 * n["kv"] * n["hd"] * itemsize


def attn_flops_per_position(config):
    """Score and value FLOPs of one new token against one position of its
    context, all layers."""
    n = dims(config)
    return 4.0 * n["H"] * n["hd"] * n["L"]


def train_flops_per_token(config, seq):
    """Not trained here; the convention of the other families, for the
    contract's sake."""
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
        from horovod_tpu.models.blockdiff_moe import EXPERT_TILE
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
