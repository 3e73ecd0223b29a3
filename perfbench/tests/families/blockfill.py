"""A rehearsal family that only the benchmark's tests use, to prove that a
model whose step yields no fixed number of tokens can bring its own
served-path check (``served_stats``, families/__init__.py) as files alone.
It is dense_gqa's leaves and equations with three changes:

- a position sees every position of its own block of ``block_length`` and of
  earlier blocks (dense_gqa: of itself and earlier positions);
- the vocabulary's last id is the mask id M, held by a position not yet
  filled, and is never served;
- the logits at a position predict that position's own token (no shift).

A block is filled in passes.  A pass reads the logits at the block from the
prefix and the block's state (filled positions hold their token, the others
M; positions of the last block past the request's end hold M throughout).  A
masked position's candidate is its best id other than M, its confidence that
id's softmax probability among the ids other than M.  The pass fills every
masked position whose confidence is at least ``unmask_threshold`` and the
most confident masked one in any case, and the program notes the number of
the pass (0-based, counted a block) for each position.  Passes repeat until
the block is full.  The stream's done record is ``{"tokens": [..], "steps":
[..]}``, one pass number a served token.

No program module (the harness has no served program of the kind: the tests'
"program" is the loop above in plain jax.numpy), no configuration file, no
cell, never on the chip."""

from __future__ import annotations

import math

from perfbench.families import dense_gqa as G

EMBED, HEAD = G.EMBED, G.HEAD
embed, head, leaf_specs, layer_kinds = (G.embed, G.head, G.leaf_specs,
                                        G.layer_kinds)


def mask_id(config):
    return config["vocab_size"] - 1


def layer(kind, p, x, config, mm):
    """dense_gqa's layer under the block mask."""
    import jax
    import jax.numpy as jnp
    d, H, KV, hd = G.dims(config)
    B, S, _ = x.shape
    theta, eps = float(config["rope_theta"]), G.norm_eps(config)
    h = G.rmsnorm(x, p["attn_norm.scale"], eps)
    q = G.rope(mm(h, p["wq.kernel"]).reshape(B, S, H, hd), theta)
    k = G.rope(mm(h, p["wk.kernel"]).reshape(B, S, KV, hd), theta)
    v = mm(h, p["wv.kernel"]).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    block = jnp.arange(S) // config["block_length"]
    s = jnp.where((block[:, None] >= block[None, :])[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + mm(o.reshape(B, S, H * hd), p["wo.kernel"])
    h = G.rmsnorm(x, p["ffn_norm.scale"], eps)
    g = jax.nn.silu(mm(h, p["w_gate.kernel"])) * mm(h, p["w_up.kernel"])
    return x + mm(g, p["w_down.kernel"])


def candidates(z, config):
    """(best id other than M, its probability among the ids other than M)
    for logits z [.., V]."""
    import jax
    import jax.numpy as jnp
    zz = z[..., :mask_id(config)]
    return jnp.argmax(zz, -1), jnp.max(jax.nn.softmax(zz, -1), -1)


def states(config, sample):
    """The states the record says the program went through, one a block and
    pass: (rows [N, T] as the pass saw them, [(request, block's first
    position, the positions filled in that pass, those still masked in
    it)])."""
    import numpy as np
    Bk, M = config["block_length"], mask_id(config)
    rows, at = [], []
    for r, (seq, (first, n), done) in enumerate(zip(
            sample["seqs"], sample["spans"], sample["done"])):
        start, end = first + 1, first + 1 + n
        step = dict(zip(range(start, end), done["steps"]))
        for P in range(start - start % Bk, end, Bk):
            mine = [j for j in range(P, P + Bk) if start <= j < end]
            for s in sorted({step[j] for j in mine}):
                masked = [j for j in mine if step[j] >= s]
                row = np.array(seq, np.int32)
                row[masked] = M
                row[end:P + Bk] = M
                rows.append(row)
                at.append((r, P, [j for j in mine if step[j] == s], masked))
    return np.stack(rows), at


def served_stats(config, seed, sample, tokens_of, quant=None):
    """For each state the record names, the reference's logits at the block;
    for each position filled in that pass, against those logits z: ``gap`` =
    (best over the ids other than M - z[token]) over the standard deviation
    of z (a served M: infinite), ``flip`` = the token is not that best.
    ``numbers``: ``early_unmask_share``, the share of served tokens that the
    record fills in a pass where the reference's confidence in them lies
    under ``unmask_threshold`` less ``unmask_margin`` and another masked
    position of the block is the reference's most confident: a program that
    fills a block in fewer passes than the rule allows serves, with honest
    steps, tokens whose gaps all pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from perfbench.lib import reference as R
    Bk, M = config["block_length"], mask_id(config)
    floor = config["unmask_threshold"] - config["unmask_margin"]
    rows, at = states(config, sample)
    w = R.Weights(config, seed)
    passes = [None] + ([quant] if tokens_of == "quant" else [])
    hidden = {q: R.hidden_states(config, w, rows, q) for q in passes}
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
        top = jnp.take_along_axis(z, best[:, None], -1)[:, 0]
        mine = jnp.take_along_axis(z, tok[:, None], -1)[:, 0]
        gap = jnp.where(tok == M, jnp.inf, (top - mine) / jnp.std(z, -1))
        return gap, tok != best, conf

    T = rows.shape[1]
    gap, flip, early = {}, {}, 0
    for i, (r, P, filled, masked) in enumerate(at):
        pos = np.minimum(P + np.arange(Bk), T - 1)
        g, f, conf = (np.asarray(a) for a in block_stats(
            head_p, hidden[None][i, pos], hidden[passes[-1]][i, pos],
            jnp.asarray(np.asarray(sample["seqs"][r], np.int32)[pos])))
        surest = max(masked, key=lambda j: conf[j - P])
        for j in filled:
            gap[r, j], flip[r, j] = float(g[j - P]), bool(f[j - P])
            early += bool(conf[j - P] < floor and j != surest)
    order = sorted(gap)     # by request, then by position
    return {"gap": [gap[k] for k in order], "flip": [flip[k] for k in order],
            "numbers": {"early_unmask_share": early / len(order)}}


def tiny(config):
    return dict(G.tiny(config), block_length=4)


# The configuration the tests run (the family has no file under configs/) and
# the limits they judge by: serve-decode's for the gaps; the family's own
# number reads 0 on a sound record, whose every early position would have to
# lie within the margin of the threshold, and 0.3 or more where every block
# is filled in its first pass.  The threshold is the toy's: its random
# weights give confidences of 0.02 to 0.2, so that some passes fill several
# positions and some one.
TOY = tiny({"family": "blockfill", "rope_theta": 1e6, "rms_norm_eps": 1e-5,
            "assumed": {"rms_norm_eps": 1e-6},
            "unmask_threshold": 0.05, "unmask_margin": 0.005})
OWN_LIMITS = {"early_unmask_share": 0.05}
