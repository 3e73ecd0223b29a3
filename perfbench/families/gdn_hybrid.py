"""Gated delta-rule layers (Gated DeltaNet, arXiv:2412.06464; ``beta`` up to
2, so that a transition's eigenvalue may be negative) beside full attention
without positions, in the OLMo 2 / 3 block: no norm BEFORE a sublayer, an
RMSNorm with a gain on its OUTPUT.  The program's side is
horovod_tpu.models.gdn_hybrid; see families/__init__.py for what each name
is.  Served only: no ``loss``.

``layer_types[i]`` is ``linear_attention`` or ``full_attention`` (three of
the first, then one of the second).  ``N(y; w) = y rsqrt(mean(y^2) + eps)
w``; no bias anywhere.  Layer i on x [T, d]:

  h = x + N(mixer_i(x); mix_norm);  x' = h + N(FFN(h); ffn_norm)
  FFN(h) = (silu(h W_gate) * (h W_up)) W_down
  full:   q = N(x W_q; q_norm), k = N(x W_k; k_norm) over all d outputs,
          THEN cut into H heads of d / H; v = x W_v; softmax(q k^T /
          sqrt(d / H) + causal mask) v; W_o.  No positional encoding.
  linear: [q; k; v] = silu(conv(x W_qkv))  (depthwise, K taps, the LAST on
          the current position, zero before position 0, no bias), H heads
          of dk, dk, dv;  q = q / sqrt(|q|^2 + eps) / sqrt(dk),
          k = k / sqrt(|k|^2 + eps);  beta = 2 sigmoid(x W_b),
          g = -exp(A_log) softplus(x W_a + dt_bias)  (a head each)
          S_t = exp(g_t) S_{t-1};  S_t += beta_t (v_t - S_t k_t) k_t^T;
          o_t = S_t q_t                          (S [dv, dk], S_{-1} = 0)
          mixer = [o_t rsqrt(mean(o_t^2) + eps) o_norm * silu(x W_z)] W_out
  logits = N(x_L; final_norm) W_head                    (untied)

The recurrence is a plain loop over positions in float32; the attention
runs a block of queries at a time so that a row of 12,800 positions fits.
"""

from __future__ import annotations

import math
import re
from typing import NamedTuple

EMBED = ("embed.table",)
HEAD = ("final_norm.scale", "lm_head.kernel")
#: queries a step of the reference's attention (30 heads x 512 x 12,800
#: float32 scores are 0.79 GB)
QUERY_BLOCK = 512
#: a sampled row is cut to the longest of the sample, rounded up to this (a
#: row is one shape, one compilation a kind of layer)
ROW_STEP = 256


def dims(config):
    d, H = config["hidden_size"], config["num_attention_heads"]
    return {"d": d, "H": H, "hd": d // H, "f": config["intermediate_size"],
            "L": config["num_hidden_layers"], "V": config["vocab_size"],
            "Hl": config["linear_num_value_heads"],
            "dk": config["linear_key_head_dim"],
            "dv": config["linear_value_head_dim"],
            "K": config["linear_conv_kernel_dim"],
            "eps": float(config["rms_norm_eps"])}


def layer_kind(config, i):
    return {"linear_attention": "linear",
            "full_attention": "full"}[config["layer_types"][i]]


def _layers(config):
    """{kind: how many of the FIRST ``num_hidden_layers`` layers} (the list
    is kept whole in a file whose depth is cut)."""
    kinds = [layer_kind(config, i) for i in range(config["num_hidden_layers"])]
    return {k: kinds.count(k) for k in ("linear", "full")}


def full_every(config):
    """The period of the full layers, which the list must keep."""
    types = config["layer_types"]
    period = types.index("full_attention") + 1
    if any((t == "full_attention") != ((i + 1) % period == 0)
           for i, t in enumerate(types)):
        raise SystemExit("gdn_hybrid: layer_types is no whole number of "
                         f"periods of {period}")
    return period


# --------------------------------------------------------------- the program
def program(config, max_seq=None):
    from horovod_tpu.models import gdn_hybrid
    from perfbench.lib import weights
    n = dims(config)
    engine = config.get("engine", {})
    if config["linear_num_key_heads"] != n["Hl"] or \
            config["num_key_value_heads"] != n["H"]:
        raise SystemExit("gdn_hybrid: a key head a value head, and a key "
                         "head a query head, is all the program has")
    return gdn_hybrid, gdn_hybrid.GdnHybridConfig(
        vocab=n["V"], dim=n["d"], n_layers=n["L"], n_heads=n["H"],
        ffn_dim=n["f"], full_every=full_every(config), lin_heads=n["Hl"],
        lin_key_dim=n["dk"], lin_value_dim=n["dv"], conv_kernel=n["K"],
        # a prompt's chunk is at least two of the recurrence's chunks, so
        # that the rehearsal passes a state between them too
        chunk=min(64, max(8, engine.get("prefill_chunk", 128) // 2)),
        norm_eps=n["eps"],
        max_seq=max_seq or engine.get("max_seq_len",
                                      config["max_position_embeddings"]),
        dtype=weights.dtype_of(config))


# --------------------------------------------------------------- the weights
class Init(NamedTuple):
    """A leaf's ``std`` that is no number (as families/sambay.py ``Init``):
    lib/weights.leaf makes a leaf as ``normal * std``, and this turns the
    standard normal draw into what the gated delta-rule layer's published
    code initialises the leaf with, the same bits for the program and the
    reference.  Hashable: the draw's program is cached by it.

    ``a_log``: the log of a draw uniform over ``(0, hi)``.  ``dt_bias``: the
    inverse softplus of a step drawn log-uniformly over ``lo .. hi``, so
    that ``softplus(bias)`` lies there."""
    how: str
    lo: float = 1e-3
    hi: float = 1e-1

    def __rmul__(self, x):
        import jax.numpy as jnp
        from jax.scipy.special import ndtr
        u = jnp.clip(ndtr(x), 1e-6, 1.0 - 1e-6)     # uniform over (0, 1)
        if self.how == "a_log":
            return jnp.log(self.hi * u)
        dt = jnp.exp(math.log(self.lo)
                     + u * (math.log(self.hi) - math.log(self.lo)))
        return dt + jnp.log(-jnp.expm1(-dt))


def leaf_specs(config):
    n = dims(config)
    d, f, H, dk, dv, K = (n[k] for k in ("d", "f", "Hl", "dk", "dv", "K"))
    s = 1.0 / math.sqrt(d)
    conv = H * (2 * dk + dv)
    out = [("embed.table", (n["V"], d), 0.02),
           ("final_norm.scale", (d,), None),
           ("lm_head.kernel", (d, n["V"]), s)]
    for i in range(n["L"]):
        p = f"layers.{i}."
        if layer_kind(config, i) == "full":
            a = p + "attn."
            out += [(a + w + ".kernel", (d, d), s)
                    for w in ("wq", "wk", "wv", "wo")]
            out += [(a + "q_norm.scale", (d,), None),
                    (a + "k_norm.scale", (d,), None)]
        else:
            g = p + "gdn."
            out += [(g + "qkv.kernel", (d, conv), s),
                    (g + "z.kernel", (d, H * dv), s),
                    (g + "a.kernel", (d, H), s),
                    (g + "b.kernel", (d, H), s),
                    (g + "conv.taps", (conv, K), 1.0 / math.sqrt(K)),
                    (g + "A_log", (H,), Init("a_log", hi=16.0)),
                    (g + "dt_bias", (H,), Init("dt_bias")),
                    (g + "o_norm.scale", (dv,), None),
                    (g + "out.kernel", (H * dv, d), 1.0 / math.sqrt(H * dv))]
        out += [(p + "mix_norm.scale", (d,), None),
                (p + "ffn_norm.scale", (d,), None),
                (p + "w_gate.kernel", (d, f), s),
                (p + "w_up.kernel", (d, f), s),
                (p + "w_down.kernel", (f, d), 1.0 / math.sqrt(f))]
    return out


# ------------------------------------------------------------- the reference
def rms_norm(y, gain, config):
    import jax
    import jax.numpy as jnp
    return y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                             + float(config["rms_norm_eps"])) * gain


def layer_kinds(config):
    return [layer_kind(config, i) for i in range(config["num_hidden_layers"])]


def embed(p, ids, config):
    import jax.numpy as jnp
    return jnp.take(p["embed.table"], ids, axis=0)


def delta_loop(q, k, v, g, beta, S0=None, last=False):
    """The recurrence, a position after another: q, k [B, S, H, dk], v [B,
    S, H, dv], g, beta [B, S, H] -> o [B, S, H, dv], from the state ``S0``
    [B, H, dv, dk] (nothing where None); with ``last`` also the state after
    the last position."""
    import jax
    import jax.numpy as jnp

    def step(S, t):
        q, k, v, g, beta = t                # [B, H, dk] x 2, [B, H, dv], ..
        S = jnp.exp(g)[..., None, None] * S
        u = beta[..., None] * (v - jnp.sum(S * k[..., None, :], -1))
        S = S + u[..., :, None] * k[..., None, :]
        return S, jnp.sum(S * q[..., None, :], -1)
    t_major = lambda x: jnp.moveaxis(x, 1, 0)
    B, _, H, dk = q.shape
    S, o = jax.lax.scan(
        step, jnp.zeros((B, H, v.shape[-1], dk), v.dtype) if S0 is None
        else S0, tuple(map(t_major, (q, k, v, g, beta))))
    return (t_major(o), S) if last else t_major(o)


def linear_mixer(p, x, config, mm):
    """A gated delta-rule layer's mixer on x [B, S, d]."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    H, dk, dv, K, S = n["Hl"], n["dk"], n["dv"], n["K"], x.shape[1]
    u = mm(x, p["gdn.qkv.kernel"])
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    w = p["gdn.conv.taps"]
    u = jax.nn.silu(sum(w[:, j] * up[:, j:j + S] for j in range(K)))
    q, k, v = jnp.split(u, [H * dk, 2 * H * dk], axis=-1)
    heads = lambda a, width: a.reshape(a.shape[:2] + (H, width))
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, -1, keepdims=True) + n["eps"])
    q, k, v = unit(heads(q, dk)) / math.sqrt(dk), unit(heads(k, dk)), \
        heads(v, dv)
    beta = 2.0 * jax.nn.sigmoid(mm(x, p["gdn.b.kernel"]))
    g = -jnp.exp(p["gdn.A_log"]) * jax.nn.softplus(
        mm(x, p["gdn.a.kernel"]) + p["gdn.dt_bias"])
    o = rms_norm(delta_loop(q, k, v, g, beta), p["gdn.o_norm.scale"], config)
    o = o.reshape(x.shape[:2] + (H * dv,)) * jax.nn.silu(
        mm(x, p["gdn.z.kernel"]))
    return mm(o, p["gdn.out.kernel"])


def attention_row(q, k, v, block):
    """Causal attention of one row, q, k, v [S, H, hd], ``block`` queries at
    a time."""
    import jax
    import jax.numpy as jnp
    S, H, hd = q.shape
    pad = -S % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, H, hd)
    at = jnp.arange(S + pad).reshape(-1, block)

    def one(t):
        qs, pos = t
        s = jnp.einsum("qhd,khd->hqk", qs, k) / math.sqrt(hd)
        s = jnp.where(pos[None, :, None] >= jnp.arange(S)[None, None, :], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return jax.lax.map(one, (qb, at)).reshape(S + pad, H, hd)[:S]


def full_mixer(p, x, config, mm):
    import jax
    n = dims(config)
    q = rms_norm(mm(x, p["attn.wq.kernel"]), p["attn.q_norm.scale"], config)
    k = rms_norm(mm(x, p["attn.wk.kernel"]), p["attn.k_norm.scale"], config)
    v = mm(x, p["attn.wv.kernel"])
    heads = lambda a: a.reshape(a.shape[:2] + (n["H"], n["hd"]))
    block = min(QUERY_BLOCK, x.shape[1])
    o = jax.lax.map(lambda t: attention_row(*t, block),
                    (heads(q), heads(k), heads(v)))
    return mm(o.reshape(x.shape), p["attn.wo.kernel"])


def layer(kind, p, x, config, mm):
    import jax
    y = (full_mixer if kind == "full" else linear_mixer)(p, x, config, mm)
    h = x + rms_norm(y, p["mix_norm.scale"], config)
    f = mm(jax.nn.silu(mm(h, p["w_gate.kernel"])) * mm(h, p["w_up.kernel"]),
           p["w_down.kernel"])
    return h + rms_norm(f, p["ffn_norm.scale"], config)


def head(p, x, config, mm):
    return mm(rms_norm(x, p["final_norm.scale"], config), p["lm_head.kernel"])


def served_stats(config, seed, sample, tokens_of, quant=None):
    """``lib/reference.generated_logit_stats`` on the sample's rows cut to
    the longest of them (the harness pads every row to the mix's longest
    prompt + answer, 12,800 positions; a causal pass does not read what
    lies behind a row's end), a row at a time: a row of the longest request
    is [1, ~12,800, 11,520] float32 in a linear layer's projections."""
    from perfbench.lib import reference
    spans = [tuple(s) for s in sample["spans"]]
    need = max(first + n for first, n in spans) + 1
    need = min(-(-need // ROW_STEP) * ROW_STEP, len(sample["seqs"][0]))
    return reference.generated_logit_stats(
        config, seed, [row[:need] for row in sample["seqs"]], spans,
        tokens_of, quant=quant, row_block=1)


# -------------------------------------------------------------- the toy copy
def tiny(config):
    """Toy widths, two whole periods (six linear layers, two full)."""
    return dict(config, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=4, intermediate_size=96,
                num_hidden_layers=8, linear_num_key_heads=4,
                linear_num_value_heads=4, linear_key_head_dim=8,
                linear_value_head_dim=16, vocab_size=256,
                max_position_embeddings=256, torch_dtype="float32")


# ------------------------------------------------------------- the yardstick
def _mixers(config):
    """{kind: (matrix parameters a token is multiplied by, every other
    parameter) of one mixer of that kind}."""
    n = dims(config)
    d, H, dk, dv, K = (n[k] for k in ("d", "Hl", "dk", "dv", "K"))
    conv = H * (2 * dk + dv)
    return {"linear": (d * conv + 2 * d * H * dv + 2 * d * H,
                       conv * K + 2 * H + dv),
            "full": (4 * d * d, 2 * d)}


def params_by_kind(config):
    """{kind: parameters of one whole layer of that kind} (mixer, FFN and the
    two norms)."""
    n = dims(config)
    rest = 3 * n["d"] * n["f"] + 2 * n["d"]
    return {k: a + b + rest for k, (a, b) in _mixers(config).items()}


def param_counts(config, layers=None):
    """``matmul``: the head's matrix and every layer's (a token's own row of
    the embedding is a lookup); ``total`` every leaf once (the head is
    untied).  ``layers``: {kind: how many}, the file's own where None."""
    n = dims(config)
    mix, layers = _mixers(config), layers or _layers(config)
    embed = n["d"] * n["V"]
    matmul = sum(layers[k] * (mix[k][0] + 3 * n["d"] * n["f"]) for k in mix)
    total = sum(layers[k] * v for k, v in params_by_kind(config).items())
    return {"matmul": matmul + embed, "embed": embed,
            "total": total + 2 * embed + n["d"]}


def uncut_param_count(config):
    """Every leaf of the model at the depth its ``layer_types`` lists."""
    types = config["layer_types"]
    return param_counts(config, {
        "linear": types.count("linear_attention"),
        "full": types.count("full_attention")})["total"]


def tick_weight_bytes(config, tokens, itemsize):
    """Every matrix once, whatever the tick's tokens: the stack is dense."""
    return itemsize * param_counts(config)["matmul"]


def cache_bytes_per_position_per_layer(config, itemsize):
    """What one full layer's pool holds of one cached position (K and V,
    all ``d`` outputs of each)."""
    return 2 * dims(config)["d"] * itemsize


def cache_bytes_per_position(config, itemsize):
    """K and V that a new token READS of one position of its context: the
    full layers'.  The linear layers' states (one ``[H, dv, dk]`` float32 a
    slot a layer, read and written whatever the context) are left out, so
    ``model_step.required_roofline_share.serve`` would err low."""
    return (cache_bytes_per_position_per_layer(config, itemsize)
            * _layers(config)["full"])


def attn_flops_per_position(config):
    """Score and value FLOPs of one new token against one position of its
    context: the full layers', H queries of hd against a key and H weights
    on a value."""
    n = dims(config)
    return 2.0 * n["H"] * 2 * n["hd"] * _layers(config)["full"]


def train_flops_per_token(config, seq):
    """Not trained here (16 bytes a parameter fit no cut within the guide's
    floors); the convention of the other families, for the contract's
    sake."""
    n = dims(config)
    return (6.0 * param_counts(config)["matmul"]
            + 6.0 * seq * n["H"] * n["hd"] * _layers(config)["full"])


def replay_rows(config):
    """Rows of a slot's ring in the ``delta`` kind: a verify row's ``1 +
    spec_k`` less its first (the program's paged.replay_rows, reckoned here
    from the configuration file alone)."""
    e = config["engine"]
    return max(e.get("spec_k", 4) if e.get("spec_decode", True) else 0, 1)


def state_bytes_per_slot(config, itemsize, columns=None, rows=None):
    """{state kind: bytes a slot}: the conv inputs at ``columns`` columns
    (the engine's: the ``K - 1`` a tick reads back + a verify row), ``[conv]``
    each in the model's type, and the delta kind: ONE ``[H, dv, dk]`` float32
    a layer, where it stands, and a ring of ``rows`` rows (the engine's:
    :func:`replay_rows`) of (k, v, g, beta) float32."""
    n, L = dims(config), _layers(config)["linear"]
    H, dk, dv = n["Hl"], n["dk"], n["dv"]
    rows = replay_rows(config) if rows is None else rows
    columns = n["K"] + replay_rows(config) if columns is None else columns
    return {"conv": L * columns * H * (2 * dk + dv) * itemsize,
            "delta": L * (H * dv * dk * 4 + 4
                          + rows * H * (dk + dv + 2) * 4)}


# ---------------------------------------------------------------- the readers
def _kind_counts(ctx, kind):
    a, b = (ctx["marks"][k]["stats"].get("kv_pool", {}).get("kinds", {})
            .get(kind) for k in ("start", "end"))
    ticks = ctx["marks"]["end"]["tick"] - ctx["marks"]["start"]["tick"]
    if not a or not b or not ticks:
        return None
    return dict({k: b[k] - a[k] for k in b if k.endswith("_ticks")},
                ticks=ticks)


def state_counts(ctx):
    """What the engine's counters of BOTH state kinds
    (``stats()["kv_pool"]["kinds"]``: ``conv`` and ``delta``) grew by between
    the window's marks, added up ({name: delta}, with the ticks and one
    kind's slot-ticks): what the linear layers hold a slot beside what a
    key-value cache of the same layers would.  None where the program has no
    such kinds (the parent commit) or no tick ran."""
    conv, delta = _kind_counts(ctx, "conv"), _kind_counts(ctx, "delta")
    if not conv or not delta:
        return None
    # both kinds are the same twelve layers': a key-value cache of them
    # counts once
    return dict(conv, state_bytes_ticks=conv["state_bytes_ticks"]
                + delta["state_bytes_ticks"])


def _ops(ctx):
    tr = ctx["trace"]
    return (tr.get("ops_s", {}), tr["module_count"]) \
        if tr and tr.get("module_count") else ({}, 0)


def _top(label, hits, ticks):
    top = sorted(hits.items(), key=lambda kv: -kv[1])[:5]
    print(f"perfbench: {label} ms/tick "
          + "; ".join(f"{n}={1e3 * s / ticks:.3f}" for n, s in top),
          flush=True)


#: the named scopes of the program's side (models/gdn_hybrid.py), innermost
#: first, for the builder's table of a traced run (tools/family_table.py)
SCOPES = ("gdn/state", "gdn/replay", "gdn/step", "gdn/chunk", "gdn/pass",
          "gdn/conv", "gdn/gate", "gdn/norm", "gdn/in", "gdn/out",
          "attn/full")


def _type(name):
    """(type, dims) of what a device op makes, off its short name
    (lib/tracered.short_name: ``fusion f32[16,30,192,96] params_..``)."""
    m = re.search(r"\b([a-z]+\d+)\[([\d,]*)\]", name)
    if not m:
        return None, ()
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def state_op_group(name, config):
    """Which of the state kinds' pools a device op moves (``gdn/state``), by
    the type it makes and the configuration's sizes alone, or None:

      ``states``  ``[linear layers, slots, .., H, dv, dk]``: the stacked
                  committed states, which the commit's scatter returns whole;
      ``ring``    whatever ends in a ring's row, ``H (dk + dv + 2)`` wide (a
                  row's k, v, g and beta side by side), and the ring's rows
                  by head ``[slots, rows, H, ..]`` as they are read;
      ``conv``    the conv inputs' pool ``[.., slots, columns, conv]``, whole,
                  some layers of it or one, and the reads of it, ``[the
                  tick's rows, conv]`` with no weight behind them;
      ``at``      the positions the states stand after, ``s32[linear layers,
                  slots]``.

    ONE layer's states ``[slots, H, dv, dk]`` are the recurrence's
    (:func:`mix_op_group`): the op that reads them is the product that
    makes the new ones."""
    n, e = dims(config), config["engine"]
    L, S, R = _layers(config)["linear"], e["max_slots"], replay_rows(config)
    H, dk, dv = n["Hl"], n["dk"], n["dv"]
    conv, row, cols = H * (2 * dk + dv), H * (dk + dv + 2), n["K"] + R
    kind, d = _type(name)
    if not d:
        return None
    if d[:2] == (L, S) and d[-3:] == (H, dv, dk):
        return "states"
    if d[-1] == row or (d[:2] == (S, R) and H in d[2:]):
        return "ring"
    if d[-1] == conv and (d[-3:-1] == (S, cols) or (
            kind == "bf16" and "params" not in name
            and d[:-1] in ((S * (R + 1),), (e["max_batch_tokens"],)))):
        return "conv"
    if kind == "s32" and d[:2] == (L, S) and set(d[2:]) <= {1}:
        return "at"
    return None


def mix_op_group(name, config):
    """Which part of the recurrence a device op is (``gdn/replay``,
    ``gdn/step``, ``gdn/chunk``, ``gdn/pass``), by the float32 type it makes
    and the model's sizes alone — H heads, dk, dv: no chunk's length, no
    grouping of the program's is read —, or None:

      ``state``   ``[.., H, dv, dk]``: the new states, the state passed
                  along a slot's chunks (the pools': :func:`state_op_group`);
      ``square``  ``[.., H, .., T, T]``: a chunk's matrices, rows by rows —
                  the keys' products under the decay, the inverse and its
                  blocks, the queries' products;
      ``rows``    ``[.., H, T, dk | dv | dk + dv]``, heads before rows: what
                  the inverse is applied to, the products with the state, the
                  outputs;
      ``fed``     ``[rows, H, dk | dv]``, rows before heads, where the rows
                  are NOT the tick's own (those are the norms', ``gdn/norm``:
                  not counted): the ring's rows laid before a slot's own.

    The projections, the convolution and the gates make ``[rows, ..]`` with
    the heads side by side and are not counted.  Neither is what is ``[..,
    H, T]`` alone (the running sums of g): nothing tells it from the full
    layers' ``[slots, heads, columns]`` (0.15 of a narrow tick's 4.6 ms, my
    chip run, PR 48).  The full layers' own ``[.., heads, .., head_dim]``
    end in another number; where it is one of dk, dv, dk + dv nothing is
    told apart and every op reads None."""
    n, e = dims(config), config["engine"]
    S, R = e["max_slots"], replay_rows(config)
    H, dk, dv = n["Hl"], n["dk"], n["dv"]
    wide = (dk, dv, dk + dv)
    kind, d = _type(name)
    if kind != "f32" or H not in d or n["hd"] in wide \
            or state_op_group(name, config):
        return None
    after = d[d.index(H) + 1:]
    if after == (dv, dk):
        return "state"
    if len(after) >= 2 and after[-1] == after[-2]:
        return "square"
    if len(after) >= 2 and after[-1] in wide:
        return "rows"
    own = ((S * (R + 1),), (e["max_batch_tokens"],), (S, R + 1))
    if len(after) == 1 and after[0] in wide and d[:-2] and d[:-2] not in own:
        return "fed"
    return None


def _hits(ctx, group, need):
    """{op: seconds} of the traced ops that ``group`` names, or None where
    one of the groups ``need`` has no op in the trace: a share of what is
    left would read as a gain."""
    ops, _ = _ops(ctx)
    by = {}
    for name, s in ops.items():
        g = group(name, ctx["config"])
        if g:
            by.setdefault(g, {})[name] = s
    if any(g not in by for g in need):
        return None
    return {name: s for hits in by.values() for name, s in hits.items()}


def mix_share(ctx):
    """Device self-time of the recurrence's own ops (:func:`mix_op_group`)
    over the tick programs' device time in the trace, in %.  Prints the five
    costliest.  None without a trace, or where the trace lacks the new
    states, a chunk's matrices or its products: the program then runs the
    recurrence in ops this reader does not know."""
    hits = _hits(ctx, mix_op_group, ("state", "square", "rows"))
    if not hits or not ctx["trace"].get("module_s"):
        return None
    _top("gdn mix ops", hits, _ops(ctx)[1])
    return 100.0 * sum(hits.values()) / ctx["trace"]["module_s"]


def state_ops_ms(ctx):
    """Device self-time a tick, in ms, of the ops that move the committed
    states, the ring and the conv inputs (:func:`state_op_group`); prints
    the five costliest.  None without a trace, or where one of the three
    pools has no op in it."""
    hits = _hits(ctx, state_op_group, ("states", "ring", "conv"))
    if not hits:
        return None
    ticks = _ops(ctx)[1]
    _top("gdn state ops", hits, ticks)
    return 1e3 * sum(hits.values()) / ticks
