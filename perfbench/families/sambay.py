"""The decoder-hybrid-decoder ("SambaY", arXiv:2507.06607): a SELF-DECODER
whose layers alternate a selective state-space layer (Mamba-1,
arXiv:2312.00752) and sliding-window attention, one FULL attention layer
whose keys and values are the only ones of their kind, and a CROSS-DECODER
whose layers alternate a gated memory unit — an elementwise gate on the
last state-space layer's scan output — and attention that reads that one
full layer's keys and values again.  All attention is differential
(arXiv:2410.05258).  The program's side is horovod_tpu.models.sambay; see
families/__init__.py for what each name is.  Served only: no ``loss``.

L layers, half = L // 2; layer i is
  mamba  i even, i <= half           swa    i odd, i < half
  full   i = half + 1                cross  i odd, i >= half + 3
  gmu    i even, i >= half + 2
``LN(x; g, b) = (x - mean) rsqrt(var + eps) g + b``; no positional encoding
anywhere; no bias on a projection unless said.  Layer i on x [T, d]:

  a = LN(x; mix_norm);  x = x + mixer_i(a);  x = x + FFN(LN(x; ffn_norm))
  FFN(h):  g, u = split2(h W_fc1);  (silu(g) * u) W_fc2
  mamba:  u, z = split2(a W_in);  u = silu(conv(u) + b_c)  (depthwise, K
          taps, the LAST on the current position, u zero before position 0)
          dt, B, C = split(u W_x; R, N, N);  D_t = softplus(dt W_dt + b_dt)
          h_t = exp(D_t A) * h_{t-1} + (D_t u_t) B_t^T     [di, N], h_{-1} = 0
          y_t = h_t C_t + D * u_t;   mixer = (y * silu(z)) W_out
          A = -exp(A_log).  Layer ``half`` also keeps m = y, BEFORE the gate.
  swa / full:  q, k, v = split(a W_qkv; H hd, KV hd, KV hd) by head.
          Differential head n of H/2 has the queries q[2n], q[2n+1] and
          reads group g = n // (H / KV) of KV/2, whose keys are k[2g],
          k[2g+1] and whose ONE value is v[2g] || v[2g+1] (2 hd wide):
          P^j = softmax(q^j k^j^T / sqrt(hd) + mask),  o = P^1 v - lam P^2 v
          o = o rsqrt(mean(o^2) + eps) subln * (1 - lam_init),  concat W_o
          lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,
          lam_init = 0.8 - 0.6 exp(-0.3 i).  The mask is causal; in swa a
          query at P sees keys P - W + 1 .. P.
  cross:  q = a W_q alone; the same attention against layer half + 1's keys
          and values of positions <= P; W_o.
  gmu:    (silu(a W_1) * m) W_2, m layer ``half``'s at the same position.
  logits = LN(x_L; final_norm) E^T        (E the embedding)

Departures from the published description, each for the contract's sake:
the reference's ``layer`` carries x alone, so x is ``[residual | m | k | v]``
side by side (d + di + 2 KV hd wide): layer ``half`` fills m, layer half + 1
fills k and v, every layer passes the three on unchanged and ``head`` reads
the first d.  The scan is a plain loop over positions in float32.  The leaf
``A_log`` is held ``[N, di]``, the published ``[di, N]`` turned (the program
keeps the carry it multiplies that way: di is whole lanes on the chip).
"""

from __future__ import annotations

import math
from typing import NamedTuple

EMBED = ("embed.table",)
HEAD = ("final_norm.scale", "final_norm.bias", "embed.table")
#: the context at which a window layer's share of a cached position is
#: reckoned (:func:`cache_bytes_per_position`): the mean over a window of the
#: live slots' contexts under reason-decode.json: its pairs of prompt p and
#: answer o give sum(o (p + o / 2)) / sum(o) = 651-655
MEAN_LIVE_CONTEXT = 650


def dims(config):
    d, H = config["hidden_size"], config["num_attention_heads"]
    sizes = config["assumed"]["sizes"]
    return {"d": d, "H": H, "kv": config["num_key_value_heads"],
            "hd": d // H, "f": config["intermediate_size"],
            "L": config["num_hidden_layers"], "V": config["vocab_size"],
            "W": config["sliding_window"], "di": sizes["expand"] * d,
            "N": sizes["d_state"], "K": sizes["d_conv"],
            # Mamba's "auto": a sixteenth of the model's width, rounded up
            "R": -(-d // 16)}


def layer_kind(config, i):
    """``mamba``, ``swa``, ``full``, ``cross`` or ``gmu`` (module docstring);
    a state-space layer every ``mb_per_layer`` layers."""
    half = config["num_hidden_layers"] // 2
    if i % config["mb_per_layer"] == 0:
        return "mamba" if i <= half else "gmu"
    return "swa" if i < half else "full" if i == half + 1 else "cross"


def _layers(config):
    """{kind: how many of the L layers}."""
    kinds = [layer_kind(config, i) for i in range(config["num_hidden_layers"])]
    return {k: kinds.count(k) for k in ("mamba", "swa", "full", "cross",
                                        "gmu")}


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


# --------------------------------------------------------------- the program
def program(config, max_seq=None):
    from horovod_tpu.models import sambay
    from perfbench.lib import weights
    n = dims(config)
    engine = config.get("engine", {})
    return sambay, sambay.SambaYConfig(
        vocab=n["V"], dim=n["d"], n_layers=n["L"], n_heads=n["H"],
        n_kv_heads=n["kv"], ffn_dim=n["f"], window=n["W"],
        mb_per_layer=config["mb_per_layer"], d_state=n["N"], d_conv=n["K"],
        expand=n["di"] // n["d"], norm_eps=float(config["layer_norm_eps"]),
        max_seq=max_seq or engine.get("max_seq_len",
                                      config["max_position_embeddings"]),
        dtype=weights.dtype_of(config))


# --------------------------------------------------------------- the weights
class Init(NamedTuple):
    """A leaf's ``std`` that is no number: lib/weights.leaf makes a leaf as
    ``normal * std``, and this turns the standard normal draw into what
    Mamba initialises the leaf with (arXiv:2312.00752, section 3.6 and its
    code), the same bits for the program and the reference.  Hashable: the
    draw's program is cached by it.

    ``a_log``: ``log(1 .. N)`` along the FIRST axis, every column alike
    (the draw is not used).  ``dt_bias``: the inverse softplus of a step drawn
    log-uniformly over ``lo .. hi``, so that ``softplus(bias)`` lies
    there."""
    how: str
    lo: float = 1e-3
    hi: float = 1e-1

    def __rmul__(self, x):
        import jax.numpy as jnp
        from jax.scipy.special import ndtr
        if self.how == "a_log":
            return 0.0 * x + jnp.log(jnp.arange(
                1, x.shape[0] + 1, dtype=jnp.float32))[:, None]
        dt = jnp.exp(math.log(self.lo)
                     + ndtr(x) * (math.log(self.hi) - math.log(self.lo)))
        return dt + jnp.log(-jnp.expm1(-dt))


def leaf_specs(config):
    n = dims(config)
    d, f, di, N, K, R, hd = (n[k] for k in ("d", "f", "di", "N", "K", "R",
                                             "hd"))
    s = 1.0 / math.sqrt(d)
    norm = lambda name: [(name + ".scale", (d,), None),
                         (name + ".bias", (d,), 0.02)]
    out = [("embed.table", (n["V"], d), 0.02)] + norm("final_norm")
    for i in range(n["L"]):
        p, kind = f"layers.{i}.", layer_kind(config, i)
        out += norm(p + "mix_norm")
        if kind == "mamba":
            m = p + "mamba."
            out += [(m + "in_proj.kernel", (d, 2 * di), s),
                    (m + "conv.taps", (di, K), 1.0 / math.sqrt(K)),
                    (m + "conv.bias", (di,), 0.02),
                    (m + "x_proj.kernel", (di, R + 2 * N),
                     1.0 / math.sqrt(di)),
                    (m + "dt_proj.kernel", (R, di), 1.0 / math.sqrt(R)),
                    (m + "dt_proj.bias", (di,), Init("dt_bias")),
                    (m + "A_log", (N, di), Init("a_log")),
                    (m + "D", (di,), None),
                    (m + "out_proj.kernel", (di, d), 1.0 / math.sqrt(di))]
        elif kind == "gmu":
            out += [(p + "gmu.w1.kernel", (d, di), s),
                    (p + "gmu.w2.kernel", (di, d), 1.0 / math.sqrt(di))]
        else:
            a = p + "attn."
            out.append((a + "wq.kernel", (d, n["H"] * hd), s)
                       if kind == "cross" else
                       (a + "wqkv.kernel", (d, (n["H"] + 2 * n["kv"]) * hd),
                        s))
            out += [(a + "wo.kernel", (n["H"] * hd, d),
                     1.0 / math.sqrt(n["H"] * hd))]
            out += [(a + "lambda_" + w, (hd,), 0.1)
                    for w in ("q1", "k1", "q2", "k2")]
            out.append((a + "subln.scale", (2 * hd,), None))
        out += norm(p + "ffn_norm")
        out += [(p + "ffn.fc1.kernel", (d, 2 * f), s),
                (p + "ffn.fc2.kernel", (f, d), 1.0 / math.sqrt(f))]
    return out


# ------------------------------------------------------------- the reference
def layer_norm(x, p, name, config):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + float(config["layer_norm_eps"]))
            * p[name + ".scale"] + p[name + ".bias"])


def layer_kinds(config):
    """A layer's kind for the reference: its mixer's, with the layer's index
    where the equations read it (``lam_init``), and ``mamba+m`` for the one
    state-space layer that hands its scan output on."""
    half = config["num_hidden_layers"] // 2
    out = []
    for i in range(config["num_hidden_layers"]):
        kind = layer_kind(config, i)
        out.append((kind, i) if kind in ("swa", "full", "cross")
                   else "mamba+m" if i == half else kind)
    return out


def _parts(config):
    """Where the residual, m, k and v lie in the reference's x."""
    n = dims(config)
    ends, at = {}, 0
    for name, width in (("x", n["d"]), ("m", n["di"]),
                        ("k", n["kv"] * n["hd"]), ("v", n["kv"] * n["hd"])):
        ends[name] = (at, at + width)
        at += width
    return ends, at


def embed(p, ids, config):
    import jax.numpy as jnp
    x = jnp.take(p["embed.table"], ids, axis=0)
    _, width = _parts(config)
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),))


def mamba(p, a, config, mm):
    """(mixer output, y before the gate) of a state-space layer on its normed
    input a [B, S, d]: the scan a plain loop over the positions."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    K, N, R, S = n["K"], n["N"], n["R"], a.shape[1]
    u, z = jnp.split(mm(a, p["mamba.in_proj.kernel"]), 2, axis=-1)
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    w = p["mamba.conv.taps"]
    u = jax.nn.silu(sum(w[:, j] * up[:, j:j + S] for j in range(K))
                    + p["mamba.conv.bias"])
    dt, B, C = jnp.split(mm(u, p["mamba.x_proj.kernel"]), [R, R + N], axis=-1)
    delta = jax.nn.softplus(mm(dt, p["mamba.dt_proj.kernel"])
                            + p["mamba.dt_proj.bias"])
    A = -jnp.exp(p["mamba.A_log"]).T        # the leaf is held [N, di]

    def step(h, t):
        delta_t, u_t, B_t, C_t = t          # [B, di], [B, di], [B, N], [B, N]
        h = (jnp.exp(delta_t[..., None] * A) * h
             + (delta_t * u_t)[..., None] * B_t[:, None, :])
        return h, jnp.sum(h * C_t[:, None, :], -1)
    t_major = lambda x: jnp.moveaxis(x, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros(u.shape[:1] + A.shape, jnp.float32),
                        tuple(map(t_major, (delta, u, B, C))))
    y = t_major(y) + p["mamba.D"] * u
    return mm(y * jax.nn.silu(z), p["mamba.out_proj.kernel"]), y


def lam(p, i):
    """A layer's ``lam``: what its four leaves learnt, about ``lam_init``."""
    import jax.numpy as jnp
    dot = lambda a, b: jnp.sum(p["attn.lambda_" + a] * p["attn.lambda_" + b])
    return jnp.exp(dot("q1", "k1")) - jnp.exp(dot("q2", "k2")) + lambda_init(i)


def subln(o, p, config):
    """The norm of a differential head's output [.., 2 hd], with its gain."""
    import jax
    import jax.numpy as jnp
    return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + float(
        config["layer_norm_eps"])) * p["attn.subln.scale"]


def diff_attention_row(p, a, config, mm, i, kv=None, window=None):
    """One row's differential attention on its normed input a [S, d]:
    (output, (k, v) as projected); with ``kv`` [S, KV hd] each, only the
    queries are projected and those are read."""
    import jax
    import jax.numpy as jnp
    n = dims(config)
    S, H, KV, hd = a.shape[0], n["H"], n["kv"], n["hd"]
    if kv is None:
        q, k, v = jnp.split(mm(a, p["attn.wqkv.kernel"]),
                            [H * hd, (H + KV) * hd], axis=-1)
    else:
        q, (k, v) = mm(a, p["attn.wq.kernel"]), kv
    G, rep = KV // 2, H // KV
    qg = q.reshape(S, G, rep, 2, hd)       # head n = g * rep + r, query j
    kg = k.reshape(S, G, 2, hd)
    vg = v.reshape(S, G, 2 * hd)
    s = jnp.einsum("qgrjd,kgjd->grjqk", qg, kg) / math.sqrt(hd)
    at = jnp.arange(S)
    see = at[:, None] >= at[None, :]
    if window is not None:
        see &= at[:, None] - at[None, :] < window
    P = jax.nn.softmax(jnp.where(see, s, -jnp.inf), -1)
    o = jnp.einsum("grjqk,kgv->qgrjv", P, vg)
    o = o[..., 0, :] - lam(p, i) * o[..., 1, :]
    o = subln(o, p, config) * (1.0 - lambda_init(i))
    return mm(o.reshape(S, -1), p["attn.wo.kernel"]), (k, v)


def layer(kind, p, xx, config, mm):
    import jax
    import jax.numpy as jnp
    ends, _ = _parts(config)
    cut = lambda name: xx[..., ends[name][0]:ends[name][1]]
    x, m, k, v = (cut(name) for name in ("x", "m", "k", "v"))
    a = layer_norm(x, p, "mix_norm", config)
    if kind in ("mamba", "mamba+m"):
        y, mem = mamba(p, a, config, mm)
        if kind == "mamba+m":
            m = mem
    elif kind == "gmu":
        y = mm(jax.nn.silu(mm(a, p["gmu.w1.kernel"])) * m, p["gmu.w2.kernel"])
    else:
        kind, i = kind
        if kind == "cross":
            y = jax.lax.map(lambda t: diff_attention_row(
                p, t[0], config, mm, i, kv=t[1:])[0], (a, k, v))
        else:
            window = config["sliding_window"] if kind == "swa" else None
            y, made = jax.lax.map(lambda row: diff_attention_row(
                p, row, config, mm, i, window=window), a)
            if kind == "full":
                k, v = made
    x = x + y
    g, u = jnp.split(mm(layer_norm(x, p, "ffn_norm", config),
                        p["ffn.fc1.kernel"]), 2, axis=-1)
    x = x + mm(jax.nn.silu(g) * u, p["ffn.fc2.kernel"])
    return jnp.concatenate([x, m, k, v], -1)


def head(p, xx, config, mm):
    x = xx[..., :config["hidden_size"]]
    return mm(layer_norm(x, p, "final_norm", config), p["embed.table"].T)


# -------------------------------------------------------------- the toy copy
def tiny(config):
    """Toy widths, 4 + 4 layers so that every kind of layer is there (three
    state-space, two window, the full one, a gated memory unit, a cross
    layer), a window of 8 that every rehearsed answer passes."""
    return dict(config, hidden_size=64, num_attention_heads=8,
                num_key_value_heads=4, intermediate_size=96,
                num_hidden_layers=8, sliding_window=8, vocab_size=256,
                max_position_embeddings=256, torch_dtype="float32")


# ------------------------------------------------------------- the yardstick
def _mixers(config):
    """{kind: (matrix parameters a token is multiplied by, every other
    parameter) of one mixer of that kind}."""
    n = dims(config)
    d, di, N, K, R, hd = (n[k] for k in ("d", "di", "N", "K", "R", "hd"))
    q, kv = d * n["H"] * hd, d * 2 * n["kv"] * hd
    lam = 4 * hd + 2 * hd
    return {"mamba": (d * 2 * di + di * (R + 2 * N) + R * di + di * d,
                      di * K + di + di + di * N + di),
            "swa": (q + kv + n["H"] * hd * d, lam),
            "full": (q + kv + n["H"] * hd * d, lam),
            "cross": (q + n["H"] * hd * d, lam),
            "gmu": (2 * d * di, 0)}


def params_by_kind(config):
    """{kind: parameters of one whole layer of that kind} (mixer, FFN and the
    two norms with their biases)."""
    n = dims(config)
    rest = 3 * n["d"] * n["f"] + 4 * n["d"]
    return {k: a + b + rest for k, (a, b) in _mixers(config).items()}


def param_counts(config):
    """``matmul`` counts the embedding once, as the head's matrix (a token's
    own row of it is a lookup); ``total`` every leaf once (the head is
    tied)."""
    n = dims(config)
    mix, layers = _mixers(config), _layers(config)
    embed = n["d"] * n["V"]
    matmul = sum(layers[k] * (mix[k][0] + 3 * n["d"] * n["f"]) for k in mix)
    total = sum(layers[k] * v for k, v in params_by_kind(config).items())
    return {"matmul": matmul + embed, "embed": embed,
            "total": total + embed + 2 * n["d"]}


def tick_weight_bytes(config, tokens, itemsize):
    """Every matrix once, whatever the tick's tokens: the stack is dense."""
    return itemsize * param_counts(config)["matmul"]


def cache_bytes_per_position_per_layer(config, itemsize):
    """What one attention layer's pool holds of one cached position (K and
    V, ``KV`` heads of ``hd`` each)."""
    n = dims(config)
    return 2 * n["kv"] * n["hd"] * itemsize


def _layers_read(config, context=MEAN_LIVE_CONTEXT):
    """Layers' worth of cached positions that a new token reads a position
    of context: the ONE full layer once for itself and once for every cross
    layer, a window layer ``window / context`` once the context has passed
    its window (as families/swa_moe.py reckons its rings)."""
    n, layers = dims(config), _layers(config)
    return (layers["full"] + layers["cross"]
            + layers["swa"] * min(1.0, n["W"] / context))


def cache_bytes_per_position(config, itemsize):
    """K and V that a new token READS of one position of its context, all
    layers, at ``MEAN_LIVE_CONTEXT``.  The state-space layers' carries (one
    ``[N, di]`` float32 column a slot a layer, read and written whatever the
    context) are left out, so ``model_step.required_roofline_share.serve``
    errs low."""
    return (cache_bytes_per_position_per_layer(config, itemsize)
            * _layers_read(config))


def attn_flops_per_position(config):
    """Score and value FLOPs of one new token against one position of its
    context, all layers: H queries of hd against a key, and H weights on a
    value of 2 hd."""
    n = dims(config)
    return 2.0 * n["H"] * (n["hd"] + 2 * n["hd"]) * _layers_read(config)


def train_flops_per_token(config, seq):
    """Not trained here (16 bytes a parameter fit no chip whole, and no cut
    that fits keeps every kind of layer); the convention of the other
    families, for the contract's sake."""
    n, layers = dims(config), _layers(config)
    return (6.0 * param_counts(config)["matmul"] + 6.0 * seq * n["H"]
            * n["hd"] * (layers["swa"] + layers["full"] + layers["cross"]))


def state_columns(config):
    """{state kind: columns a slot of its pool}: what a tick reads back (the
    conv's ``K - 1`` inputs, the scan's ONE carry) plus a verify row's ``1 +
    spec_k`` (the program's paged.state_columns, reckoned here from the
    configuration file alone)."""
    e = config["engine"]
    row = 1 + e.get("spec_k", 4) if e.get("spec_decode", True) else 1
    return {"conv": dims(config)["K"] - 1 + row, "carry": 1 + row}


def state_bytes_per_slot(config, columns, itemsize):
    """{state kind: bytes a slot} at ``columns`` columns of either kind: the
    conv inputs ``[di]`` in the model's type, the carries ``[N, di]`` in
    float32 whatever ``itemsize``."""
    n, layers = dims(config), _layers(config)["mamba"]
    return {"conv": layers * columns * n["di"] * itemsize,
            "carry": layers * columns * n["di"] * n["N"] * 4}


def ring_positions(config):
    """Positions a slot's ring holds in a window layer's pool: the window
    plus one chunk, rounded up to blocks, at most a whole context's (the
    program's paged.ring_blocks)."""
    e = config["engine"]
    bs = e["block_size"]
    return bs * min(-(-(config["sliding_window"] + e["prefill_chunk"]) // bs),
                    -(-e["max_seq_len"] // bs))


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
    (``stats()["kv_pool"]["kinds"]``: ``conv`` and ``carry``) grew by between
    the window's marks, added up ({name: delta}, with the ticks and one
    kind's slot-ticks): what the state-space layers hold a slot beside what
    a key-value cache of the same layers would.  None where the program has
    no such kinds (the parent commit) or no tick ran."""
    conv, carry = _kind_counts(ctx, "conv"), _kind_counts(ctx, "carry")
    if not conv or not carry:
        return None
    # both kinds are the same nine layers': a key-value cache of them counts
    # once
    return dict(conv, state_bytes_ticks=conv["state_bytes_ticks"]
                + carry["state_bytes_ticks"])


def ring_counts(ctx):
    """The window kind's counters between the window's marks (as
    families/swa_moe.py ``ring_counts``)."""
    return _kind_counts(ctx, "window")


def _ops(ctx):
    tr = ctx["trace"]
    return (tr.get("ops_s", {}), tr["module_count"]) \
        if tr and tr.get("module_count") else ({}, 0)


def _top(label, hits, ticks):
    top = sorted(hits.items(), key=lambda kv: -kv[1])[:5]
    print(f"perfbench: {label} ms/tick "
          + "; ".join(f"{n}={1e3 * s / ticks:.3f}" for n, s in top),
          flush=True)


def scan_op_types(config):
    """The output types of the device ops that are a selective scan's own:
    whatever is ``[.., N, di]`` (the discretised ``exp(D_t A)`` and ``(D_t
    u_t) B_t``, the recurrence's state, the readout's product), the state
    kinds' ops apart (:func:`state_op_types`: the pools, and the buffer of
    the carry after every row, which is kept for the snapshots' sake)."""
    n = dims(config)
    return [f",{n['N']},{n['di']}]"]


def state_op_types(config):
    """The output types of the device ops that make or move the two state
    kinds' pools: the stacked carries ``[layers, slots, columns, N, di]``,
    which the snapshots' scatter returns whole, one layer of them, the one
    column a slot that a tick reads (``[slots, N, di]`` out of a gather),
    the conv inputs' pool ``[layers, slots, columns, di]``, and the buffer
    the snapshots are taken from: the carry after EVERY row of the scan,
    ``[1 + spec_k, slots, N, di]`` in a verify tick and ``[max_batch_tokens,
    1, N, di]`` in a chunk-wide one (its zeros, the row written a step, its
    copy out of the loop).  The scan alone would keep the last carry."""
    n, e = dims(config), config["engine"]
    layers, cols, S = _layers(config)["mamba"], state_columns(config), e["max_slots"]
    one = f"{n['N']},{n['di']}]"
    carry = f"{S},{cols['carry']},{one}"
    conv = f"{S},{cols['conv']},{n['di']}]"
    return [f"[{layers},{carry}", f"[{carry}", f"[{layers},{conv}",
            f"[{conv}", f"[{cols['carry'] - 1},{S},{one}",
            f"[{e['max_batch_tokens']},1,{one}"]


def _is_state_op(name, config):
    n, S = dims(config), config["engine"]["max_slots"]
    return (any(t in name for t in state_op_types(config))
            or (name.startswith(("gather", "dynamic-slice"))
                and f"[{S},{n['N']},{n['di']}]" in name))


def scan_share(ctx):
    """Device self-time of the nine scans' own ops (:func:`scan_op_types`,
    the state kinds' ops apart) over the tick program's device time in the
    trace, in %.  What makes ``[rows, di]`` — the gate, the conv, the
    projections — is NOT counted: the share is the discretisation's, the
    recurrence's and the readout's.  Prints the five costliest.  None
    without a trace or such ops."""
    ops, ticks = _ops(ctx)
    config = ctx["config"]
    hits = {n: s for n, s in ops.items()
            if any(t in n for t in scan_op_types(config))
            and not _is_state_op(n, config)}
    if not hits or not ctx["trace"].get("module_s"):
        return None
    _top("ssm scan ops", hits, ticks)
    return 100.0 * sum(hits.values()) / ctx["trace"]["module_s"]


def state_ops_ms(ctx):
    """Device self-time a tick, in ms, of the ops that make or move the
    state kinds' pools (:func:`state_op_types`: the carry's read, the
    snapshots' writes, the conv columns); prints the five costliest.  None
    without a trace or such ops."""
    ops, ticks = _ops(ctx)
    hits = {n: s for n, s in ops.items() if _is_state_op(n, ctx["config"])}
    if not hits:
        return None
    _top("ssm state ops", hits, ticks)
    return 1e3 * sum(hits.values()) / ticks


def shared_kv_op_types(config):
    """The output types of the device ops that fetch the ONE full layer's
    pool, read by that layer and by every cross layer: a tile's gather by
    table, ``[slots of a block, a tile's entries, block, KV hd]`` or flat
    ``[.., a tile's positions, KV hd]`` (the program's paged.TILE positions
    a step), and the stacked pool ``[1, blocks, block, KV hd]`` that the
    full layer's scatter returns.  A window layer's ring is ``ring /
    block`` entries long, another number."""
    n, e = dims(config), config["engine"]
    bs = e["block_size"]
    try:
        from horovod_tpu.models.paged import TILE
    except ImportError:
        return []
    tail, tb = f"{n['kv'] * n['hd']}]", max(TILE // bs, 1)
    # (the chip makes a tile's gather for its block's two slots, or a
    # chunk's one, with slots and entries as one axis)
    return [f",{tb},{bs},{tail}", f",{TILE},{tail}",
            f"[{2 * tb},{bs},{tail}", f"[{tb},{bs},{tail}",
            f"[1,{e['cache_blocks']},{bs},{tail}",
            f"[{e['cache_blocks']},{bs},{tail}"]


def shared_kv_ops_ms(ctx):
    """Device self-time a tick, in ms, of the ops shaped like a fetch of the
    one full layer's pool (:func:`shared_kv_op_types`); prints the five
    costliest.  None without a trace or such ops."""
    ops, ticks = _ops(ctx)
    types = shared_kv_op_types(ctx["config"]) if ops else []
    hits = {n: s for n, s in ops.items() if any(t in n for t in types)}
    if not hits:
        return None
    _top("shared kv ops", hits, ticks)
    return 1e3 * sum(hits.values()) / ticks


def pool_op_types(config, kind):
    """The output types of the device ops that make or move the WINDOW
    kind's pool or a gather of it (``kind`` is ``window``; the one full
    layer's pool is :func:`shared_kv_op_types`'s): the stacked rings
    ``[window layers, slots x ring entries, block, KV hd]`` that a layer's
    scatter returns, and the gather of the ring's entries that the windows of
    a tick's queries can reach (the program's ``_attention``: ``(window + c -
    2) // block + 2`` of them for c columns, at most the ring's) by slot
    ``[.., entries, block, KV hd]``, with slots and entries as one axis, and
    flat ``[.., positions, KV hd]`` or cut into the ``KV / 2`` values of ``2
    hd`` (the relayout of the gathered keys and values for the differential
    heads)."""
    if kind != "window":
        return []
    n, e = dims(config), config["engine"]
    bs, S = e["block_size"], e["max_slots"]
    tail = f"{n['kv'] * n['hd']}]"
    ring = ring_positions(config) // bs
    out = [f"[{_layers(config)['swa']},{S * ring},{bs},{tail}"]
    for c in (state_columns(config)["carry"] - 1, e["prefill_chunk"]):
        need = min(ring, (n["W"] + c - 2) // bs + 2)
        out += [f",{need},{bs},{tail}", f"[{need},{bs},{tail}",
                f"[{S * need},{bs},{tail}", f",{need * bs},{tail}",
                f",{need * bs},{n['kv'] // 2},{2 * n['hd']}]"]
    return out


def pool_ops_ms(ctx, kind):
    """Device self-time a tick, in ms, of the ops shaped like the window
    kind's pool or a gather of it (:func:`pool_op_types`; as
    families/swa_moe.py ``pool_ops_ms``); prints the five costliest.  None
    without a trace or such ops, and for any kind but ``window``."""
    ops, ticks = _ops(ctx)
    types = pool_op_types(ctx["config"], kind)
    hits = {n: s for n, s in ops.items() if any(t in n for t in types)}
    if not hits:
        return None
    _top(f"{kind} pool ops", hits, ticks)
    return 1e3 * sum(hits.values()) / ticks
