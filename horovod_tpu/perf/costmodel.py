"""Analytical cost model: the predicted half of the attribution plane.

The Horovod paper justified tensor fusion by characterizing where step
time went BY HAND with its timeline (arxiv 1802.05799 §4); arxiv
1810.11112 argues that characterization must be systematic.  This module
is the systematic half: trace-time FLOP/byte accounting that yields a
roofline-style *predicted* step time per link class, which the ledger
(``perf/ledger.py``) holds against the *measured* decomposition — so the
model's own drift is observable (``docs/profiling.md``).

One source of truth: ``bench.py``'s MFU math (the ``PEAKS`` table keyed
by ``device_kind``, the 6·N FLOPs/token convention) lives HERE and is
imported by the bench, the ledger and the tests — the constants can no
longer fork.

Deliberately stdlib-only at module level (no jax, no package-relative
imports), so ``scripts/perf_gate.py`` can load this file standalone by
path.  Functions that consume jax objects (bucket plans, compiled
programs) import lazily inside their bodies.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------- hardware
# Published per-chip peaks, keyed by jax's ``device_kind`` (the spellings
# are jax's own: jax/_src/pallas/mosaic/tpu_info.py).  The ONE table: a
# kind that is not in it is an error, never a default.
PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v4": {"bf16_tflops": 275.0, "hbm_gbps": 1200.0, "hbm_gb": 32.0,
               "source": "Google Cloud documentation, 'TPU v4'"},
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0, "hbm_gb": 16.0,
                    "source": "Google Cloud documentation, 'TPU v5e'"},
    "TPU v5p": {"bf16_tflops": 459.0, "hbm_gbps": 2765.0, "hbm_gb": 95.0,
                "source": "Google Cloud documentation, 'TPU v5p'"},
    "TPU v6 lite": {"bf16_tflops": 918.0, "hbm_gbps": 1640.0, "hbm_gb": 32.0,
                    "source": "Google Cloud documentation, 'TPU v6e'"},
}

# What the predictive model below prices a CPU-virtual host at, so the
# layout solver and the drift gauges have a compute term to rank with on
# the virtual test mesh.  A modelling constant, not a peak: no
# utilization is ever computed against it.
CPU_MODEL_TFLOPS = 0.5

# Per-chip link bandwidth by fabric class, GB/s (order-of-magnitude public
# figures: ICI ~ hundreds of GB/s per chip, DCN ~ tens, loopback is a
# same-host memcpy).  The roofline uses these to turn modeled wire bytes
# into seconds; absolute accuracy matters less than the ICI/DCN ratio —
# the quantity that decides comm-bound vs compute-bound.
LINK_GBPS: Dict[str, float] = {
    "ici": 100.0,
    "dcn": 6.25,       # ~50 Gbit/s per host
    "loopback": 10.0,  # CPU-virtual: one-process memcpy "fabric"
}
LINK_CLASSES = tuple(sorted(LINK_GBPS))


def device_peaks(device_kind: str) -> Dict[str, Any]:
    """One chip's published peaks by ``device_kind``; a kind outside the
    table raises (there is no chip to fall back to)."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"(horovod_tpu/perf/costmodel.py PEAKS: {', '.join(PEAKS)}); "
            "add its published peaks with their source before computing "
            "a utilization on it")
    return PEAKS[device_kind]


def peak_flops(chip: str) -> float:
    """FLOP/s the predictive model prices ``chip`` at: a ``device_kind``
    of the peaks table, or ``"cpu"`` (the CPU-virtual modelling
    constant)."""
    if chip == "cpu":
        return CPU_MODEL_TFLOPS * 1e12
    return device_peaks(chip)["bf16_tflops"] * 1e12


def link_bandwidth(link: str) -> float:
    """Link class name -> bytes/s."""
    if link not in LINK_GBPS:
        raise ValueError(
            f"unknown link class {link!r}; valid: {', '.join(LINK_CLASSES)} "
            "(HOROVOD_PERF_LINK, docs/profiling.md)")
    return LINK_GBPS[link] * 1e9


# ------------------------------------------------------------------- flops
def train_flops_per_token(n_params: int,
                          attention: Optional[Dict[str, Any]] = None
                          ) -> float:
    """Training FLOPs per token.

    Baseline convention (what bench.py's MFU always used): ``6·N`` —
    2·N for the forward matmuls, 4·N for backward, attention score/value
    matmuls EXCLUDED.  This is the standard, conservative MFU convention.

    ``attention={"n_layers", "dim", "seq", "causal"}`` adds the attention
    term: per layer and token the score (q·Kᵀ) and value (p·V) matmuls
    are 2·2·seq·dim MACs = 4·seq·dim forward FLOPs, tripled for the
    backward pass -> ``12·n_layers·seq·dim`` per token; ``causal=True``
    (default) halves it, since position t attends to t+1 of seq keys on
    average.  MFU computed with the attention term included is reported
    as ``mfu_attn`` beside the conservative ``mfu`` (docs/profiling.md).
    """
    flops = 6.0 * float(n_params)
    if attention:
        layers = float(attention["n_layers"])
        dim = float(attention["dim"])
        seq = float(attention["seq"])
        attn = 12.0 * layers * seq * dim
        if attention.get("causal", True):
            attn *= 0.5
        flops += attn
    return flops


# ------------------------------------------------------------ param counts
def llama_param_count(vocab: int, dim: int, n_layers: int, n_heads: int,
                      n_kv_heads: int, ffn_dim: int) -> int:
    """Exact parameter count of ``models/llama.py`` init() from config
    shapes — no device allocation needed, so golden tests and the cost
    model can price the bench configs analytically."""
    head_dim = dim // n_heads
    per_layer = (
        dim * n_heads * head_dim          # wq
        + 2 * dim * n_kv_heads * head_dim  # wk, wv
        + n_heads * head_dim * dim         # wo
        + 3 * dim * ffn_dim                # w_gate, w_up, w_down
        + 2 * dim                          # attn_norm, ffn_norm
    )
    return (vocab * dim                    # embed
            + n_layers * per_layer
            + dim                          # final_norm
            + dim * vocab)                 # lm_head


def moe_llama_param_count(vocab: int, dim: int, n_layers: int,
                          n_heads: int, n_kv_heads: int, moe_hidden: int,
                          n_experts: int) -> int:
    """Exact parameter count of ``models/moe_llama.py`` init(): llama
    attention blocks with the dense FFN replaced by router + stacked
    expert FFNs (``parallel/expert.py`` init_moe_params layout)."""
    head_dim = dim // n_heads
    per_layer = (
        dim * n_heads * head_dim
        + 2 * dim * n_kv_heads * head_dim
        + n_heads * head_dim * dim
        + 2 * dim                                  # attn_norm, ffn_norm
        + dim * n_experts                          # router
        + 2 * n_experts * dim * moe_hidden         # wi, wo
    )
    return vocab * dim + n_layers * per_layer + dim + dim * vocab


def moe_llama_active_param_count(vocab: int, dim: int, n_layers: int,
                                 n_heads: int, n_kv_heads: int,
                                 moe_hidden: int, n_experts: int,
                                 experts_per_token: int) -> int:
    """Parameters a single token's forward pass actually touches (the N
    that belongs in 6·N for MoE MFU): all non-expert weights plus
    ``experts_per_token`` expert FFNs per layer."""
    total = moe_llama_param_count(vocab, dim, n_layers, n_heads,
                                  n_kv_heads, moe_hidden, n_experts)
    inactive_experts = n_experts - experts_per_token
    return total - n_layers * 2 * inactive_experts * dim * moe_hidden


# ---------------------------------------------------------------- roofline
def ring_wire_bytes(nelems: int, itemsize: float, n: int) -> float:
    """Per-chip wire bytes of one ring allreduce (the same model as
    ``ops/wire.modeled_wire_bytes``'s flat case, restated stdlib-only:
    each chip sends 2(n-1) chunks of ceil(nelems/n) elements)."""
    if n <= 1:
        return 0.0
    return 2.0 * (n - 1) * math.ceil(nelems / n) * itemsize


def predicted_step_time(flops: float, comm_bytes: float, *,
                        chip: str = "cpu", link: str = "loopback",
                        overlap_fraction: float = 0.0,
                        input_seconds: float = 0.0) -> Dict[str, float]:
    """Roofline-style predicted step decomposition, in seconds.

    ``compute`` = flops / chip peak; ``exposed_comm`` = the
    non-overlapped share of comm bytes over the link-class bandwidth
    (overlapped comm hides behind compute by construction, so only the
    exposed share lands on the critical path); ``step`` adds the
    host-input term.  A prediction, not a measurement — the ledger
    records the deltas against measured time so model drift is itself
    observable (``hvd_perf_model_drift_ratio``)."""
    if not 0.0 <= overlap_fraction <= 1.0:
        raise ValueError(
            f"overlap_fraction {overlap_fraction} outside [0, 1]")
    compute = float(flops) / peak_flops(chip)
    exposed = (float(comm_bytes) * (1.0 - overlap_fraction)
               / link_bandwidth(link))
    return {
        "compute_s": compute,
        "exposed_comm_s": exposed,
        "host_input_s": float(input_seconds),
        "step_s": compute + exposed + float(input_seconds),
        "chip": chip,
        "link": link,
    }


# ------------------------------------------------------- ZeRO what-if model
# Wire itemsize per RS-leg format (bytes/element on the wire) — the
# stdlib restatement of ops/wire.py's table, so the zero chain's
# trace-time gauges and this prediction cannot fork.
WIRE_ITEMSIZE: Dict[str, float] = {
    "none": 4.0, "bf16": 2.0, "fp16": 2.0,
    "int8_ring": 1.0, "dcn_int8": 1.0,
}
ZERO_LEVELS = (0, 1, 2, 3)


def _ring_half_leg(n: int, nelems: float, itemsize: float) -> float:
    """One reduce_scatter OR all_gather leg of the standard ring, per
    chip: (n-1) chunks of ceil(nelems/n) elements (half of
    :func:`ring_wire_bytes`'s full allreduce)."""
    if n <= 1:
        return 0.0
    return (n - 1) * math.ceil(nelems / n) * itemsize


def zero_comm_bytes(nelems: float, world: int, level: int, *,
                    k: int = 1, wire_format: str = "none",
                    itemsize: float = 4.0) -> Dict[str, float]:
    """Per-chip modeled wire bytes of ONE optimizer step of the ZeRO
    chain (parallel/zero.py; docs/zero.md) — the RS and AG legs priced
    separately, per level:

      level 0  plain DP: accumulate k microbatches locally, ONE
               allreduce (both ring phases at the wire itemsize, the
               ops/wire.py allreduce model);
      level 1  k per-microbatch syncs; at k > 1 each shard is gathered
               back to keep the full gradient accumulator (the
               redundancy level 2 deletes), plus the update all_gather;
      level 2  k reduce_scatters onto the resident shard + one update
               all_gather;
      level 3  k reduce_scatters + one PARAM all_gather at step start —
               the same bytes as level 2 (RS+AG == AR at k=1: the
               ZeRO/arXiv:2004.13336 equal-wire-bytes claim).

    The RS leg carries ``wire_format``'s itemsize; AG legs are exact
    (``itemsize``) — gathered payloads are master state with no EF
    channel (docs/zero.md#wire-composition).
    """
    if level not in ZERO_LEVELS:
        raise ValueError(f"zero level {level} invalid; must be one of "
                         f"{ZERO_LEVELS}")
    n = int(world)
    enc = WIRE_ITEMSIZE.get(wire_format, itemsize)
    rs = _ring_half_leg(n, nelems, enc)
    ag = _ring_half_leg(n, nelems, itemsize)
    if level == 0:
        rs_total, ag_total = rs, _ring_half_leg(n, nelems, enc)
    elif level == 1:
        rs_total = k * rs
        ag_total = (k + 1) * ag if k > 1 else ag
    else:
        rs_total, ag_total = k * rs, ag
    return {"rs_bytes": rs_total, "ag_bytes": ag_total,
            "total_bytes": rs_total + ag_total}


def zero_memory_bytes(level: int, n_params: float, world: int, *,
                      opt_slots: int = 2, ef: bool = False,
                      itemsize: float = 4.0) -> Dict[str, int]:
    """Analytical PER-RANK resident bytes of the training state under a
    ZeRO level (docs/zero.md#memory-math): params, the gradient
    accumulator, optimizer state (``opt_slots`` params-shaped buffers —
    2 for adam's moments) and the EF residual (full-size per rank when a
    lossy wire format is error-compensated; inherent to EF-on-RS).
    Level 0 = plain data parallelism, the reduction baseline."""
    if level not in ZERO_LEVELS:
        raise ValueError(f"zero level {level} invalid; must be one of "
                         f"{ZERO_LEVELS}")
    n = max(int(world), 1)
    p = float(n_params) * itemsize
    out = {
        "params_bytes": p / n if level >= 3 else p,
        "grads_bytes": p / n if level >= 2 else p,
        "opt_state_bytes": (p * opt_slots / n if level >= 1
                           else p * opt_slots),
        "ef_residual_bytes": p if ef else 0.0,
    }
    out = {key: int(v) for key, v in out.items()}
    out["total_bytes"] = sum(out.values())
    return out


def zero_level_table(n_params: float, world: int, *,
                     opt_slots: int = 2, k: int = 1,
                     wire_format: str = "none", ef: bool = False,
                     chip: str = "cpu", link: str = "loopback",
                     flops_per_step: Optional[float] = None
                     ) -> List[Dict[str, Any]]:
    """The "what would ZeRO-N cost me at my topology" table
    (docs/zero.md): one row per level with the analytical per-rank
    memory, the per-step wire bytes split RS/AG, the exposed-comm
    seconds on ``link``, and — when ``flops_per_step`` is known — the
    roofline predicted step.  Rendered by ``hvd.perf_report()`` /
    ``GET /perf`` / ``hvdrun doctor --perf``; the ledger measures the
    active level's drift against it."""
    rows = []
    for level in ZERO_LEVELS:
        comm = zero_comm_bytes(n_params, world, level, k=k,
                               wire_format=wire_format)
        row: Dict[str, Any] = {
            "level": level,
            "memory": zero_memory_bytes(level, n_params, world,
                                        opt_slots=opt_slots, ef=ef),
            "comm": {key: int(v) for key, v in comm.items()},
            "exposed_comm_s": comm["total_bytes"] / link_bandwidth(link),
        }
        if flops_per_step:
            row["predicted"] = predicted_step_time(
                flops_per_step, comm["total_bytes"], chip=chip, link=link)
        rows.append(row)
    return rows


# ------------------------------------------------------- 3D layout solver
# The whole-parallelism-space extrapolation of the ZeRO what-if table
# above (ROADMAP item 2; docs/parallelism.md): enumerate (dp, tp, pp,
# zero_level, wire, overlap_depth) factorizations of the topology, price
# each with the SAME roofline primitives the ledger validates
# (ring_wire_bytes / zero_comm_bytes / zero_memory_bytes), filter by a
# per-chip memory cap, rank by predicted step time.  Stdlib-only like
# everything else here so bench.py can load it standalone.
LAYOUT_AXES = ("dp", "tp", "pp")

# Live activation bytes per token per resident layer, in units of
# dim * itemsize: residual stream + normed input + attn output + ffn
# intermediate held for the backward pass.  A deliberate small-constant
# model (docs/parallelism.md#memory-cap), not a measurement — the bench
# reports the measured peak beside it so the gap stays observable.
ACTIVATION_MULT = 4.0


def tp_comm_bytes(tp: int, tokens: float, dim: int, n_layers: int, *,
                  itemsize: float = 4.0) -> float:
    """Per-chip wire bytes of Megatron-style tensor parallelism for one
    step: each transformer layer all_reduces the [tokens, dim] residual
    activation twice in the forward (attention wo and FFN down row-
    parallel psums) and twice in the backward (the conjugate f-operator
    psums at the column-parallel block inputs) -> 4 ring allreduces per
    layer over the tp group (parallel/layout.py places exactly these)."""
    if tp <= 1:
        return 0.0
    return 4.0 * n_layers * ring_wire_bytes(tokens * dim, itemsize, tp)


def pp_comm_bytes(pp: int, n_micro: int, mb_tokens: float, dim: int, *,
                  itemsize: float = 4.0) -> float:
    """Per-chip wire bytes of the GPipe schedule for one step: one
    ppermute shift of a [mb_tokens, dim] activation per tick, with
    ``n_micro + pp - 1`` ticks, forward and backward (ppermute's
    transpose is the reverse shift, same payload)."""
    if pp <= 1:
        return 0.0
    return 2.0 * (n_micro + pp - 1) * mb_tokens * dim * itemsize


def _effective_microbatches(local_batch: int, requested: int) -> int:
    """Largest divisor of ``local_batch`` that is <= ``requested`` — the
    GPipe microbatch count a (dp, pp) candidate can actually run."""
    m = max(1, min(int(requested), int(local_batch)))
    while m > 1 and local_batch % m:
        m -= 1
    return m


def layout_memory_bytes(model: Dict[str, Any], dp: int, tp: int, pp: int,
                        *, zero_level: int = 1, ef: bool = False,
                        opt_slots: int = 2) -> Dict[str, int]:
    """Per-chip resident bytes under a (dp, tp, pp) layout: the ZeRO
    state triangle priced on this chip's ``n_params / (tp*pp)`` slice
    with the RS/AG group = the dp subgroup, plus the activation term
    (batch/dp rows x the layers resident on this pipeline stage; the
    residual stream is replicated across tp so tp does not divide it)."""
    itemsize = float(model.get("itemsize", 4.0))
    n_local = float(model["n_params"]) / (tp * pp)
    out = dict(zero_memory_bytes(zero_level, n_local, dp,
                                 opt_slots=opt_slots, ef=ef,
                                 itemsize=itemsize))
    total = out.pop("total_bytes")
    batch = float(model.get("batch", dp))
    seq = float(model.get("seq", 1))
    n_layers = float(model.get("n_layers", 1))
    act = (batch / dp) * seq * (n_layers / pp) \
        * float(model.get("dim", 0)) * ACTIVATION_MULT * itemsize
    out["activation_bytes"] = int(act)
    out["total_bytes"] = int(total + act)
    return out


def layout_step_time(model: Dict[str, Any], dp: int, tp: int, pp: int, *,
                     zero_level: int = 1, k: int = 1,
                     wire_format: str = "none", overlap_depth: int = 0,
                     n_micro: int = 4, chip: str = "cpu",
                     link: str = "loopback", ef: bool = False,
                     opt_slots: int = 2) -> Dict[str, Any]:
    """Predicted step decomposition of one (dp, tp, pp) candidate:

      compute        model FLOPs spread over all dp*tp*pp chips;
      tp_comm        4 activation allreduces per layer over the tp ring;
      pp_comm        the GPipe ppermute stream;
      bubble         (S-1)/(M+S-1) inflates compute + tp comm (those run
                     inside the pipelined region; docs/parallelism.md);
      zero_comm      RS/AG legs of the chain priced on the n_params/(tp*pp)
                     slice over the DP SUBGROUP only — level-3 param
                     all_gathers hide behind forward compute with a
                     prefetch window, so depth d exposes ag/d.

    All terms land on one ``link`` class (per-link-class roofline);
    memory comes from :func:`layout_memory_bytes`."""
    itemsize = float(model.get("itemsize", 4.0))
    bw = link_bandwidth(link)
    seq = float(model.get("seq", 1))
    batch = float(model.get("batch", dp))
    n_layers = int(model.get("n_layers", 1))
    dim = int(model.get("dim", 0))
    local_rows = batch / dp
    m = _effective_microbatches(int(local_rows), n_micro) if pp > 1 else 1
    compute_s = (float(model.get("flops_per_step", 0.0))
                 / (peak_flops(chip) * dp * tp * pp))
    # Every microbatch passes through this chip's resident n_layers/pp
    # layers, so the tp rings see all local tokens per step.
    tp_s = tp_comm_bytes(tp, local_rows * seq, dim,
                         n_layers // pp if pp > 1 else n_layers,
                         itemsize=itemsize) / bw
    pp_s = pp_comm_bytes(pp, m, (local_rows / m) * seq, dim,
                         itemsize=itemsize) / bw
    bubble = (pp - 1) / (m + pp - 1) if pp > 1 else 0.0
    comm = zero_comm_bytes(float(model["n_params"]) / (tp * pp), dp,
                           zero_level, k=k, wire_format=wire_format,
                           itemsize=itemsize)
    rs_s = comm["rs_bytes"] / bw
    ag_s = comm["ag_bytes"] / bw
    if zero_level >= 3 and overlap_depth > 0:
        ag_s /= overlap_depth
    zero_s = rs_s + ag_s
    step_s = (compute_s + tp_s) / (1.0 - bubble) + pp_s + zero_s
    return {
        "layout": {"dp": dp, "tp": tp, "pp": pp},
        "zero_level": int(zero_level),
        "wire_format": wire_format,
        "overlap_depth": int(overlap_depth),
        "n_micro": int(m),
        "bubble_fraction": bubble,
        "compute_s": compute_s,
        "tp_comm_s": tp_s,
        "pp_comm_s": pp_s,
        "zero_comm_s": zero_s,
        "step_s": step_s,
        "memory": layout_memory_bytes(model, dp, tp, pp,
                                      zero_level=zero_level, ef=ef,
                                      opt_slots=opt_slots),
        "chip": chip,
        "link": link,
    }


def _factorizations(world: int):
    for dp in range(1, world + 1):
        if world % dp:
            continue
        rest = world // dp
        for tp in range(1, rest + 1):
            if rest % tp:
                continue
            yield dp, tp, rest // tp


def enumerate_layouts(model: Dict[str, Any], world: int, *,
                      levels=(1, 2, 3), wires=("none",),
                      overlap_depths=(0,), k: int = 1, n_micro: int = 4,
                      chip: str = "cpu", link: str = "loopback",
                      ef: bool = False) -> List[Dict[str, Any]]:
    """All VALID (dp, tp, pp, zero_level, wire, overlap_depth) candidates
    at ``world`` chips: dp*tp*pp == world, tp divides n_heads AND
    n_kv_heads (contiguous GQA head slices stay aligned), pp divides
    n_layers, dp divides the global batch.  ``overlap_depths`` only fans
    out at level 3 (prefetch is a level-3 knob; docs/zero.md)."""
    n_heads = int(model.get("n_heads", 1))
    n_kv = int(model.get("n_kv_heads", n_heads))
    n_layers = int(model.get("n_layers", 1))
    batch = int(model.get("batch", world))
    rows = []
    for dp, tp, pp in _factorizations(int(world)):
        if n_heads % tp or n_kv % tp or n_layers % pp or batch % dp:
            continue
        for level in levels:
            for wire in wires:
                depths = overlap_depths if level >= 3 else (0,)
                for depth in depths:
                    rows.append(layout_step_time(
                        model, dp, tp, pp, zero_level=level, k=k,
                        wire_format=wire, overlap_depth=depth,
                        n_micro=n_micro, chip=chip, link=link, ef=ef))
    return rows


def solve_layout(model: Dict[str, Any], world: int, *,
                 mem_cap_bytes: Optional[float] = None,
                 levels=(1, 2, 3), wires=("none",), overlap_depths=(0,),
                 k: int = 1, n_micro: int = 4, chip: str = "cpu",
                 link: str = "loopback", ef: bool = False
                 ) -> Dict[str, Any]:
    """The auto-layout decision (HOROVOD_LAYOUT=auto; ROADMAP item 2):
    rank :func:`enumerate_layouts` candidates memory-fits-first then by
    predicted step time (ties -> fewer pipeline stages, then less tensor
    parallelism — pure dp wins when the model says it's free).  The
    default ``mem_cap_bytes`` callers pass is the memory plane's measured
    ``headroom_bytes`` (PR 16).  Returns the full ranked table plus the
    chosen row; ``chosen["fits"]`` is False only when NOTHING fits — the
    least-infeasible candidate is still surfaced so doctor can say why."""
    rows = enumerate_layouts(model, world, levels=levels, wires=wires,
                             overlap_depths=overlap_depths, k=k,
                             n_micro=n_micro, chip=chip, link=link, ef=ef)
    if not rows:
        raise ValueError(
            f"no valid (dp, tp, pp) factorization of world={world} for "
            f"this model (check n_heads/n_kv_heads/n_layers/batch "
            "divisibility; docs/parallelism.md#constraints)")
    for row in rows:
        row["fits"] = (mem_cap_bytes is None
                       or row["memory"]["total_bytes"] <= mem_cap_bytes)
    rows.sort(key=lambda r: (not r["fits"], r["step_s"],
                             r["layout"]["pp"], r["layout"]["tp"]))
    for rank, row in enumerate(rows, start=1):
        row["rank"] = rank
    return {
        "world": int(world),
        "mem_cap_bytes": (int(mem_cap_bytes)
                          if mem_cap_bytes is not None else None),
        "n_candidates": len(rows),
        "chosen": rows[0],
        "candidates": rows,
    }


def llama_layout_model(*, vocab: int, dim: int, n_layers: int,
                       n_heads: int, n_kv_heads: int, ffn_dim: int,
                       batch: int, seq: int,
                       itemsize: float = 4.0) -> Dict[str, Any]:
    """The model descriptor :func:`solve_layout` consumes, built from
    llama config shapes with the module's own exact param count and the
    6·N FLOPs convention — so the solver, the bench MFU and the ledger
    all price the same model."""
    n_params = llama_param_count(vocab, dim, n_layers, n_heads,
                                 n_kv_heads, ffn_dim)
    return {
        "family": "llama",
        "n_params": n_params,
        "dim": dim,
        "n_layers": n_layers,
        "n_heads": n_heads,
        "n_kv_heads": n_kv_heads,
        "batch": batch,
        "seq": seq,
        "itemsize": itemsize,
        "flops_per_step": train_flops_per_token(n_params) * batch * seq,
    }


# ----------------------------------------------- plan-cache comm accounting
def plan_comm_bytes(plan, policy: str, axis_sizes: Dict[str, int],
                    op=None) -> Dict[str, Any]:
    """Per-fusion-bucket comm bytes of one gradient sync under a wire
    policy: the plan cache's bucket plan × the wire-policy format of each
    bucket × the ring model, summed per fabric — the analytical comm leg
    of the predicted step (uses ``ops/wire.py`` as the byte-model source
    of truth; imported lazily, this is the one jax-touching entry point).
    """
    from ..common.reduce_op import ReduceOp
    from ..ops import wire

    op = ReduceOp.AVERAGE if op is None else op
    axis_name = ("dcn.data", "ici.data") if "dcn" in axis_sizes else "data"
    pol = wire.get_policy(policy)
    total = 0.0
    per_fabric: Dict[str, float] = {}
    per_format: Dict[str, float] = {}
    for b in plan.buckets:
        import numpy as np
        fmt = wire.resolve_format(pol(b.nbytes, b.dtype, axis_name),
                                  b.dtype, axis_name, op)
        m = wire.modeled_wire_bytes(sum(b.sizes),
                                    np.dtype(b.dtype).itemsize, fmt,
                                    axis_sizes)
        total += m["bottleneck"]
        per_format[fmt] = per_format.get(fmt, 0.0) + m["bottleneck"]
        for fabric, v in m["per_fabric"].items():
            per_fabric[fabric] = per_fabric.get(fabric, 0.0) + v
    return {"bottleneck": int(total),
            "per_fabric": {k: int(v) for k, v in sorted(per_fabric.items())},
            "per_format": {k: int(v) for k, v in sorted(per_format.items())}}


def compiled_flops(fn, *args, **kwargs) -> Optional[float]:
    """FLOPs of one call of ``fn(*args)`` from XLA's own
    ``cost_analysis()`` where the backend provides it (jit lower ->
    compile -> cost_analysis), None otherwise — callers fall back to the
    6·N analytical model (``train_flops_per_token``), which stays the
    single convention the MFU numbers are defined by."""
    try:
        import jax
        compiled = jax.jit(fn).lower(*args, **kwargs).compile()
        ca = compiled.cost_analysis()
        if not ca:
            return None
        flops = ca.get("flops")
        return float(flops) if flops and flops > 0 else None
    except Exception:
        return None
