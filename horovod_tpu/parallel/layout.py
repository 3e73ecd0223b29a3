"""3D parallelism: (dp, tp, pp) composition driven by the layout solver.

ROADMAP item 2 realized (docs/parallelism.md): the mesh factorizes into
``dp x tp x pp`` — data parallelism with the ZeRO bucket chain riding
the ``dp`` axis ONLY, Megatron-style tensor parallelism (fsdp.py's
column/row rules, placed explicitly) over ``tp``, and the GPipe
microbatch schedule (pipeline.py's scan) over ``pp`` — and the cost
model (``perf/costmodel.solve_layout``) picks the factorization:
enumerate valid (dp, tp, pp, zero_level, wire, overlap_depth)
candidates, filter by the per-chip memory cap, rank by predicted step
time.  ``HOROVOD_LAYOUT=auto`` resolves the training mesh at init.

Composition contract (what tests/test_layout.py proves bit-near the
pure-dp reference at every (tp, pp, zero_level, wire) combination):

  * ONE shard_map over the full (dp, tp, pp) mesh.  Inside the body the
    forward places its own collectives — ``lax.psum`` over ``tp`` after
    the row-parallel matmuls, the ppermute scan over ``pp`` — and the
    ZeRO chain's psum_scatter/all_gather legs run over ``dp`` only, so
    per-bucket wire formats and EF residuals thread through UNCHANGED
    (each (tp, pp) coordinate owns its own dp subgroup of shards).
  * Megatron's conjugate f/g operators are explicit ``custom_vjp``
    pairs: ``g`` = psum forward / identity backward (after wo and
    w_down), ``f`` = identity forward / psum backward (at the
    column-parallel block inputs).  With them, every rank's activation
    cotangents are the TRUE cotangents, tp-sharded weight gradients are
    exact slices, and tp-replicated leaves (norms, lm_head) get
    identical true gradients on every rank — no per-leaf rescaling.
  * The ONE gradient fixup: the embedding's gradient is produced only by
    the pipeline's stage-0 ranks (the GPipe schedule feeds tokens in at
    stage 0), so it is psum'd over ``pp`` before entering the chain.
  * ZeRO state geometry: per-bucket arrays of GLOBAL shape
    ``[world, bucket/dp, ...]`` with dim 0 sharded
    ``P(("dp", "tp", "pp"))`` — each rank holds one row (ITS shard of
    ITS (tp, pp) coordinate's parameter slice); bucket plans derive from
    the LOCAL (tp/pp-sliced) leaf shapes, identical on every rank.

Wire caveat (docs/parallelism.md#cpu-virtual): lossy wire formats
quantize per bucket, and bucket geometry differs between layouts, so
cross-layout comparisons under lossy wires are proven via within-layout
level equivalence plus a loose envelope against the reference — the
exact-wire matrix is the bitwise proof.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.reduce_op import ReduceOp, Average
from ..perf import costmodel as _cm
from . import zero as _zero
from .pipeline import _spmd_pipeline, stack_stage_params

LAYOUT_AXES = ("dp", "tp", "pp")
# ZeRO state dim 0 is dp-major over the FULL mesh: row (i*tp + j)*pp + k
# belongs to rank (dp=i, tp=j, pp=k) — shard_map's P(tuple) ordering.
STATE_SPEC = P(LAYOUT_AXES)
LAYOUT_VALUES = ("", "auto", "dp-only")


# ------------------------------------------------------------ knob surface
def _parse_explicit(value: str) -> Optional[Tuple[int, int, int]]:
    parts = [p.strip() for p in value.split(",")]
    if len(parts) != 3 or not all(p.isdigit() for p in parts):
        return None
    return tuple(int(p) for p in parts)  # type: ignore[return-value]


def validate_layout_knobs(knobs, world: Optional[int] = None,
                          mesh_spec: str = "") -> None:
    """Fail loudly AT INIT on invalid layout knob values (consumed by
    hvd.init BEFORE mesh construction — the layout controls the mesh,
    docs/parallelism.md#knobs)."""
    value = str(knobs["HOROVOD_LAYOUT"]).strip()
    tp = int(knobs["HOROVOD_TP"])
    pp = int(knobs["HOROVOD_PP"])
    if tp < 0 or pp < 0:
        raise ValueError(
            f"HOROVOD_TP={tp} / HOROVOD_PP={pp} invalid; the parallel "
            "degrees must be >= 0 (0 = let the solver pick; "
            "docs/parallelism.md)")
    explicit = _parse_explicit(value) if value else None
    if value and value not in LAYOUT_VALUES and explicit is None:
        raise ValueError(
            f"HOROVOD_LAYOUT={value!r} invalid; use 'auto', 'dp-only' or "
            "an explicit 'dp,tp,pp' triple (docs/parallelism.md)")
    if value and mesh_spec:
        raise ValueError(
            f"HOROVOD_LAYOUT={value!r} and an explicit mesh spec "
            f"({mesh_spec!r}) both claim the mesh; set one "
            "(docs/parallelism.md#knobs)")
    if not value and (tp > 1 or pp > 1):
        raise ValueError(
            f"HOROVOD_TP={tp} / HOROVOD_PP={pp} have no effect without "
            "HOROVOD_LAYOUT (set HOROVOD_LAYOUT=auto to constrain the "
            "solver, or an explicit 'dp,tp,pp'; docs/parallelism.md)")
    if value == "dp-only" and (tp > 1 or pp > 1):
        raise ValueError(
            f"HOROVOD_LAYOUT=dp-only conflicts with HOROVOD_TP={tp} / "
            f"HOROVOD_PP={pp} (docs/parallelism.md)")
    if explicit is not None:
        d, t, p = explicit
        if min(explicit) < 1:
            raise ValueError(
                f"HOROVOD_LAYOUT={value!r} invalid; every factor of the "
                "'dp,tp,pp' triple must be >= 1 (docs/parallelism.md)")
        if tp > 1 and tp != t:
            raise ValueError(
                f"HOROVOD_TP={tp} contradicts HOROVOD_LAYOUT={value!r}")
        if pp > 1 and pp != p:
            raise ValueError(
                f"HOROVOD_PP={pp} contradicts HOROVOD_LAYOUT={value!r}")
        if world is not None and d * t * p != world:
            raise ValueError(
                f"HOROVOD_LAYOUT={value!r} covers {d * t * p} chips but "
                f"{world} are visible (dp*tp*pp must equal the world "
                "size; docs/parallelism.md)")
    if world is not None:
        for name, deg in (("HOROVOD_TP", tp), ("HOROVOD_PP", pp)):
            if deg > 1 and world % deg:
                raise ValueError(
                    f"{name}={deg} does not divide the world size "
                    f"{world} (docs/parallelism.md#constraints)")
        if tp > 1 and pp > 1 and world % (tp * pp):
            raise ValueError(
                f"HOROVOD_TP={tp} x HOROVOD_PP={pp} does not divide the "
                f"world size {world} (docs/parallelism.md#constraints)")


def resolve_layout(world: int, knobs=None, *,
                   model: Optional[Dict[str, Any]] = None,
                   mem_cap_bytes: Optional[float] = None
                   ) -> Optional[Tuple[int, int, int]]:
    """The (dp, tp, pp) triple HOROVOD_LAYOUT resolves to at ``world``
    chips, or None when the knob is unset (legacy 1-D mesh).

    ``auto`` runs :func:`perf.costmodel.solve_layout` — against
    ``model`` when the caller knows it (bench, the integration workers),
    else against a permissive topology-only descriptor, where every
    factorization is admissible and the zero-FLOP tie-break prefers pure
    dp — constrained to HOROVOD_TP / HOROVOD_PP when set.  Sets the
    hvd_layout_* gauges with the decision."""
    if knobs is None:
        from ..common.knobs import current
        value = str(current("HOROVOD_LAYOUT")).strip()
        tp_knob = int(current("HOROVOD_TP"))
        pp_knob = int(current("HOROVOD_PP"))
        level = int(current("HOROVOD_ZERO_LEVEL"))
    else:
        value = str(knobs["HOROVOD_LAYOUT"]).strip()
        tp_knob = int(knobs["HOROVOD_TP"])
        pp_knob = int(knobs["HOROVOD_PP"])
        level = int(knobs["HOROVOD_ZERO_LEVEL"])
    if not value:
        return None
    if value == "dp-only":
        return (world, 1, 1)
    explicit = _parse_explicit(value)
    if explicit is not None:
        if int(np.prod(explicit)) != world:
            raise ValueError(
                f"HOROVOD_LAYOUT={value!r} covers "
                f"{int(np.prod(explicit))} chips but {world} are visible")
        return explicit
    if model is None:
        # Topology-only: nothing to price, every factorization valid.
        model = {"n_params": 0, "n_heads": world, "n_kv_heads": world,
                 "n_layers": world, "batch": world, "dim": 0, "seq": 1,
                 "flops_per_step": 0.0}
    sol = _cm.solve_layout(model, world,
                           mem_cap_bytes=mem_cap_bytes,
                           levels=(level,) if level in (1, 2, 3) else (1,))
    chosen = None
    for row in sol["candidates"]:
        lay = row["layout"]
        if tp_knob > 1 and lay["tp"] != tp_knob:
            continue
        if pp_knob > 1 and lay["pp"] != pp_knob:
            continue
        chosen = row
        break
    if chosen is None:
        raise ValueError(
            f"HOROVOD_LAYOUT=auto found no valid layout at world={world} "
            f"under HOROVOD_TP={tp_knob} / HOROVOD_PP={pp_knob} "
            "(docs/parallelism.md#constraints)")
    from ..utils import metrics as M
    M.LAYOUT_CANDIDATES.set(sol["n_candidates"])
    M.LAYOUT_CHOSEN_RANK.set(chosen["rank"])
    M.LAYOUT_PREDICTED_STEP.set(chosen["step_s"])
    lay = chosen["layout"]
    return (lay["dp"], lay["tp"], lay["pp"])


def layout_mesh_spec(dp: int, tp: int, pp: int) -> str:
    """The runtime mesh spec string of a resolved layout — axis names
    are the composition contract: zero legs ride 'dp', the f/g psums
    ride 'tp', the GPipe ppermute rides 'pp'."""
    return f"dp={dp},tp={tp},pp={pp}"


def layout_of_mesh(mesh: Mesh) -> Tuple[int, int, int]:
    """(dp, tp, pp) sizes of a layout mesh; raises on a non-layout mesh
    (the legacy 1-D 'hvd' mesh has no dp/tp/pp axes)."""
    missing = [a for a in LAYOUT_AXES if a not in mesh.shape]
    if missing:
        raise ValueError(
            f"mesh axes {tuple(mesh.axis_names)} are missing {missing}; "
            "layout train steps need the (dp, tp, pp) mesh that "
            "HOROVOD_LAYOUT resolves at init (docs/parallelism.md)")
    return tuple(int(mesh.shape[a]) for a in LAYOUT_AXES)  # type: ignore


# ------------------------------------------- Megatron conjugate operators
@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _g_psum(x, axis):
    """Megatron's ``g``: psum forward (completes a row-parallel matmul),
    identity backward (every rank already holds the true cotangent of
    the summed output)."""
    return lax.psum(x, axis)


def _g_psum_fwd(x, axis):
    return lax.psum(x, axis), None


def _g_psum_bwd(axis, _, ct):
    return (ct,)


_g_psum.defvjp(_g_psum_fwd, _g_psum_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _f_copy(x, axis):
    """Megatron's ``f``: identity forward (the input is replicated over
    tp), psum backward (each rank's cotangent is the contribution
    through ITS weight slice; the sum is the true cotangent)."""
    return x


def _f_copy_fwd(x, axis):
    return x, None


def _f_copy_bwd(axis, _, ct):
    return (lax.psum(ct, axis),)


_f_copy.defvjp(_f_copy_fwd, _f_copy_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _scale_grad(x, s):
    """Identity forward, cotangent scaled by ``s`` backward — pairs with
    the plain psum that collects the pipeline's last-stage outputs
    (every pp rank computes the loss redundantly with seed 1, so the
    psum transpose would multiply cotangents by pp; 1/pp restores the
    true value)."""
    return x


def _scale_grad_fwd(x, s):
    return x, None


def _scale_grad_bwd(s, _, ct):
    return (jax.tree_util.tree_map(lambda c: c * s, ct),)


_scale_grad.defvjp(_scale_grad_fwd, _scale_grad_bwd)


# ------------------------------------------------- llama family realization
def llama_layout_params(params: Dict[str, Any], pp: int) -> Dict[str, Any]:
    """Restack a ``models/llama.init`` pytree into the layout form:
    ``{"embed", "final_norm", "lm_head", "stages"}`` with every stage
    leaf stacked ``[pp, n_layers/pp, ...]`` (pipeline.py's restack
    shape).  TP slicing is NOT applied here — shard_map's in_specs slice
    the stacked arrays at trace time."""
    layers = params["layers"]
    n_layers = len(layers)
    if n_layers % pp:
        raise ValueError(f"n_layers={n_layers} not divisible by pp={pp} "
                         "(docs/parallelism.md#constraints)")
    per = n_layers // pp
    groups = [stack_stage_params(layers[s * per:(s + 1) * per])
              for s in range(pp)]
    return {
        "embed": params["embed"],
        "final_norm": params["final_norm"],
        "lm_head": params["lm_head"],
        "stages": stack_stage_params(groups),
    }


def llama_layout_specs(stacked: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpecs of the stacked llama tree on the (dp, tp, pp)
    mesh — fsdp.py's Megatron rules with the stage stacking in front:
    column-parallel wq/wk/wv/w_gate/w_up (out dim over tp), row-parallel
    wo/w_down (in dim over tp), stage dim 0 over pp; norms replicate
    within a stage; embed/final_norm/lm_head replicate (they run outside
    the pipelined region on every rank)."""
    col = {"wq", "wk", "wv", "w_gate", "w_up"}
    row = {"wo", "w_down"}

    def stage_spec(name: str, leaf_name: str) -> P:
        if name in col and leaf_name == "kernel":
            return P("pp", None, None, "tp")
        if name in row and leaf_name == "kernel":
            return P("pp", None, "tp", None)
        return P("pp")

    stages = {name: {leaf: stage_spec(name, leaf) for leaf in sub}
              for name, sub in stacked["stages"].items()}
    return {
        "embed": jax.tree_util.tree_map(lambda _: P(), stacked["embed"]),
        "final_norm": jax.tree_util.tree_map(lambda _: P(),
                                             stacked["final_norm"]),
        "lm_head": jax.tree_util.tree_map(lambda _: P(),
                                          stacked["lm_head"]),
        "stages": stages,
    }


def llama_layout_template(cfg, pp: int):
    """Abstract (ShapeDtypeStruct) stacked llama tree — the bucket-plan /
    expected-state source when real params are not at hand."""
    from ..models import llama as Ll
    return jax.eval_shape(
        lambda: llama_layout_params(Ll.init(jax.random.PRNGKey(0), cfg),
                                    pp))


def _local_template(template: Any, specs: Any, mesh: Mesh) -> Any:
    """Per-rank (shard_map-local) shapes of ``template`` under ``specs``:
    each sharded dim divides by its mesh axis size.  This is what bucket
    plans and the level-3 unpack see inside the body."""
    def one(leaf, spec):
        shape = list(leaf.shape)
        for d, axes in enumerate(spec):
            if axes is None:
                continue
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                size = int(mesh.shape[a])
                if shape[d] % size:
                    raise ValueError(
                        f"dim {d} of shape {tuple(leaf.shape)} not "
                        f"divisible by mesh axis {a}={size} "
                        "(docs/parallelism.md#constraints)")
                shape[d] //= size
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    return jax.tree_util.tree_map(
        one, template,
        _broadcast_specs(specs, template))


def _broadcast_specs(specs: Any, tree: Any) -> Any:
    """Expand a spec pytree PREFIX (e.g. one P() for a whole subtree) to
    a full per-leaf spec tree matching ``tree``."""
    def expand(spec, sub):
        return jax.tree_util.tree_map(lambda _: spec, sub)
    return jax.tree_util.tree_map(
        expand, specs, tree,
        is_leaf=lambda x: isinstance(x, P))


def _tp_attn(p, x, cfg, cos, sin, tp: int):
    B, S, _ = x.shape
    nh, nkv = cfg.n_heads // tp, cfg.n_kv_heads // tp
    from ..models import layers as L
    q = L.dense(p["wq"], x).reshape(B, S, nh, cfg.head_dim)
    k = L.dense(p["wk"], x).reshape(B, S, nkv, cfg.head_dim)
    v = L.dense(p["wv"], x).reshape(B, S, nkv, cfg.head_dim)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    o = L.causal_attention(q, k, v, causal=True)
    o = L.dense(p["wo"], o.reshape(B, S, nh * cfg.head_dim))
    return _g_psum(o, "tp") if tp > 1 else o


def _tp_ffn(p, x, cfg, tp: int):
    from ..models import layers as L
    h = L.dense(p["w_down"],
                jax.nn.silu(L.dense(p["w_gate"], x)) *
                L.dense(p["w_up"], x))
    return _g_psum(h, "tp") if tp > 1 else h


def _tp_apply_layer(p, x, cfg, cos, sin, tp: int):
    """models/llama.apply_layer with the local head/ffn slice and the
    f/g conjugate pair around each parallel block.  At tp == 1 this is
    op-for-op the reference layer (the bit-near anchor)."""
    from ..models import layers as L
    a_in = L.rmsnorm(p["attn_norm"], x)
    if tp > 1:
        a_in = _f_copy(a_in, "tp")
    x = x + _tp_attn(p, a_in, cfg, cos, sin, tp)
    f_in = L.rmsnorm(p["ffn_norm"], x)
    if tp > 1:
        f_in = _f_copy(f_in, "tp")
    return x + _tp_ffn(p, f_in, cfg, tp)


def _llama_local_loss(cfg, tp: int, pp: int, n_micro: int) -> Callable:
    """The per-rank loss the composed chain differentiates: embed on
    every rank, the layer stack through TP blocks (and the GPipe scan
    when pp > 1), final norm + lm_head + mean CE on the collected hidden
    — every rank computes the identical loss value."""
    from ..models import layers as L

    def local_loss(params_local, ids):
        inputs, targets = ids[:, :-1], ids[:, 1:]
        cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
        x = L.embedding(params_local["embed"], inputs).astype(cfg.dtype)

        def stage_fn(sp, h):
            def blk(carry, lp):
                return _tp_apply_layer(lp, carry, cfg, cos, sin, tp), None
            out, _ = lax.scan(blk, h, sp)
            return out

        if pp > 1:
            B = x.shape[0]
            m = _cm._effective_microbatches(B, n_micro)
            xm = x.reshape((m, B // m) + x.shape[1:])
            h = _spmd_pipeline(stage_fn, params_local["stages"], xm, m,
                               "pp")
            h = _scale_grad(h, 1.0 / pp)
            x = h.reshape((B,) + h.shape[2:])
        else:
            stages = jax.tree_util.tree_map(lambda a: a[0],
                                            params_local["stages"])
            x = stage_fn(stages, x)
        x = L.rmsnorm(params_local["final_norm"], x)
        logits = L.dense(params_local["lm_head"], x)
        return jnp.mean(L.softmax_cross_entropy(logits, targets))

    return local_loss


def _llama_grad_fixup(pp: int) -> Callable:
    """The one per-leaf correction the f/g pairing leaves: only the
    pipeline's stage-0 ranks produce the embedding gradient (the where
    mask routes token input cotangents there), so psum it over pp —
    every other leaf's per-rank gradient is already the true gradient of
    its local slice (module docstring derivation)."""
    def fixup(grads):
        if pp > 1:
            grads = dict(grads)
            grads["embed"] = jax.tree_util.tree_map(
                lambda g: lax.psum(g, "pp"), grads["embed"])
        return grads
    return fixup


# ----------------------------------------------------- sharded state plumbing
def _expected_layout_state(optimizer, plan, dp: int, world: int, ef: bool):
    """Abstract GLOBAL state pytree of the composed chain: per bucket
    the vmapped inner state over ``[world, bucket/dp]`` rows (one row
    per rank, dim 0 dp-major over the full mesh) plus the EF residual
    ``[world, bucket]`` when a lossy wire format is error-compensated."""
    blocks = []
    for b in plan.buckets:
        Lb = _zero._padded_len(sum(b.sizes), dp)
        inner = jax.eval_shape(
            jax.vmap(optimizer.init),
            jax.ShapeDtypeStruct((world, Lb // dp), jnp.float32))
        if ef:
            blocks.append(_zero._ZeroEFBlock(
                inner=inner,
                residual=jax.ShapeDtypeStruct((world, Lb), jnp.float32)))
        else:
            blocks.append(inner)
    return tuple(blocks)


def init_layout_state(optimizer: optax.GradientTransformation,
                      params: Any, specs: Any, mesh: Mesh,
                      zero_level: Optional[int] = None,
                      wire_policy=None,
                      error_feedback: Optional[bool] = None,
                      fusion_threshold_bytes: Any = None) -> Any:
    """ZeRO state for the composed chain: each rank materializes the
    optimizer state of ITS dp-shard of ITS (tp, pp) parameter slice —
    per-bucket global arrays ``[world, bucket/dp, ...]`` sharded
    ``P(("dp", "tp", "pp"))`` on dim 0.  At tp == pp == 1 this is
    exactly ``zero.init_zero_state``'s geometry with axis 'dp'."""
    level = _zero.resolve_zero_level(zero_level)
    if level == 0:
        raise ValueError(
            "zero_level=0 is plain data parallelism — init the inner "
            "optimizer directly (docs/zero.md)")
    dp, tp, pp = layout_of_mesh(mesh)
    local = _local_template(params, specs, mesh)
    plan = _zero._bucket_plan(local, fusion_threshold_bytes)
    formats = _zero._zero_formats(
        plan, _zero._resolve_wire_policy(wire_policy), "dp", dp)
    from ..ops.wire import is_lossy
    ef = _zero._resolve_ef(error_feedback) and any(
        is_lossy(f) for f in formats)

    def body(params_local):
        leaves = _zero._f32_leaves(params_local)
        my = lax.axis_index("dp")
        out = []
        for b in plan.buckets:
            flat = _zero._pack_padded(leaves, b, dp)
            shard_len = flat.shape[0] // dp
            shard = lax.dynamic_slice_in_dim(flat, my * shard_len,
                                             shard_len)
            inner = jax.tree_util.tree_map(lambda x: x[None],
                                           optimizer.init(shard))
            if ef:
                out.append(_zero._ZeroEFBlock(
                    inner=inner,
                    residual=jnp.zeros((1, flat.shape[0]), jnp.float32)))
            else:
                out.append(inner)
        return tuple(out)

    world = dp * tp * pp
    expected = _expected_layout_state(optimizer, plan, dp, world, ef)
    out_specs = jax.tree_util.tree_map(lambda _: STATE_SPEC, expected)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(specs,),
                             out_specs=out_specs,
                             check_vma=False))(params)


def shard_layout_params(params: Any, specs: Any, mesh: Mesh,
                        fusion_threshold_bytes: Any = None) -> Any:
    """Level-3 resident layout of the composed chain: per bucket a
    ``[world, bucket/dp]`` fp32 array (dim 0 over ("dp","tp","pp")) —
    each rank keeps 1/dp of ITS (tp, pp) slice of every bucket."""
    dp, tp, pp = layout_of_mesh(mesh)
    local = _local_template(params, specs, mesh)
    plan = _zero._bucket_plan(local, fusion_threshold_bytes)

    def body(params_local):
        leaves = _zero._f32_leaves(params_local)
        my = lax.axis_index("dp")
        out = []
        for b in plan.buckets:
            flat = _zero._pack_padded(leaves, b, dp)
            shard_len = flat.shape[0] // dp
            out.append(lax.dynamic_slice_in_dim(
                flat, my * shard_len, shard_len)[None])
        return tuple(out)

    nb = plan.num_buckets
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(specs,),
                             out_specs=(STATE_SPEC,) * nb,
                             check_vma=False))(params)


def gather_layout_params(pshards: Any, params_template: Any, specs: Any,
                         mesh: Mesh,
                         fusion_threshold_bytes: Any = None) -> Any:
    """Reassemble the full stacked param tree from composed level-3
    shards (eval / checkpointing / the bit-near proofs): all_gather over
    dp inside each (tp, pp) coordinate, unpack to the local leaves, and
    let the out specs stitch the tp/pp dims back together."""
    from ..ops.fusion import unpack_bucket
    dp, tp, pp = layout_of_mesh(mesh)
    local = _local_template(params_template, specs, mesh)
    plan = _zero._bucket_plan(local, fusion_threshold_bytes)
    tleaves, treedef = jax.tree_util.tree_flatten(local)

    def body(pshards):
        out: List[Optional[jnp.ndarray]] = [None] * plan.num_leaves
        for bi, b in enumerate(plan.buckets):
            full = lax.all_gather(pshards[bi][0], "dp", axis=0,
                                  tiled=True)
            unpack_bucket(full[:sum(b.sizes)], b, out)
        return jax.tree_util.tree_unflatten(
            treedef, [l.astype(t.dtype) for l, t in zip(out, tleaves)])

    nb = plan.num_buckets
    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=((STATE_SPEC,) * nb,),
                             out_specs=specs,
                             check_vma=False))(pshards)


# ------------------------------------------------------------- step builders
def make_layout_train_step(loss_fn: Callable,
                           optimizer: optax.GradientTransformation,
                           mesh: Mesh,
                           op: ReduceOp = Average,
                           donate=None,
                           zero_level: Optional[int] = None,
                           wire_policy=None,
                           error_feedback: Optional[bool] = None,
                           backward_passes_per_step: int = 1,
                           ag_prefetch: Optional[int] = None,
                           fusion_threshold_bytes: Any = None,
                           params_template: Any = None) -> Callable:
    """Composed train step for a GENERIC (replicated-params) loss on the
    layout mesh: the ZeRO chain runs over ``dp`` inside each (tp, pp)
    coordinate; params replicate over tp/pp, so every coordinate's
    subgroup computes the identical update (the quadratic-toy path the
    2-proc integration test drives).  Model-sliced TP/PP needs the
    family builder (:func:`make_llama_layout_train_step`)."""
    specs = P()
    return _make_composed_step(
        loss_fn, optimizer, mesh, op=op, donate=donate,
        zero_level=zero_level, wire_policy=wire_policy,
        error_feedback=error_feedback,
        backward_passes_per_step=backward_passes_per_step,
        ag_prefetch=ag_prefetch,
        fusion_threshold_bytes=fusion_threshold_bytes,
        params_template=params_template, param_specs=specs,
        fixup=lambda g: g)


def make_llama_layout_train_step(cfg,
                                 optimizer: optax.GradientTransformation,
                                 mesh: Mesh,
                                 n_micro: int = 4,
                                 op: ReduceOp = Average,
                                 donate=None,
                                 zero_level: Optional[int] = None,
                                 wire_policy=None,
                                 error_feedback: Optional[bool] = None,
                                 backward_passes_per_step: int = 1,
                                 ag_prefetch: Optional[int] = None,
                                 fusion_threshold_bytes: Any = None
                                 ) -> Callable:
    """The llama-family composed step: Megatron TP over ``tp``, GPipe
    over ``pp``, the ZeRO chain over ``dp`` — takes the STACKED params
    (:func:`llama_layout_params`) at levels 1/2 or the composed level-3
    shards (:func:`shard_layout_params`), state from
    :func:`init_layout_state` built with :func:`llama_layout_specs`.
    Batch leaves are token ids ``[B, seq+1]`` (``[k, B, seq+1]`` with
    ``backward_passes_per_step = k > 1``), rows sharded over dp only."""
    dp, tp, pp = layout_of_mesh(mesh)
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads} AND "
            f"n_kv_heads={cfg.n_kv_heads} (contiguous GQA head slices; "
            "docs/parallelism.md#constraints)")
    if cfg.n_layers % pp:
        raise ValueError(
            f"pp={pp} must divide n_layers={cfg.n_layers} "
            "(docs/parallelism.md#constraints)")
    template = llama_layout_template(cfg, pp)
    specs = llama_layout_specs(template)
    return _make_composed_step(
        _llama_local_loss(cfg, tp, pp, n_micro), optimizer, mesh, op=op,
        donate=donate, zero_level=zero_level, wire_policy=wire_policy,
        error_feedback=error_feedback,
        backward_passes_per_step=backward_passes_per_step,
        ag_prefetch=ag_prefetch,
        fusion_threshold_bytes=fusion_threshold_bytes,
        params_template=template, param_specs=specs,
        fixup=_llama_grad_fixup(pp))


def _make_composed_step(local_loss: Callable,
                        optimizer: optax.GradientTransformation,
                        mesh: Mesh, *, op: ReduceOp, donate,
                        zero_level: Optional[int], wire_policy,
                        error_feedback: Optional[bool],
                        backward_passes_per_step: int,
                        ag_prefetch: Optional[int],
                        fusion_threshold_bytes: Any,
                        params_template: Any, param_specs: Any,
                        fixup: Callable) -> Callable:
    """zero.py's bucket-interleaved chain re-seated on the (dp, tp, pp)
    mesh: ``local_loss`` runs per rank (its own collectives over tp/pp
    inside), ``fixup`` applies the family's gradient correction, and the
    RS/AG legs + wire formats + EF run over ``dp`` exactly as in
    ``_make_bucketed_step`` — n of every chain formula is dp."""
    from ..ops import wire as _wire
    from ..ops.fusion import unpack_bucket
    from ..ops.overlap import priority_order
    from .data_parallel import _resolve_donate

    level = _zero.resolve_zero_level(zero_level)
    if level == 0:
        raise ValueError(
            "zero_level=0 is plain data parallelism — the composed "
            "chain shards the weight update over dp (use level 1-3; "
            "docs/parallelism.md)")
    if op != Average:
        raise ValueError("the composed chain reduces with Average "
                         "(gradient mean); prescale for other semantics")
    dp, tp, pp = layout_of_mesh(mesh)
    world = dp * tp * pp
    donate = _resolve_donate(donate)
    k = int(backward_passes_per_step)
    if k < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    if level == 3 and params_template is None:
        raise ValueError(
            "zero_level=3 keeps params sharded between steps; the "
            "composed step builder needs params_template "
            "(docs/parallelism.md)")

    policy = _zero._resolve_wire_policy(wire_policy)
    ef_requested = _zero._resolve_ef(error_feedback)

    local_cache: dict = {}

    def local_plan(params_local=None):
        lt = local_cache.get("template")
        if lt is None:
            src = params_template if params_template is not None \
                else params_local
            lt = local_cache["template"] = _local_template(
                src, param_specs, mesh)
        return _zero._bucket_plan(lt, fusion_threshold_bytes), lt

    def body(params_in, opt_state, batch):
        plan, ltemplate = local_plan(params_in if level < 3 else None)
        tleaves, treedef = jax.tree_util.tree_flatten(ltemplate)
        order = priority_order(plan)
        nb = plan.num_buckets
        formats = _zero._zero_formats(plan, policy, "dp", dp)
        ef = ef_requested and any(_wire.is_lossy(f) for f in formats)
        depth = (_zero.resolve_ag_prefetch(ag_prefetch)
                 if level == 3 else 0)
        pbytes = sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                     for l in tleaves)
        _zero._record_zero_trace(plan, order, formats, level, dp, k,
                                 depth, ef, opt_state, pbytes)
        my = lax.axis_index("dp")

        if level == 3:
            def ag(bi):
                return lax.all_gather(params_in[bi][0], "dp", axis=0,
                                      tiled=True)
            gathered = {j: ag(j) for j in range(min(depth, nb))}
            full: List[Optional[jnp.ndarray]] = [None] * plan.num_leaves
            for j in range(nb):
                if j + depth < nb:
                    gathered[j + depth] = ag(j + depth)
                b = plan.buckets[j]
                unpack_bucket(gathered.pop(j)[:sum(b.sizes)], b, full)
            params = jax.tree_util.tree_unflatten(
                treedef, [l.astype(t.dtype)
                          for l, t in zip(full, tleaves)])
            pleaves_raw = None
        else:
            params = params_in
            pleaves_raw, ptreedef = jax.tree_util.tree_flatten(params)
            pleaves_f32 = [l.astype(jnp.float32) for l in pleaves_raw]

        inner_states = [opt_state[bi].inner if ef else opt_state[bi]
                        for bi in range(nb)]
        res = ([opt_state[bi].residual[0] for bi in range(nb)]
               if ef else None)

        mbs = ([batch] if k == 1 else
               [jax.tree_util.tree_map(lambda x, _i=i: x[_i], batch)
                for i in range(k)])
        acc: List[Optional[jnp.ndarray]] = [None] * nb
        losses = []
        for mb in mbs:
            loss, grads = jax.value_and_grad(local_loss)(params, mb)
            losses.append(lax.pmean(loss, "dp"))
            grads = fixup(grads)
            gleaves = [l.astype(jnp.float32)
                       for l in jax.tree_util.tree_leaves(grads)]
            for bi in order:
                b = plan.buckets[bi]
                flat = _zero._pack_padded(gleaves, b, dp)
                if ef:
                    flat = flat + res[bi]
                enc = _wire.wire_roundtrip(flat, formats[bi])
                if ef and _wire.is_lossy(formats[bi]):
                    res[bi] = flat - enc
                shard_len = flat.shape[0] // dp
                gshard = lax.psum_scatter(
                    enc.reshape(dp, shard_len), "dp",
                    scatter_dimension=0, tiled=True)
                gshard = gshard.reshape(shard_len) / dp
                if level == 1 and k > 1:
                    contrib = lax.all_gather(gshard, "dp", axis=0,
                                             tiled=True)
                else:
                    contrib = gshard
                acc[bi] = (contrib if acc[bi] is None
                           else acc[bi] + contrib)

        new_blocks: List[Any] = [None] * nb
        ufulls: List[Optional[jnp.ndarray]] = [None] * nb
        new_pshards: List[Optional[jnp.ndarray]] = [None] * nb
        for bi in order:
            b = plan.buckets[bi]
            if level == 1 and k > 1:
                shard_len = acc[bi].shape[0] // dp
                gshard = lax.dynamic_slice_in_dim(
                    acc[bi], my * shard_len, shard_len) / k
            else:
                shard_len = acc[bi].shape[0]
                gshard = acc[bi] / k
            if level == 3:
                pshard = params_in[bi][0]
            else:
                pflat = _zero._pack_padded(pleaves_f32, b, dp)
                pshard = lax.dynamic_slice_in_dim(
                    pflat, my * shard_len, shard_len)
            state_local = jax.tree_util.tree_map(lambda x: x[0],
                                                 inner_states[bi])
            updates, state_local = optimizer.update(gshard, state_local,
                                                    pshard)
            inner_new = jax.tree_util.tree_map(lambda x: x[None],
                                               state_local)
            new_blocks[bi] = (_zero._ZeroEFBlock(inner=inner_new,
                                                 residual=res[bi][None])
                              if ef else inner_new)
            if level == 3:
                new_pshards[bi] = (pshard + updates)[None]
            else:
                ufulls[bi] = lax.all_gather(updates, "dp", axis=0,
                                            tiled=True)

        loss = jnp.mean(jnp.stack(losses))
        if level == 3:
            return tuple(new_pshards), tuple(new_blocks), loss
        out: List[Optional[jnp.ndarray]] = [None] * plan.num_leaves
        for bi, b in enumerate(plan.buckets):
            unpack_bucket(ufulls[bi][:sum(b.sizes)], b, out)
        updates_tree = jax.tree_util.tree_unflatten(
            ptreedef, [u.astype(l.dtype)
                       for u, l in zip(out, pleaves_raw)])
        params = optax.apply_updates(params_in, updates_tree)
        return params, tuple(new_blocks), loss

    batch_spec = P("dp") if k == 1 else P(None, "dp")
    param_spec = STATE_SPEC if level == 3 else param_specs
    jitted = jax.jit(
        shard_map(body, mesh=mesh,
                  in_specs=(param_spec, STATE_SPEC, batch_spec),
                  out_specs=(param_spec, STATE_SPEC, P()),
                  check_vma=False),
        donate_argnums=(0, 1) if donate else ())

    expected_cache: dict = {}

    def step(params, opt_state, batch):
        exp = expected_cache.get("state")
        if exp is None:
            plan, _ = local_plan(params if level < 3 else None)
            formats = _zero._zero_formats(plan, policy, "dp", dp)
            ef = ef_requested and any(_wire.is_lossy(f) for f in formats)
            exp = expected_cache["state"] = _expected_layout_state(
                optimizer, plan, dp, world, ef)
        _zero._check_state_layout(opt_state, exp,
                                  f"composed level-{level} layout")
        return jitted(params, opt_state, batch)

    return step


__all__ = [
    "LAYOUT_AXES", "STATE_SPEC", "LAYOUT_VALUES",
    "validate_layout_knobs", "resolve_layout", "layout_mesh_spec",
    "layout_of_mesh",
    "llama_layout_params", "llama_layout_specs", "llama_layout_template",
    "init_layout_state", "shard_layout_params", "gather_layout_params",
    "make_layout_train_step", "make_llama_layout_train_step",
]
