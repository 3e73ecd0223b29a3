"""The plain reference: forward pass, mean cross-entropy, gradients and
AdamW of a decoder in straight jax.numpy, float32, matmul precision
"highest", no cache, no kernels.  The embedding, a layer and the head are the
equations of the configuration's family (families/); what is here drives
them.  It imports nothing of the program and takes nothing the program made:
weights come from the seed (lib/weights.py), one layer at a time, so that it
fits beside or before the program's state.

``quant`` swaps every linear layer's matmul for a lower-precision one
(the control of "How correct is decided"); None is the reference itself.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import spec, weights as W

F32 = jnp.float32


# ------------------------------------------------------------ lower precision
def _fake_int8(x):
    """Symmetric int8 with one scale for the tensor, values returned in
    float32; straight-through in the backward pass."""
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def int8_mm(x, w):
    """W8A8, per tensor."""
    return jnp.matmul(_fake_int8(x), _fake_int8(w))


def plain_mm(x, w):
    return jnp.matmul(x, w)


MATMULS = {None: plain_mm, "int8": int8_mm}


def highest(fn):
    """``fn`` under matmul precision "highest", the reference's own."""
    @functools.wraps(fn)
    def wrapped(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return wrapped


# the name tests/test_perfbench_families.py still asks for (no benchmark
# PR's to edit); gone with that caller
_highest = highest


class Weights:
    """Seeded leaves by name, regenerated on demand in float32."""

    def __init__(self, config, seed, put=None):
        self.specs = {n: (i, s, std) for i, (n, s, std) in enumerate(
            spec.family(config).leaf_specs(config))}
        self.key = W.seed_key(seed)
        self.dtype = W.dtype_of(config)
        self.put = put or (lambda x: x)

    def __call__(self, name):
        i, shape, std = self.specs[name]
        # in the type the program holds it in, then exactly upcast
        return self.put(W.leaf_jit(self.key, i, shape, std,
                                   self.dtype).astype(F32))

    def layer(self, i):
        pre = f"layers.{i}."
        return {n[len(pre):]: self(n) for n in self.specs if n.startswith(pre)}

    def part(self, names):
        return {n: self(n) for n in names}


def layer_steps(config, mm):
    """[one jitted ``step(p, x)`` a layer]; layers of one kind share theirs."""
    fam = spec.family(config)
    kinds = fam.layer_kinds(config)
    steps = {kind: jax.jit(highest(functools.partial(
        fam.layer, kind, config=config, mm=mm))) for kind in set(kinds)}
    return [steps[kind] for kind in kinds]


# ------------------------------------------------------------------- serving
def hidden_states(config, w, seqs, quant=None, row_block=4):
    """The last layer's output [R, T, d] for token rows ``seqs`` [R, T],
    layer by layer from the seeded weights ``w``."""
    fam = spec.family(config)
    p = w.part(fam.EMBED)
    xs = [fam.embed(p, jnp.asarray(seqs[i:i + row_block]), config)
          for i in range(0, len(seqs), row_block)]
    for i, step in enumerate(layer_steps(config, MATMULS[quant])):
        p = w.layer(i)
        xs = [step(p, x) for x in xs]
    return jnp.concatenate(xs, 0)


def logits_at(config, seed, seq, positions, quant=None):
    """Reference logits [len(positions), V] of one token row (float32, or
    the control's with ``quant``)."""
    w = Weights(config, seed)
    x = hidden_states(config, w, np.asarray([seq], np.int32), quant)[0]
    fam = spec.family(config)
    f = jax.jit(highest(lambda p, x: fam.head(p, x, config, MATMULS[quant])))
    return f(w.part(fam.HEAD), x[np.asarray(positions)])


def generated_logit_stats(config, seed, seqs, spans, tokens_of, quant=None,
                          row_block=4):
    """Teacher-forced forward over ``seqs`` [R, T] (prompt + served tokens,
    zero-padded at the end).  ``spans[r] = (first, n)``: logits at positions
    first..first+n-1 predict the n served tokens.  For each such position,
    against the float32 reference logits z: ``gap = max(z) - z[token]`` over
    the standard deviation of z, where token is the served token
    (``tokens_of='served'``) or the token the ``quant`` forward puts first
    (``tokens_of='quant'``, the control).  Returns {"gap": [..], "flip":
    [..]} over all positions, in request order."""
    seqs = np.asarray(seqs, np.int32)
    R, T = seqs.shape
    w = Weights(config, seed)
    passes = [None] + ([quant] if tokens_of == "quant" else [])
    hidden = {q: hidden_states(config, w, seqs, q, row_block) for q in passes}
    fam = spec.family(config)
    head = w.part(fam.HEAD)

    @jax.jit
    @highest
    def stats(head, x_ref, x_alt, served):
        z = fam.head(head, x_ref, config, plain_mm)
        if tokens_of == "quant":
            tok = jnp.argmax(fam.head(head, x_alt, config, MATMULS[quant]),
                             -1)
        else:
            tok = served
        top = jnp.max(z, -1)
        mine = jnp.take_along_axis(z, tok[:, None], -1)[:, 0]
        return (top - mine) / jnp.std(z, -1), tok != jnp.argmax(z, -1)

    gaps, flips = [], []
    nmax = max(n for _, n in spans)
    for r, (first, n) in enumerate(spans):
        pos = np.minimum(first + np.arange(nmax), T - 1)
        served = np.zeros(nmax, np.int32)
        served[:n] = seqs[r, first + 1:first + 1 + n]
        g, f = stats(head, hidden[None][r, pos],
                     hidden[passes[-1]][r, pos], jnp.asarray(served))
        gaps.extend(np.asarray(g)[:n].tolist())
        flips.extend(np.asarray(f)[:n].tolist())
    return {"gap": gaps, "flip": flips}


def served_stats_for(config):
    """What gives a configuration's teacher-forced statistics: its family's
    own ``served_stats`` (families/__init__.py) where it brings one,
    ``generated_logit_stats`` itself where it does not."""
    return getattr(spec.family(config), "served_stats", generated_logit_stats)


# ------------------------------------------------------------------ training
def _top(fam, head, x, targets, denom, config, mm):
    z = fam.head(head, x, config, mm)
    lse = jax.nn.logsumexp(z, -1)
    tgt = jnp.take_along_axis(z, targets[..., None], -1)[..., 0]
    return jnp.sum(lse - tgt) / denom


def train_steps(config, seed, batches, opt, devices=None, quant=None,
                rows_per_device=2):
    """AdamW steps on ``batches`` (a list of int arrays [B, S+1], one per
    step) from the seeded weights.  Returns {"loss": [per step],
    "mnorm": [per step {leaf: norm of Adam's first moment}], "dnorm":
    {leaf: norm of the parameters' change after the last step}}.

    Memory: parameters and first moments stay on the device; second
    moments are stashed (on the host with one device, sharded over the
    devices with several); gradients exist one layer at a time; rows go
    through in blocks.  With several devices the rows of a block are
    spread over them and the compiler adds the reduction."""
    devices = devices or jax.devices()[:1]
    n = len(devices)
    mm, fam = MATMULS[quant], spec.family(config)
    if n > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(devices), ("x",))
        rep = NamedSharding(mesh, P())
        rows = NamedSharding(mesh, P("x"))
        put = lambda x: jax.device_put(x, rep)
        put_rows = lambda x: jax.device_put(x, rows)
        stash = lambda x: [jax.device_put(x, rows)]
        fetch = lambda box: jax.device_put(box[0], rep)
    else:
        put = put_rows = lambda x: jax.device_put(x, devices[0])
        pending = []

        def stash(x):
            """To the host, copied while the device goes on; at most two
            leaves wait on the device."""
            x.copy_to_host_async()
            box = [x]
            pending.append(box)
            if len(pending) > 1:
                done = pending.pop(0)
                done[0] = np.asarray(done[0])
            return box
        fetch = lambda box: put(box[0])
    w = Weights(config, seed, put)
    names = list(w.specs)
    params = {k: w(k) for k in names}
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    v2 = {}      # second moments, stashed; absent means zero
    fwds, kinds = layer_steps(config, mm), fam.layer_kinds(config)
    layer_names = [[k for k in names if k.startswith(f"layers.{i}.")]
                   for i in range(len(fwds))]
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["lr"], opt["weight_decay"]

    @functools.partial(jax.jit, static_argnums=(0,))
    @highest
    def bwd(kind, p, x, ct):
        _, pull = jax.vjp(lambda p, x: fam.layer(kind, p, x, config, mm), p, x)
        return pull(ct)

    @functools.partial(jax.jit, static_argnums=(3,))
    @highest
    def top(head, x, targets, denom):
        loss, pull = jax.vjp(
            lambda h, x: _top(fam, h, x, targets, denom, config, mm), head, x)
        return (loss,) + pull(jnp.ones((), F32))

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def adamw(p, g, m, v, t):
        m = b1 * m + (1 - b1) * g
        v = (1 - b2) * g * g + (0.0 if v is None else b2 * v)
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (u + wd * p), m, v, jnp.sqrt(jnp.sum(m * m))

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    embed_grad = jax.jit(lambda p, ids, ct: jax.vjp(
        lambda p: fam.embed(p, ids, config), p)[1](ct)[0])

    def part(names_, prefix=""):
        return {k[len(prefix):]: params[k] for k in names_}

    out = {"loss": [], "mnorm": [], "step_s": []}
    for t, batch in enumerate(batches, start=1):
        t_step = time.perf_counter()
        batch = np.asarray(batch, np.int32)
        B, S = batch.shape[0], batch.shape[1] - 1
        rb = rows_per_device * n
        blocks = [put_rows(batch[i:i + rb]) for i in range(0, B, rb)]
        acts = [[fam.embed(part(fam.EMBED), b[:, :-1], config)]
                for b in blocks]
        for i, fwd in enumerate(fwds):
            p = part(layer_names[i], f"layers.{i}.")
            for a in acts:
                a.append(fwd(p, a[-1]))
        mnorm, tt = {}, jnp.asarray(t, F32)

        def update(k, g):
            v = fetch(v2.pop(k)) if k in v2 else None
            params[k], m[k], v, mnorm[k] = adamw(params[k], g, m[k], v, tt)
            if t < len(batches):
                v2[k] = stash(v)

        loss, g, cts = 0.0, None, []
        for b, a in zip(blocks, acts):
            l, gp, ct = top(part(fam.HEAD), a.pop(), b[:, 1:], B * S)
            loss += float(l)
            g = gp if g is None else add(g, gp)
            cts.append(ct)
        for k in fam.HEAD:
            update(k, g[k])
        for i in reversed(range(len(fwds))):
            pre = f"layers.{i}."
            p = part(layer_names[i], pre)
            g = None
            for j, a in enumerate(acts):
                gp, cts[j] = bwd(kinds[i], p, a.pop(), cts[j])
                g = gp if g is None else add(g, gp)
            for k in g:
                update(pre + k, g[k])
            del p
        g = None
        for b, ct in zip(blocks, cts):
            gp = embed_grad(part(fam.EMBED), b[:, :-1], ct)
            g = gp if g is None else add(g, gp)
        for k in fam.EMBED:
            update(k, g[k])
        del g, cts, acts
        out["loss"].append(loss)
        out["step_s"].append(time.perf_counter() - t_step)
        out["mnorm"].append({k: float(x) for k, x in mnorm.items()})
    dn = jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2)))
    out["dnorm"] = {k: float(dn(params[k], w(k))) for k in names}
    return out
