"""Seeded weights, as the benchmark makes them: for the program the whole
tree in one jitted call in the serving/training type, for the reference one
leaf at a time.  Both read ``leaf``, so the same seed gives the same values.
Which leaves a model has is its family's ``leaf_specs`` (families/)."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

from . import spec


def dtype_of(config):
    """The type the configuration states (its ``torch_dtype``)."""
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config.get("torch_dtype", "bfloat16")]


def seed_key(seed):
    """``--seed`` may exceed 31 bits; fold the high part in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf(key, index, shape, std, dtype):
    """One rounding, at the end: jit and eager give the same bits."""
    if std is None:
        return jnp.ones(shape, dtype)
    x = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    return (x * std).astype(dtype)


# one program per (shape, std, dtype); the leaf's index is an argument
leaf_jit = jax.jit(leaf, static_argnums=(2, 3, 4))


def draw(specs, key, dtype):
    """{name: leaf} for ``specs`` [(name, shape, std)], leaf ``i`` with the
    bits of ``leaf(key, i, ...)``.  Leaves whose names differ only in a
    number (a layer's or an expert's index) and that share shape and std are
    drawn in one vmapped call, so that layers of several kinds still make a
    small program."""
    groups = {}
    for i, (name, shape, std) in enumerate(specs):
        sig = (re.sub(r"(?<=\.)\d+(?=\.)", "#", name), tuple(shape), std)
        groups.setdefault(sig, []).append(i)
    out = {}
    for (_, shape, std), idx in groups.items():
        if len(idx) == 1:
            out[specs[idx[0]][0]] = leaf(key, idx[0], shape, std, dtype)
            continue
        # evenly spaced (layers of one kind): computed, since an array of
        # constants makes the program's code 100 KB larger on the chip, which
        # stays in HBM beside the state and moved a training step by 0.06%
        stride = idx[1] - idx[0]
        if all(b - a == stride for a, b in zip(idx, idx[1:])):
            at = jnp.arange(len(idx)) * stride + idx[0]
        else:
            at = jnp.asarray(np.asarray(idx, np.int32))
        stacked = jax.vmap(lambda i: leaf(key, i, shape, std, dtype))(at)
        for k, i in enumerate(idx):
            out[specs[i][0]] = stacked[k]
    return out


def tree(named):
    """The pytree that dotted names spell: a part that is a number indexes a
    list, any other part a dict."""
    root = {}
    for name, value in named.items():
        parts = name.split(".")
        node = root
        for a in parts[:-1]:
            node = node.setdefault(a, {})
        node[parts[-1]] = value

    def lists(node):
        if not isinstance(node, dict):
            return node
        if node and all(k.isdigit() for k in node):
            return [lists(node[str(i)]) for i in range(len(node))]
        return {k: lists(v) for k, v in node.items()}
    return lists(root)


def make(config, key, dtype=jnp.bfloat16):
    """The program's parameter pytree from ``key = seed_key(seed)``.  Call
    under ``jax.jit`` with the wanted out_shardings and the key as an
    ARGUMENT: a seed closed over would be a constant of the program, and
    every seed would compile anew."""
    return tree(draw(spec.family(config).leaf_specs(config), key, dtype))


def flat(params):
    """{dotted name: leaf} of a pytree laid out as ``tree`` lays it."""
    def part(k):
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                return str(getattr(k, attr))
        return str(k)
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    return {".".join(part(k) for k in path): x for path, x in leaves}
