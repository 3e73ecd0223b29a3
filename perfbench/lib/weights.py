"""Seeded weights of a dense GQA decoder (gated SiLU FFN, no biases, untied
head), as the benchmark makes them: for the program the whole tree in one
jitted call in the serving/training type, for the reference one leaf at a
time.  Both read ``leaf``, so the same seed gives the same values."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def leaf_specs(config):
    """[(name, shape, std or None for a norm scale of ones)] in tree order.
    Names are the program's pytree paths joined by dots."""
    d, V = config["hidden_size"], config["vocab_size"]
    hd = d // config["num_attention_heads"]
    nq, nkv = d, config["num_key_value_heads"] * hd
    f = config["intermediate_size"]
    s = 1.0 / math.sqrt(d)
    out = [("embed.table", (V, d), 0.02),
           ("final_norm.scale", (d,), None),
           ("lm_head.kernel", (d, V), s)]
    for i in range(config["num_hidden_layers"]):
        p = f"layers.{i}."
        out += [(p + "attn_norm.scale", (d,), None),
                (p + "wq.kernel", (d, nq), s), (p + "wk.kernel", (d, nkv), s),
                (p + "wv.kernel", (d, nkv), s), (p + "wo.kernel", (nq, d), s),
                (p + "ffn_norm.scale", (d,), None),
                (p + "w_gate.kernel", (d, f), s), (p + "w_up.kernel", (d, f), s),
                (p + "w_down.kernel", (f, d), 1.0 / math.sqrt(f))]
    return out


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


def make(config, key, dtype=jnp.bfloat16):
    """The program's parameter pytree (horovod_tpu.models.llama layout)
    from ``key = seed_key(seed)``.  Call under ``jax.jit`` with the wanted
    out_shardings and the key as an ARGUMENT: a seed closed over would be a
    constant of the program, and every seed would compile anew.  Leaves of one
    kind are drawn for all layers in one vmapped call (the same values as
    ``leaf`` gives one by one), so the program stays small."""
    specs = leaf_specs(config)
    L = config["num_hidden_layers"]
    per = (len(specs) - 3) // L
    tree = {"layers": [{} for _ in range(L)]}

    def put(node, name, value):
        a, b = name.split(".")[-2:]
        node.setdefault(a, {})[b] = value

    for index in range(3):
        name, shape, std = specs[index]
        put(tree, name, leaf(key, index, shape, std, dtype))
    for k in range(per):
        name, shape, std = specs[3 + k]
        idx = jnp.arange(L) * per + 3 + k
        stacked = jax.vmap(lambda i: leaf(key, i, shape, std, dtype))(idx)
        for i in range(L):
            put(tree["layers"][i], name, stacked[i])
    return tree


def flat(tree):
    """{dotted name: leaf} of a pytree laid out as ``make`` lays it."""
    def part(k):
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                return str(getattr(k, attr))
        return str(k)
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {".".join(part(k) for k in path): x for path, x in leaves}
