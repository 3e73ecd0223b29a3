"""Scratch: the engine's on-device argmax against the argmax of the logits
the same call returns."""
import json, os, sys
import numpy as np
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from perfbench.lib import spec, weights

seed, path, row = int(sys.argv[1]), sys.argv[2], int(sys.argv[3])
_, config, _ = spec.cell("serve-decode")
e = config["engine"]; llama, cfg = spec.family(config).program(config)
C, bs, S = e["prefill_chunk"], e["block_size"], e["max_slots"]
sample = json.load(open(path)); seq = sample["seqs"][row]
first, n = sample["spans"][row]
params = jax.jit(lambda key: weights.make(config, key, cfg.dtype))(weights.seed_key(seed))

def engine_step(params, cache, bt, lengths, n_new, tokens):
    logits, cache = llama.apply_cached(params, tokens, cfg, cache, bt, lengths, n_new)
    nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    return cache, nxt

def both_step(params, cache, bt, lengths, n_new, tokens):
    logits, cache = llama.apply_cached(params, tokens, cfg, cache, bt, lengths, n_new)
    nxt = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    return cache, nxt, logits

eng = jax.jit(engine_step); both = jax.jit(both_step)
nblocks = 512
tables = -np.ones((S, e["max_seq_len"] // bs), np.int32)
tables[0, :32] = np.arange(32)
def run(fn):
    cache = llama.init_cache(cfg, nblocks, bs)
    picks, pos, P = [], 0, first + 1
    extra = []
    while pos < first + n:
        k = min(C, P - pos) if pos < P else 1
        toks = np.zeros((S, C), np.int32); toks[0, :k] = seq[pos:pos + k]
        ln = np.zeros(S, np.int32); nn = np.zeros(S, np.int32); ln[0], nn[0] = pos, k
        out = fn(params, cache, jnp.asarray(tables), jnp.asarray(ln), jnp.asarray(nn), jnp.asarray(toks))
        cache = out[0]; pos += k
        if pos >= P:
            picks.append(int(out[1][0, k - 1]))
            if len(out) > 2:
                z = np.asarray(out[2][0, k - 1], np.float32)
                extra.append((int(z.argmax()), float(z.max()), z))
    return picks, extra
served = seq[first + 1:first + 1 + n]
p1, _ = run(eng)
p2, ex = run(both)
print("ARGMAX engine-style jit == served:", float(np.mean(np.array(p1) == np.array(served))))
print("ARGMAX both-outputs jit device argmax == served:", float(np.mean(np.array(p2) == np.array(served))))
print("ARGMAX host argmax of returned logits == served:", float(np.mean(np.array([e[0] for e in ex]) == np.array(served))))
bad = [i for i in range(n) if p1[i] != ex[i][0]][:8]
for i in bad:
    z = ex[i][2]
    print("ARGMAX nth", i, "engine-style pick", p1[i], "its bf16 logit", float(z[p1[i]]),
          "| bf16 argmax", ex[i][0], "max", ex[i][1], "| tokens tied at max",
          int((z == z.max()).sum()), "| rank of engine pick", int((z > z[p1[i]]).sum()),
          "| std", float(z.std()))
