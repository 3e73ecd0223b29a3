"""Scratch: does a slot's logits depend on what other slots do in the same tick?"""
import functools, json, os, sys
import numpy as np
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from perfbench.lib import spec, weights

seed, path, row, at = int(sys.argv[1]), sys.argv[2], int(sys.argv[3]), int(sys.argv[4])
_, config, _ = spec.cell("serve-decode")
e = config["engine"]; llama, cfg = spec.family(config).program(config)
C, bs, S = e["prefill_chunk"], e["block_size"], e["max_slots"]
sample = json.load(open(path)); seq = sample["seqs"][row]
params = jax.jit(lambda key: weights.make(config, key, cfg.dtype))(weights.seed_key(seed))
step = jax.jit(functools.partial(llama.apply_cached, cfg=cfg))
nblocks = 1024
rng = np.random.default_rng(0)
order = rng.permutation(nblocks)
tables = -np.ones((S, e["max_seq_len"] // bs), np.int32)
per = 64
for s in range(S):
    tables[s, :per] = order[s * per:(s + 1) * per]
cache = llama.init_cache(cfg, nblocks, bs)
# slot 3 holds seq[:at]; slots 6..15 hold 300 random tokens each
def feed(cache, slot_tokens, lengths):
    toks = np.zeros((S, C), np.int32); n_new = np.zeros(S, np.int32); ln = np.zeros(S, np.int32)
    for s, t in slot_tokens.items():
        toks[s, :len(t)] = t; n_new[s] = len(t); ln[s] = lengths[s]
    return step(params, jnp.asarray(toks), cache=cache, block_tables=jnp.asarray(tables),
                lengths=jnp.asarray(ln), n_new=jnp.asarray(n_new))
others = {s: rng.integers(0, config["vocab_size"], 300).tolist() for s in range(6, 16)}
for pos in range(0, at, C):
    _, cache = feed(cache, {3: seq[pos:min(pos + C, at)]}, {3: pos})
for pos in range(0, 300, C):
    _, cache = feed(cache, {s: t[pos:min(pos + C, 300)] for s, t in others.items()},
                    {s: pos for s in others})
def one(extra, extra_len):
    work = {3: [seq[at]]}; lens = {3: at}
    work.update(extra); lens.update(extra_len)
    logits, _ = feed(cache, work, lens)
    return np.asarray(logits[3, 0], np.float32)
alone = one({}, {})
pre = one({5: rng.integers(0, config["vocab_size"], 128).tolist()}, {5: 0})
dec = one({s: [7] for s in others}, {s: 300 for s in others})
both = one({**{s: [7] for s in others}, 5: rng.integers(0, config["vocab_size"], 128).tolist()},
           {**{s: 300 for s in others}, 5: 0})
sd = alone.std()
for name, z in (("with a prefill beside", pre), ("with 10 decodes beside", dec), ("with both", both)):
    print("XTALK", name, "max|dz|/std", float(np.abs(z - alone).max() / sd),
          "rms/std", float(np.sqrt(np.mean((z - alone) ** 2)) / sd),
          "argmax", int(alone.argmax()), int(z.argmax()))
