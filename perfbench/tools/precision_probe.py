"""Precision through the program module's apply_cached at the published widths, on the chip,
against the float32 reference: relative RMS error of the logits per position,
||z - z_ref|| / ||z_ref - mean(z_ref)||.  With a saved sample of a served
run it teacher-forces the served sequences (one slot active, a shuffled block
table, prompt and answer fed in prefill_chunk pieces); without one it draws
seeded sequences, several seeds in one process.

  python3 perfbench/tools/precision_probe.py --seeds 1,2,3 [--sample FILE --rows 1,5]
"""
import argparse
import functools
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--sample", default="")
    ap.add_argument("--rows", default="")
    ap.add_argument("--length", type=int, default=512)
    ap.add_argument("--positions", type=int, default=96)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from perfbench.lib import reference, spec, weights
    _, config, _ = spec.cell("serve-decode")
    e = config["engine"]
    llama, cfg = spec.family(config).program(config)
    C, bs = e["prefill_chunk"], e["block_size"]
    nblocks = 512
    step = jax.jit(functools.partial(llama.apply_cached, cfg=cfg),
                   donate_argnames=("cache",))
    full = jax.jit(functools.partial(llama.apply, cfg=cfg))

    def cached_logits(params, seq, positions, seed):
        cache = llama.init_cache(cfg, nblocks, bs)
        order = np.random.default_rng(seed).permutation(nblocks)
        tables = -np.ones((e["max_slots"], e["max_seq_len"] // bs), np.int32)
        tables[3, :-(-len(seq) // bs)] = order[:-(-len(seq) // bs)]
        out = {}
        for pos in range(0, len(seq), C):
            n = min(C, len(seq) - pos)
            toks = np.zeros((e["max_slots"], C), np.int32)
            toks[3, :n] = seq[pos:pos + n]
            lengths = np.zeros(e["max_slots"], np.int32)
            n_new = np.zeros(e["max_slots"], np.int32)
            lengths[3], n_new[3] = pos, n
            logits, cache = step(params, jnp.asarray(toks), cache=cache,
                                 block_tables=jnp.asarray(tables),
                                 lengths=jnp.asarray(lengths),
                                 n_new=jnp.asarray(n_new))
            for p in positions:
                if pos <= p < pos + n:
                    out[p] = np.asarray(logits[3, p - pos], np.float32)
        return np.stack([out[p] for p in positions])

    def decode_like_engine(params, seq, first, seed, slot=3):
        """Prefill seq[:first+1] in chunks, then one token a call, as the
        engine does; returns the argmax after each call."""
        cache = llama.init_cache(cfg, nblocks, bs)
        order = np.random.default_rng(seed).permutation(nblocks)
        tables = -np.ones((e["max_slots"], e["max_seq_len"] // bs), np.int32)
        tables[slot, :-(-len(seq) // bs)] = order[:-(-len(seq) // bs)]
        picks, pos = [], 0
        P = first + 1
        while pos < len(seq) - 1:
            n = min(C, P - pos) if pos < P else 1
            toks = np.zeros((e["max_slots"], C), np.int32)
            toks[slot, :n] = seq[pos:pos + n]
            lengths = np.zeros(e["max_slots"], np.int32)
            n_new = np.zeros(e["max_slots"], np.int32)
            lengths[slot], n_new[slot] = pos, n
            logits, cache = step(params, jnp.asarray(toks), cache=cache,
                                 block_tables=jnp.asarray(tables),
                                 lengths=jnp.asarray(lengths),
                                 n_new=jnp.asarray(n_new))
            pos += n
            if pos >= P:
                picks.append(int(jnp.argmax(
                    logits[slot, n - 1].astype(jnp.float32))))
        return picks

    def rel_rms(z, ref):
        return np.linalg.norm(z - ref, axis=-1) / np.linalg.norm(
            ref - ref.mean(-1, keepdims=True), axis=-1)

    for seed in [int(s) for s in args.seeds.split(",")]:
        params = jax.jit(lambda key: weights.make(config, key, cfg.dtype))(
            weights.seed_key(seed))
        if args.sample:
            sample = json.load(open(args.sample))
            rows = [int(r) for r in args.rows.split(",")] if args.rows else \
                range(len(sample["seqs"]))
            cases = [(sample["seqs"][r][:sample["spans"][r][0] + 1
                                        + sample["spans"][r][1]],
                      list(range(sample["spans"][r][0],
                                 sample["spans"][r][0] + sample["spans"][r][1])))
                     for r in rows]
        else:
            rng = np.random.default_rng(seed)
            seq = rng.integers(0, config["vocab_size"], args.length).tolist()
            cases = [(seq, sorted(rng.choice(args.length, args.positions,
                                             replace=False).tolist()))]
        for seq, positions in cases:
            ref = np.asarray(reference.logits_at(config, seed, seq, positions))
            zc = cached_logits(params, seq, positions, seed)
            zf = np.asarray(full(params, jnp.asarray([seq]))[0][np.asarray(
                positions)], np.float32)
            ec, ef = rel_rms(zc, ref), rel_rms(zf, ref)
            low = rel_rms(np.asarray(reference.logits_at(
                config, seed, seq, positions, quant="int8")), ref)
            nxt = np.asarray(seq[1:] + [0])[positions]
            agree = (zc.argmax(-1) == nxt).mean()
            if args.sample:
                picks = decode_like_engine(params, seq, positions[0], seed)
                gap = ref.max(-1) - ref[np.arange(len(nxt)), nxt]
                gap = gap / ref.std(-1)
                big = np.argsort(-gap)[:6]
                print("LIVE " + json.dumps({
                    "decode_like_engine_agrees": float(np.mean(
                        np.asarray(picks[:len(nxt)]) == nxt)),
                    "at_big_gaps": [[int(positions[i]), float(gap[i]),
                                     int(nxt[i]), int(picks[i]),
                                     int(zc[i].argmax()), int(ref[i].argmax())]
                                    for i in big]}), flush=True)
            worst = np.argsort(-ec)[:6]
            print("PROBE " + json.dumps({
                "seed": seed, "len": len(seq), "positions": len(positions),
                "cached_rel_rms_median": float(np.median(ec)),
                "cached_rel_rms_max": float(ec.max()),
                "int8_control_rel_rms_median": float(np.median(low)),
                "int8_control_rel_rms_min": float(low.min()),
                "full_rel_rms_median": float(np.median(ef)),
                "full_rel_rms_max": float(ef.max()),
                "cached_argmax_is_next_token": float(agree),
                "worst": [[int(positions[i]), float(ec[i]), float(ef[i])]
                          for i in worst]}), flush=True)


if __name__ == "__main__":
    main()
