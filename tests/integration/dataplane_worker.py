"""Per-rank worker for the multi-process JAX data-plane integration test.

Launched by hvdrun with -np 2 on localhost; each process drives 4 virtual
CPU chips, so the mesh is 8 chips across 2 real processes — the smallest
topology where the cross-process code in ops/collectives.py
(_make_global via make_array_from_process_local_data, the process->chip
reindexing of ragged allgather and uneven alltoall, broadcast_object's
root lookup) actually executes with process_size > 1.

Reference strategy: test/integration/test_static_run.py runs real
horovodrun over localhost the same way.

Exits non-zero on any assertion failure; the launcher's fail-fast
propagates it to the pytest that spawned us.
"""

import sys

import _env_setup  # noqa: F401  (must run before other jax imports)

import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def main() -> int:
    hvd.init()
    assert hvd.process_size() == 2, hvd.process_size()
    assert hvd.size() == 8, hvd.size()
    assert hvd.local_size() == 4, hvd.local_size()
    rt = hvd.runtime.get()
    positions = rt.local_chip_positions()

    # ---- eager allreduce: per-chip distinct values --------------------
    x = np.stack([np.full((3,), float(pos), np.float32)
                  for pos in positions])
    out = np.asarray(hvd.allreduce(x, op=hvd.Sum))
    expect = float(sum(range(8)))  # every chip contributes its position
    assert out.shape == (4, 3) and np.allclose(out, expect), out

    avg = np.asarray(hvd.allreduce(x, op=hvd.Average))
    assert np.allclose(avg, expect / 8.0), avg

    # ---- broadcast from a chip owned by the OTHER process -------------
    root = 5  # chip position 5 lives on some process; both must agree
    out = np.asarray(hvd.broadcast(x, root_rank=root))
    root_val = 5.0
    assert np.allclose(out, root_val), out

    # ---- ragged allgather: chip i contributes i+1 rows ----------------
    tensors = [np.full((pos + 1, 2), float(pos), np.float32)
               for pos in positions]
    g = np.asarray(hvd.allgather_ragged(tensors))
    want_rows = sum(p + 1 for p in range(8))
    assert g.shape == (want_rows, 2), g.shape
    off = 0
    for p in range(8):
        rows = p + 1
        assert np.allclose(g[off:off + rows], float(p)), (p, g[off:off+rows])
        off += rows

    # ---- equal-split alltoall -----------------------------------------
    # chip i sends rows [8*i .. 8*i+7]; after alltoall chip j holds row
    # block from every source at position j.
    a2a_in = np.stack([
        np.arange(8, dtype=np.float32)[:, None] + 8 * pos
        for pos in positions])  # [4, 8, 1]
    out, recv = hvd.alltoall(a2a_in)
    out = np.asarray(out)
    assert out.shape == (4, 8, 1), out.shape
    for li, pos in enumerate(positions):
        want = np.array([8 * src + pos for src in range(8)],
                        np.float32)[:, None]
        assert np.allclose(out[li], want), (pos, out[li], want)
    assert np.asarray(recv).shape == (4, 8) and int(np.asarray(recv)[0, 0]) == 1

    # ---- uneven alltoall ----------------------------------------------
    # chip i sends (dst+1) rows to each dst chip, value = 100*i + dst.
    splits = np.broadcast_to(np.arange(1, 9, dtype=np.int64), (4, 8)).copy()
    blocks = []
    for pos in positions:
        rows = []
        for dst in range(8):
            rows.append(np.full((dst + 1, 1), 100.0 * pos + dst, np.float32))
        blocks.append(np.concatenate(rows, axis=0))
    ua_in = np.stack(blocks)  # [4, 36, 1]
    out, recv = hvd.alltoall(ua_in, splits=splits)
    recv = np.asarray(recv)
    for li, pos in enumerate(positions):
        o = np.asarray(out[li]) if isinstance(out, list) else np.asarray(
            out)[li]
        # chip `pos` receives (pos+1) rows from every src, value 100*src+pos
        assert o.shape == ((pos + 1) * 8, 1), (pos, o.shape)
        off = 0
        for src in range(8):
            assert np.allclose(o[off:off + pos + 1], 100.0 * src + pos), \
                (pos, src, o[off:off + pos + 1])
            off += pos + 1
        assert list(recv[li]) == [pos + 1] * 8, recv[li]

    # ---- broadcast_object across processes ----------------------------
    payload = {"process": hvd.process_rank(), "tag": "hello"} \
        if hvd.process_rank() == 0 else None
    got = hvd.broadcast_object(payload, root_rank=0)
    assert got == {"process": 0, "tag": "hello"}, got

    # ---- allgather_object ---------------------------------------------
    objs = hvd.allgather_object({"p": hvd.process_rank()})
    assert {o["p"] for o in objs} == {0, 1}, objs

    # ---- grouped allreduce (fusion across the process boundary) -------
    tensors = [np.stack([np.full((5,), float(pos) + i, np.float32)
                         for pos in positions]) for i in range(3)]
    outs = hvd.grouped_allreduce(tensors, op=hvd.Sum)
    for i, o in enumerate(outs):
        assert np.allclose(np.asarray(o), expect + 8.0 * i), (i, o)

    # ---- quantized-wire sync across the process boundary --------------
    # The int8 ring (ops/quantized.py) rides ppermute over the GLOBAL
    # mesh: its cross-process collective_permute hops only execute here.
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map
    from horovod_tpu.optimizer import sync_gradients
    mesh = hvd.mesh()
    g_local = np.stack([np.full((16,), float(pos), np.float32)
                        for pos in positions])
    g_global = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("hvd")), g_local)
    qf = jax.jit(shard_map(
        lambda g: sync_gradients({"g": g}, "hvd",
                                 quantized_wire=True)["g"],
        mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
        check_vma=False))
    q_out = jax.block_until_ready(qf(g_global))
    for shard in q_out.addressable_shards:
        # per-chunk constants quantize exactly; mean(0..7) = 3.5
        assert np.allclose(np.asarray(shard.data), 3.5, atol=0.02), \
            np.asarray(shard.data)

    # ---- barrier ------------------------------------------------------
    hvd.barrier()

    print(f"dataplane worker process {hvd.process_rank()} OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
