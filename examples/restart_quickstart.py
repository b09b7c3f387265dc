"""Warm restarts: kill a serving box, boot the next one in milliseconds.

PR 8 closes the crash windows (journaled publish + staged-rename
compaction, DESIGN.md §12), so a killed service always reopens to a
consistent store.  This example shows the OTHER half of the restart
story: `WarmState` checkpoints make the reopen fast.

A cold `GraphService.from_store` boot re-derives its serving state by
scanning the store — every shard is read once just to build the Bloom
filters.  A warm boot restores that state from a checkpoint instead:

1. ingest + serve, apply an update, answer a query (populating the
   session cache), then `save_warm_state()` and close — simulating a
   planned restart or a periodic snapshot before a crash,
2. cold-boot a fresh service and count its boot reads,
3. warm-boot from the checkpoint: ZERO boot reads, the repeat query is a
   session-cache hit, and fresh queries are bitwise the cold service's,
4. mutate the store BEHIND a snapshot and warm-boot again: the touched
   shard is rejected (store is authoritative), everything else stays
   warm, and answers are still correct.

An `emulate_bw` throttle makes the boot-time difference visible on a
small example.

Run:  PYTHONPATH=src python examples/restart_quickstart.py
"""

import os
import tempfile
import time

import numpy as np

from repro.core.graph import rmat_graph
from repro.serve import GraphService

BW = 200e6  # emulated disk bandwidth, bytes/s — makes boot reads cost time


def main() -> None:
    num_v, num_e, shards = 20_000, 200_000, 8
    with tempfile.TemporaryDirectory() as d:
        root = os.path.join(d, "store")
        ckdir = os.path.join(d, "warm")

        # 1. serve, mutate, query, snapshot, die
        g = rmat_graph(num_v, num_e, seed=3)
        svc = GraphService.from_graph(
            g, root, num_shards=shards, cache_bytes=64 << 20)
        svc.apply_updates(
            inserts=(np.array([1, 2]), np.array([3, 4]))).result()
        r0 = svc.query("bfs", 0)
        svc.save_warm_state(ckdir)
        svc.close()
        print(f"snapshot saved to {ckdir} at store version "
              f"{svc.engine.store.delta.version}")

        # 2. cold boot: the filter build reads every shard
        t0 = time.perf_counter()
        cold = GraphService.from_store(root, emulate_bw=BW,
                                       cache_bytes=64 << 20)
        cold_wall = time.perf_counter() - t0
        io = cold.engine.loading_io
        print(f"cold boot: {cold_wall*1e3:7.1f} ms  "
              f"({io.reads} reads, {io.bytes_read} bytes)")

        # 3. warm boot: restore Bloom sources + session cache, read nothing
        t0 = time.perf_counter()
        warm = GraphService.from_store(root, warm_state=ckdir,
                                       emulate_bw=BW, cache_bytes=64 << 20)
        warm_wall = time.perf_counter() - t0
        rep = warm.warm_restore_report
        io = warm.engine.loading_io
        print(f"warm boot: {warm_wall*1e3:7.1f} ms  "
              f"({io.reads} reads, {io.bytes_read} bytes)  "
              f"shards_warm={rep['shards_warm']}/{shards} "
              f"sessions={rep['sessions_restored']}")
        assert rep["valid"] and io.reads == 0
        assert warm_wall < cold_wall

        hit = warm.query("bfs", 0)  # restored session entry: no sweep
        assert hit.cached and np.array_equal(hit.values, r0.values)
        print(f"repeat query after warm boot: cached={hit.cached}")
        a, b = warm.query("sssp", 7), cold.query("sssp", 7)
        assert np.array_equal(a.values, b.values)  # warm == cold, bitwise
        warm.close()

        # 4. the store moves on behind the snapshot: publish via the cold
        # service, then warm-boot from the now-stale checkpoint
        cold.apply_updates(
            inserts=(np.array([5]), np.array([6]))).result()
        r_new = cold.query("bfs", 0)
        cold.close()

        stale = GraphService.from_store(root, warm_state=ckdir,
                                        cache_bytes=64 << 20)
        rep = stale.warm_restore_report
        print(f"stale snapshot: shards_warm={rep['shards_warm']} "
              f"shards_stale={rep['shards_stale']} "
              f"sessions={rep['sessions_restored']}")
        assert rep["valid"] and rep["shards_stale"] >= 1
        assert rep["sessions_restored"] == 0  # content changed: no replays
        r = stale.query("bfs", 0)
        assert not r.cached and np.array_equal(r.values, r_new.values)
        print("stale shards rejected, answers still correct — the store "
              "is always authoritative.")
        stale.close()
        print("done.")


if __name__ == "__main__":
    main()
