"""Cell drivers: build the system under test, warm it, drive the window.

A driver is picked by the configuration's ``system``:

``vsw``
    One ``VSWEngine`` over the generated graph; the window runs
    ``VSWEngine.run`` back to back (``analytics`` traffic).
``service``
    One ``GraphService``; the window is a closed loop of callers
    (``closed_loop`` traffic) submitting through ``GraphService.submit``.

Each driver's ``check`` compares what the window produced with the plain
reference (:mod:`bench.reference`) over the generated edge list, and
returns every number compared with its limit from the traffic file.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List

import numpy as np

from bench import reference as ref
from bench import traffic as traffic_gen
from bench.graphgen import rmat_edges

#: Seconds to wait for the queries in flight when a serving window closes.
DRAIN_TIMEOUT_S = 60.0

#: Per combine, a program whose first iteration has every vertex active.
ALL_ACTIVE = {"sum": lambda apps: apps.pagerank(),
              "min": lambda apps: apps.wcc()}


def generate(cfg: Dict, seed: int):
    g = cfg["graph"]
    if g["generator"] != "rmat":
        raise ValueError(f"unknown generator {g['generator']!r}")
    src, dst = rmat_edges(g["scale"], g["edge_factor"], seed,
                          a=g["a"], b=g["b"], c=g["c"], permute=g["permute"])
    return 1 << g["scale"], src, dst


def _program_graph(n, src, dst):
    from repro.core.graph import Graph

    return Graph(n, src, dst)


def _phases(t0, t1, t2, t3) -> Dict[str, float]:
    """Seconds of set-up spent generating the graph, building the store and
    opening the engine or service, and warming up."""
    return {"graph_s": t1 - t0, "build_s": t2 - t1, "warm_s": t3 - t2}


def _flush(root: str) -> None:
    """Write the built store through to the disk, so that the write-back of
    its dirty pages falls in set-up and not in the window."""
    for d, _, files in os.walk(root):
        for f in files:
            fd = os.open(os.path.join(d, f), os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)


class _Cell:
    def __init__(self, cfg: Dict, traffic: Dict, seed: int, root: str):
        self.cfg, self.traffic, self.seed, self.root = cfg, traffic, seed, root
        self.limits = traffic["limits"]

    def _graph(self):
        self.n, self.src, self.dst = generate(self.cfg, self.seed)
        self.m = len(self.src)

    def shard_work(self, intervals) -> Dict[int, tuple]:
        """Per shard: ``(edges, rows)`` from the generated edge list and the
        shard's destination interval."""
        counts = np.bincount(np.searchsorted(intervals, self.dst, "right") - 1,
                             minlength=len(intervals) - 1)
        return {p: (int(counts[p]), int(intervals[p + 1] - intervals[p]))
                for p in range(len(intervals) - 1)}

    def _checks(self, **values) -> Dict[str, Dict]:
        return {k: {"value": v, "limit": self.limits[k]}
                for k, v in values.items()}


class VswCell(_Cell):
    consumer_thread = "MainThread"

    def setup(self) -> None:
        from repro.core import apps
        from repro.core.vsw import VSWEngine

        t0 = time.perf_counter()
        self._graph()
        t1 = time.perf_counter()
        st = self.cfg["store"]
        self.engine = VSWEngine.from_graph(
            _program_graph(self.n, self.src, self.dst), self.root,
            num_shards=st["num_shards"], window=st["window"], k=st["k"],
            tr=st["tr"], **self.cfg["engine"])
        _flush(self.root)
        self.apps = apps
        self.ops = traffic_gen.analytics_plan(
            self.traffic, self.src, self.n, self.seed)
        # One iteration with every vertex active visits every shard, which
        # compiles each shard's kernel for this program's combine: the only
        # programs the window runs.
        combine = apps.get_program(self.traffic["program"]).combine
        t2 = time.perf_counter()
        self.engine.run(ALL_ACTIVE[combine](apps), max_iters=1)
        self.phases = _phases(t0, t1, t2, time.perf_counter())
        self.runs: List = []

    def _run(self, op):
        name, source, params = op
        kw = dict(params)
        if source >= 0:
            kw["source"] = source
        iters = int(self.traffic.get("iterations")
                    or self.traffic["max_iters"])
        return self.engine.run(self.apps.get_program(name, **kw),
                               max_iters=iters)

    def run_window(self, win) -> None:
        win.open()
        for op in self.ops:
            if time.perf_counter() >= win.deadline:
                break
            t0 = time.perf_counter()
            r = self._run(op)
            self.runs.append((op, t0, time.perf_counter(), r))
        win.close()

    def release(self) -> None:
        self.intervals = np.asarray(self.engine.meta.intervals)
        self.engine.close()

    @property
    def attempted(self) -> int:
        return len(self.runs)

    failed = 0

    def check(self) -> Dict[str, Dict]:
        t = self.traffic
        if t["program"] == "pagerank":
            want = ref.pagerank(self.src, self.dst, self.n,
                                damping=t["params"]["damping"],
                                iterations=t["iterations"])
            self.work = [t["iterations"] * self.m] * len(self.runs)
            return self._checks(
                rel_l1=max(ref.rel_l1(r.values, want)
                           for *_, r in self.runs),
                iteration_mismatch=sum(r.num_iterations != t["iterations"]
                                       for *_, r in self.runs))
        mism = iters = 0
        self.work = []
        for (_, source, _), _, _, r in self.runs:
            lv = ref.bfs_levels(self.src, self.dst, self.n, source,
                                max_iters=t["max_iters"])
            mism += ref.level_mismatch(r.values, lv)
            iters += r.num_iterations != ref.depth(lv) + 1
            self.work.append(ref.reached_edges(self.src, lv))
        return self._checks(level_mismatch=mism, iteration_mismatch=iters)

    def units(self) -> List[List]:
        """Per run in the window: iterations, seconds, edges counted, each
        iteration's seconds, and the run's seconds waiting for shard loads
        and in the executor."""
        return [[r.num_iterations, t1 - t0, w,
                 [round(i.time_s, 4) for i in r.iterations],
                 round(sum(i.load_wait_s for i in r.iterations), 4),
                 round(sum(i.exec_s for i in r.iterations), 4)]
                for (_, t0, t1, r), w in zip(self.runs, self.work)]

    def end_to_end(self, win) -> Dict[str, float]:
        span = self.runs[-1][2] - win.t_open
        return {"teps": sum(self.work) / span}

    def layer_ctx(self) -> Dict:
        work = self.shard_work(self.intervals)
        return {
            "iter_stats": [i for *_, r in self.runs for i in r.iterations],
            "dispatch_work": lambda spans: [
                (*work[a["shard"]], self.n, 1)
                for th, name, _, _, a in spans
                if th == self.consumer_thread and name == "exec.dispatch"],
        }


class ClosedLoop:
    """``clients`` callers, each with one query in flight.  The next query
    is sent from the completed query's callback, so a freed lane finds its
    successor queued before the service looks for backfill."""

    def __init__(self, svc, ops, clients: int, max_iters: int):
        self.svc, self.ops, self.clients = svc, ops, clients
        self.max_iters = max_iters
        self.lock = threading.Lock()
        self.next = 0
        self.inflight = 0
        self.stopping = False
        self.drained = threading.Event()
        self.records: List[Dict] = []  # completed or failed, in order

    def start(self) -> None:
        with self.svc.submit_batch():  # the first queries form one fusion set
            for _ in range(self.clients):
                self._submit()

    def completed(self) -> int:
        with self.lock:
            return len(self.records)

    def _submit(self) -> None:
        with self.lock:
            if self.stopping or self.next >= len(self.ops):
                if self.inflight == 0:
                    self.drained.set()
                return
            i = self.next
            self.next += 1
            self.inflight += 1
        program, source, params = self.ops[i]
        t0 = time.perf_counter()
        try:
            fut = self.svc.submit(program, source, max_iters=self.max_iters,
                                  **params)
        except Exception as exc:  # recorded as a failed query
            self._done(i, t0, None, exc)
            return
        fut.add_done_callback(lambda f, i=i, t0=t0: self._done(i, t0, f))

    def _done(self, i, t0, fut, exc=None) -> None:
        t1 = time.perf_counter()
        result = None
        if exc is None:
            exc = fut.exception()
            result = None if exc is not None else fut.result()
        program, source, params = self.ops[i]
        with self.lock:
            self.records.append({
                "op": i, "program": program, "source": source,
                "t_submit": t0, "t_done": t1, "result": result,
                "error": None if exc is None else repr(exc)})
            self.inflight -= 1
        self._submit()

    def stop(self) -> None:
        with self.lock:
            self.stopping = True
            if self.inflight == 0:
                self.drained.set()


class ServiceCell(_Cell):
    consumer_thread = "graphserve-worker"

    def setup(self) -> None:
        from repro.serve import GraphService

        t0 = time.perf_counter()
        self._graph()
        t1 = time.perf_counter()
        st, t = self.cfg["store"], self.traffic
        if t["kind"] != "closed_loop":
            raise ValueError("a service cell needs closed_loop traffic")
        self.svc = GraphService.from_graph(
            _program_graph(self.n, self.src, self.dst), self.root,
            num_shards=st["num_shards"], window=st["window"], k=st["k"],
            tr=st["tr"], **self.cfg["engine"], **self.cfg["service"])
        _flush(self.root)
        t2 = time.perf_counter()
        ops = traffic_gen.closed_loop_plan(t, self.src, self.n, self.seed)
        self.loop = ClosedLoop(self.svc, ops, int(t["clients"]),
                               int(t["max_iters"]))
        self.loop.start()
        # Warm-up is the loop itself: its first fusion set compiles every
        # shard's kernel at the lane widths the window keeps using.
        while self.loop.completed() < int(t["warmup_completions"]):
            time.sleep(0.02)
        self.phases = _phases(t0, t1, t2, time.perf_counter())

    def run_window(self, win) -> None:
        self.win = win
        win.open()
        time.sleep(max(0.0, win.deadline - time.perf_counter()))
        self.loop.stop()
        win.close()

    def release(self) -> None:
        if not self.loop.drained.wait(DRAIN_TIMEOUT_S):
            raise TimeoutError("queries in flight did not finish after the "
                               f"window ({DRAIN_TIMEOUT_S} s)")
        self.intervals = np.asarray(self.svc.engine.meta.intervals)
        self.svc.close()

    def window_records(self, win) -> List[Dict]:
        return [r for r in self.loop.records
                if win.t_open <= r["t_done"] <= win.deadline]

    def check(self) -> Dict[str, Dict]:
        self.win_records = self.window_records(self.win)
        mism = iters = 0
        rel = 0.0
        params = self.traffic.get("params", {})
        mi = int(self.traffic["max_iters"])
        # Every answer that came after the window opened, the drain's too.
        for r in self.loop.records:
            qr = r["result"]
            if r["t_done"] < self.win.t_open or qr is None:
                continue
            if r["program"] == "ppr":
                want = ref.ppr(self.src, self.dst, self.n, r["source"],
                               damping=params["ppr"]["damping"], max_iters=mi)
                rel = max(rel, ref.rel_l1(qr.values, want))
            else:  # bfs, sssp: unit-weight hop levels
                lv = ref.bfs_levels(self.src, self.dst, self.n, r["source"],
                                    max_iters=mi)
                mism += ref.level_mismatch(qr.values, lv)
                iters += qr.iterations != min(ref.depth(lv) + 1, mi)
        # A query that raised instead of answering, at any time of the run,
        # is a failed one.
        return self._checks(failed_queries=self.failed, level_mismatch=mism,
                            iteration_mismatch=iters, ppr_rel_l1=rel)

    @property
    def attempted(self) -> int:
        return sum(self.win.t_open <= r["t_submit"] <= self.win.deadline
                   for r in self.loop.records)

    @property
    def failed(self) -> int:
        return sum(r["error"] is not None for r in self.loop.records)

    def end_to_end(self, win) -> Dict[str, float]:
        done = [r for r in self.win_records if r["result"] is not None]
        lat = [r["t_done"] - r["t_submit"] for r in done]
        # With no answer in the window no query came back within it.
        p95 = float(np.percentile(lat, 95)) if lat else win.seconds
        return {"queries_per_s": len(done) / (win.deadline - win.t_open),
                "query_p95_s": p95}

    def layer_ctx(self) -> Dict:
        work = self.shard_work(self.intervals)

        def dispatch_work(spans):
            """Shards the worker waited for inside each sweep iteration,
            at that iteration's live lanes."""
            out = []
            iters = sorted((s, e, a.get("live_lanes", 0))
                           for th, name, s, e, a in spans
                           if th == self.consumer_thread
                           and name == "sweep.iter")
            for th, name, s, e, a in spans:
                if th != self.consumer_thread or name != "shard.wait":
                    continue
                for i0, i1, lanes in iters:
                    if i0 <= s <= i1:
                        out.append((*work[a["shard"]], self.n, lanes))
                        break
            return out

        return {
            "worker": self.consumer_thread,
            "queries": [(r["result"].latency_s, r["result"].queue_wait_s)
                        for r in self.win_records if r["result"] is not None],
            "dispatch_work": dispatch_work,
        }


DRIVERS = {"vsw": VswCell, "service": ServiceCell}
