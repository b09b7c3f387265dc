#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (graph generation, store build, opening the engine or service, and
warming every shape the window uses, compiles included) runs first and is
reported as ``setup_s``.  The window then measures for ``--seconds``; with
``--trace 1`` the same window runs under the JAX profiler and the program's
own tracer, and the per-layer metrics are reported in place of the
end-to-end ones.  After the window the results are compared with the plain
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with its limit.  The same numbers end standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits with
status 2 and prints no result.  JAX's compilation cache is ``.jax_cache/``
in this checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import devtrace, roofline, spec  # noqa: E402
from bench.cells import DRIVERS  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class CompileClock:
    """Counts JAX backend compiles, so a window that compiles shows."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration


class Window:
    """The measured window; with ``trace`` it is profiled and the
    program's tracer records its spans."""

    def __init__(self, seconds: float, trace: bool, clock: CompileClock,
                 prof_dir: str):
        self.seconds, self.trace, self.clock = seconds, trace, clock
        self.prof_dir = prof_dir
        self._stack = contextlib.ExitStack()
        self.tracer = None
        self.perf_at_open_ns = None

    def open(self) -> None:
        if self.trace:
            from repro.obs import trace as obs_trace

            self.tracer = obs_trace.Tracer(capacity=1 << 20)
            self._stack.enter_context(obs_trace.tracing(self.tracer))
            self.perf_at_open_ns = self._stack.enter_context(
                devtrace.capture(self.prof_dir))
        self.compiles0 = self.clock.compiles
        self.host0 = host_counters()
        self.t_open = time.perf_counter()
        self.deadline = self.t_open + self.seconds

    def close(self) -> None:
        self.t_close = time.perf_counter()
        self.compiles = self.clock.compiles - self.compiles0
        host1 = host_counters()
        self.host = {k: host1[k] - self.host0[k] for k in host1}
        self._stack.close()


def host_counters() -> dict:
    """This process's CPU seconds, and the bytes it read from storage rather
    than from the page cache where the kernel counts them."""
    out = {"cpu_s": sum(os.times()[:2])}
    try:
        with open("/proc/self/io") as f:
            io = dict(line.split(": ") for line in f.read().splitlines())
        out["storage_read_bytes"] = int(io["read_bytes"])
    except (OSError, KeyError, ValueError):
        pass
    return out


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    known = [p for p in peaks if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(known) if known else None}


def run_cell(c: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    clock = CompileClock()
    cfg = c["config"]
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        cell = DRIVERS[cfg["system"]](cfg, c["traffic"], seed,
                                      os.path.join(tmp, "store"))
        cell.setup()
        win = Window(seconds, trace, clock, os.path.join(tmp, "profile"))
        cell.win = win
        cell.run_window(win)
        setup_s = win.t_open - t_start
        dev = device_info(devices)
        cell.release()
        checks = cell.check()
        correct = all(v["value"] <= v["limit"] for v in checks.values())
        out = {"correct": correct, "attempted": cell.attempted,
               "failed": cell.failed}
        notes = {"compiles_in_window": win.compiles,
                 "window_s": win.t_close - win.t_open,
                 "window_host": win.host,
                 "setup_phases_s": cell.phases}
        if hasattr(cell, "units"):
            notes["units"] = cell.units()
        if not trace:
            e2e = dict(cell.end_to_end(win), setup_s=setup_s)
            out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
                              for m in c["end_to_end"]}
        else:
            ctx = cell.layer_ctx()
            prof = devtrace.load(win.prof_dir)
            spans = devtrace.align_spans(devtrace.tracer_spans(win.tracer),
                                         win.perf_at_open_ns, prof)
            spans = [s for s in spans
                     if prof.window[0] <= s[2] <= prof.window[1]]
            ctx.update(profile=prof, spans=spans,
                       peak=roofline.peaks(dev["kind"]),
                       dispatches=ctx["dispatch_work"](spans))
            out["metrics"] = spec.read_metrics(c["per_layer"], ctx)
            dev["busy_s"] = prof.mean_busy_s()
            dev["window_s"] = prof.window_s
            out["breakdown"] = {
                "device_ops": prof.top_ops(10),
                "idle_gaps": devtrace.name_gaps(
                    prof.gaps(prof.used[0]), spans,
                    cell.consumer_thread),
            }
        out["device"] = dev
        out["notes"] = notes
        out["checks"] = checks
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    c = spec.cell(args.workload, spec.benchmark())
    import jax

    devices = jax.devices()
    chips = int(c["workload"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = run_cell(c, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, devices=devices[:chips])
    print(json.dumps({"notes": out.pop("notes")}), flush=True)
    for name, v in out["checks"].items():
        print(f"check {name} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
