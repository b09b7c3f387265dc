"""Capture a device profile of the measured window and reduce it.

The JAX profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into plain interval lists.  Device operations
are the events of each ``/device:TPU:<i>`` plane's ``XLA Ops`` line; the
window is the host annotation :data:`WINDOW` that the harness opens around
the measured work.  The program's own spans (``repro.obs.trace``) are on
the ``perf_counter_ns`` clock; :func:`align_spans` moves them onto the
profile's clock through the window annotation, whose start on both clocks
is known.

Everything below :func:`load` works on plain tuples, so the reduction is
tested on a small recorded trace without a chip.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import re
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]  # (start_ns, end_ns)


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the block; yields the ``perf_counter_ns`` taken as the
    :data:`WINDOW` annotation opened (the alignment point)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # a Python call trace would swamp the window
    opts.host_tracer_level = 1  # annotations only
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield time.perf_counter_ns()
    finally:
        jax.profiler.stop_trace()


class Profile:
    """One reduced profile: device op events per device, and the window."""

    def __init__(self, ops: Dict[int, List[Tuple[str, float, float, Dict]]],
                 window: Interval):
        self.ops = ops  # device -> [(name, start_ns, end_ns, stats)]
        self.window = window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def in_window(self, device: int):
        w0, w1 = self.window
        for name, s, e, st in self.ops.get(device, ()):
            if e > w0 and s < w1:
                yield name, max(s, w0), min(e, w1), st

    def busy_s(self, device: int) -> float:
        return _length(_union((s, e) for _, s, e, _ in self.in_window(device)))

    @property
    def used(self) -> List[int]:
        """The devices that ran an operation: a cell given more chips than
        its program uses averages over the chips used."""
        return [d for d in sorted(self.ops) if self.ops[d]] or [0]

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.used) / len(self.used)

    def op_seconds(self, pattern: str, device: Optional[int] = None) -> float:
        """Summed in-window time of ops whose name or metadata matches
        ``pattern`` (a regular expression)."""
        rx = re.compile(pattern)
        devs = [device] if device is not None else sorted(self.ops)
        total = 0.0
        for d in devs:
            for name, s, e, st in self.in_window(d):
                if _matches(rx, name, st):
                    total += e - s
        return total * 1e-9

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` op names (by ``hlo_module/name``) that took the most
        device time in the window, in seconds averaged over devices."""
        acc: Dict[str, float] = collections.Counter()
        devs = self.used
        for d in devs:
            for name, s, e, st in self.in_window(d):
                acc[_op_label(name, st)] += (e - s) * 1e-9 / len(devs)
        return [[n, v] for n, v in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]

    def gaps(self, device: int = 0) -> List[Interval]:
        """Idle intervals of ``device`` inside the window."""
        busy = _union((s, e) for _, s, e, _ in self.in_window(device))
        out, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return out


def idle_share(ctx: Dict) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device
    (averaged over chips), in %; ``None`` without a device profile."""
    prof = ctx.get("profile")
    if prof is None or not prof.ops or prof.window_s <= 0:
        return None
    return 100.0 * (1.0 - prof.mean_busy_s() / prof.window_s)


def _matches(rx, name: str, stats: Dict) -> bool:
    if rx.search(name):
        return True
    return any(isinstance(v, str) and rx.search(v) for v in stats.values())


def _op_label(name: str, stats: Dict) -> str:
    """The op's instruction name without its number: a TPU trace names an
    op by its whole HLO text (``%ell_partials_masked.1 = f32[...] ...``),
    and one kernel at many shapes is one row of the breakdown."""
    short = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))
    mod = stats.get("hlo_module")
    return f"{mod}/{short}" if mod else short


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals) * 1e-9


def load(log_dir: str) -> Profile:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profile under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: Dict[int, List] = {}
    window: Optional[Interval] = None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     {k: v for k, v in dict(ev.stats).items()
                      if isinstance(v, (str, int, float))})
                    for ev in line.events
                )
            elif not m:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        raise ValueError(f"the profile has no {WINDOW!r} annotation")
    return Profile(ops, window)


# ------------------------------------------------------------ host spans
Span = Tuple[str, str, float, float, Dict]  # (thread, name, start, end, attrs)


def tracer_spans(tracer) -> List[Span]:
    """Completed spans of a ``repro.obs.trace.Tracer``, on the
    ``perf_counter_ns`` clock."""
    out: List[Span] = []
    with tracer._reg_lock:
        rings = list(tracer._rings)
    for ring in rings:
        evs, _ = ring.snapshot()
        for ph, name, t_ns, dur, attrs in evs:
            if ph == "X":
                out.append((ring.name, name, float(t_ns), float(t_ns + dur),
                            dict(attrs or {})))
    return out


def align_spans(spans: Iterable[Span], perf_at_window_ns: float,
                profile: Profile) -> List[Span]:
    """Shift spans onto the profile clock: the window annotation opened at
    ``perf_at_window_ns`` on the host clock and at ``profile.window[0]``."""
    off = profile.window[0] - perf_at_window_ns
    return [(th, n, s + off, e + off, a) for th, n, s, e, a in spans]


def name_gaps(gaps: Sequence[Interval], spans: Sequence[Span], thread: str,
              k: int = 10) -> List[List]:
    """Idle seconds per innermost span that ``thread`` had open at each
    gap's midpoint (``(no span)`` where it had none); the ``k`` largest."""
    mine = sorted((s, e, n) for th, n, s, e, _ in spans if th == thread)
    starts = [s for s, _, _ in mine]
    acc: Dict[str, float] = collections.Counter()
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        label = "(no span)"
        # One thread's spans nest, so the innermost span holding ``mid`` is
        # the latest-starting one that has not ended by then.
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if mine[i][1] >= mid:
                label = mine[i][2]
                break
        acc[label] += (g1 - g0) * 1e-9
    return [[n, v] for n, v in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]
