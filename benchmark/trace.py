"""Reduce one JAX profiler trace to device busy and idle time.

``summarize(trace_dir, window_s)`` reads the ``.xplane.pb`` that
``jax.profiler.start_trace(trace_dir)`` wrote.  The window is the host
event named ``SLICE`` (a ``jax.profiler.TraceAnnotation`` that the harness
holds open while it traces), and device events are cut to it; a trace
without that event is read whole, over the ``window_s`` given.  It returns:

- ``busy_s``: the union of the intervals in which a program ran on a
  device (its XLA module events), averaged over the devices that ran any;
- ``window_s``: the traced window;
- ``modules``: for each XLA module (one jitted program), its device
  seconds inside the window and the number of its runs that overlap it;
- ``device_ops``: the ten programs that took the most device time;
- ``idle_gaps``: the ten longest stretches of the window in which no
  program ran, each named by the host event that overlaps it most.

A device is a plane named ``/device:TPU:<n>``; its programs are the events
of its ``XLA Modules`` line.
``benchmark/check_trace.py`` checks the reduction on a trace recorded on
the CPU, where the selectors differ.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]  # start s, end s, name

TOP = 10
SLICE = "benchmark.traced_slice"


def is_tpu_plane(name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


def xplane_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> List[Interval]:
    return [
        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
        for e in line.events
        if e.duration_ns > 0
    ]


def merge(intervals: Sequence[Interval]) -> List[Tuple[float, float]]:
    """Union of intervals, as sorted disjoint (start, end) pairs."""
    out: List[List[float]] = []
    for a, b, _ in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of the intervals that lie inside [lo, hi]."""
    return [(max(a, lo), min(b, hi), n) for a, b, n in intervals if b > lo and a < hi]


def busy(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merge(intervals))


def gaps(intervals: Sequence[Interval], lo: Optional[float] = None,
         hi: Optional[float] = None) -> List[Tuple[float, float]]:
    """The idle stretches between operations, and, where the window's
    bounds ``lo`` and ``hi`` are given, before the first and after the
    last."""
    m = merge(intervals)
    out = [(m[i][1], m[i + 1][0]) for i in range(len(m) - 1)]
    if m and lo is not None and lo < m[0][0]:
        out.insert(0, (lo, m[0][0]))
    if m and hi is not None and m[-1][1] < hi:
        out.append((m[-1][1], hi))
    return out


def module_name(name: str) -> str:
    """``jit_f(123)`` -> ``jit_f``: one program, whatever the run id."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def per_name(intervals: Sequence[Interval], key: Callable[[str], str] = lambda n: n) -> Dict[str, dict]:
    out: Dict[str, dict] = defaultdict(lambda: {"seconds": 0.0, "count": 0})
    for a, b, name in intervals:
        slot = out[key(name)]
        slot["seconds"] += b - a
        slot["count"] += 1
    return dict(out)


def top(totals: Dict[str, dict], n: int = TOP) -> List[list]:
    ranked = sorted(totals.items(), key=lambda kv: -kv[1]["seconds"])
    return [[name, v["seconds"]] for name, v in ranked[:n]]


def name_gaps(idle: Sequence[Tuple[float, float]], host: Sequence[Interval], n: int = TOP) -> List[list]:
    """The ``n`` longest gaps, each named by the host event that overlaps
    it most among those no more than four times its length (an event that
    spans many gaps, such as a whole request, names none of them)."""
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:n]
    out = []
    for g0, g1 in longest:
        length = g1 - g0
        best, best_overlap = "host (no event)", 0.0
        for a, b, name in host:
            overlap = min(b, g1) - max(a, g0)
            if overlap > best_overlap and (b - a) <= 4 * length:
                best, best_overlap = name, overlap
        out.append([best, length])
    return out


def summarize(
    trace_dir: str,
    window_s: float,
    device_plane: Callable[[str], bool] = is_tpu_plane,
    busy_line: Callable[[str], bool] = lambda n: n == "XLA Modules",
    host_plane: Callable[[str], bool] = lambda n: n == "/host:CPU",
) -> Optional[dict]:
    """The reduction described in the module docstring; None where the
    trace holds no device event.  Only the ``busy_line`` of each device is
    read: a TPU's ``XLA Ops`` line holds an event, named by its whole HLO
    instruction, for every operation of every scan step, and reading it
    took minutes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_file(trace_dir))
    device_runs: List[List[Interval]] = []
    host: List[Interval] = []
    for plane in data.planes:
        if device_plane(plane.name):
            plane_runs = [e for line in plane.lines if busy_line(line.name) for e in _events(line)]
            if plane_runs:
                device_runs.append(plane_runs)
        if host_plane(plane.name):
            for line in plane.lines:
                if not busy_line(line.name):
                    host += _events(line)
    marks = [(a, b) for a, b, name in host if name == SLICE]
    lo, hi = marks[0] if marks else (None, None)
    if marks:
        window_s = hi - lo
        device_runs = [clip(r, lo, hi) for r in device_runs]
    cut = [r for r in device_runs if r]
    if not cut:
        return None
    modules = per_name([e for r in cut for e in r], module_name)
    return {
        "busy_s": sum(busy(r) for r in cut) / len(cut),
        "window_s": window_s,
        "devices": len(cut),
        "modules": modules,
        "device_ops": top(modules),
        # read on the first device that ran anything: on one chip, that chip
        "idle_gaps": name_gaps(gaps(cut[0], lo, hi), [e for e in host if e[2] != SLICE]),
    }
