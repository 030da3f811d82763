"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

A trace is read into plain data, ``[{"name": plane, "lines": [{"name":
line, "events": [(name, start_ns, duration_ns), ...]}]}]``, so the
reduction runs the same on a trace from the chip and on a small synthetic
one in the tests.

* The window is the host annotation ``bench/window`` that the benchmark
  puts around its measured window; ``window_s`` is its length.
* Device operations are the events on each device plane's ``XLA Ops`` line;
  ``busy_s`` is the union of their intervals inside the window, averaged
  over the device planes.
* Programs are the events on the ``XLA Modules`` line, by name.
* An idle gap is a stretch of the window in which no operation runs on the
  first device; it is named by the innermost ``bench/*`` host annotation
  that covers its middle.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str) -> List[dict]:
    """The newest ``.xplane.pb`` under ``trace_dir``, as plain data."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(ev.name, float(ev.start_ns),
                                    float(ev.duration_ns))
                                   for ev in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def _device_planes(planes: List[dict]) -> List[dict]:
    return [p for p in planes if p["name"].startswith("/device:")
            and any(ln["name"] == OPS_LINE for ln in p["lines"])]


def _events(plane: dict, line: str) -> List[Tuple[str, float, float]]:
    return [ev for ln in plane["lines"] if ln["name"] == line
            for ev in ln["events"]]


def _annotations(planes: List[dict]) -> List[Tuple[str, float, float]]:
    return [ev for p in planes if p["name"].startswith("/host:")
            for ln in p["lines"] for ev in ln["events"]
            if ev[0].startswith("bench/")]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(events, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s < hi and s + d > lo]


def reduce(planes: List[dict], top: int = 10) -> Optional[dict]:
    """Busy and window seconds, programs, top operations and idle gaps; None
    when the trace holds no window or no device."""
    notes = _annotations(planes)
    wins = [ev for ev in notes if ev[0] == WINDOW]
    devices = _device_planes(planes)
    if not wins or not devices:
        return None
    lo = min(s for _, s, _ in wins)
    hi = max(s + d for _, s, d in wins)
    busy = []
    ops: Dict[str, float] = defaultdict(float)
    modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for dev in devices:
        evs = _events(dev, OPS_LINE)
        busy.append(sum(b - a for a, b in _union(_clip(evs, lo, hi))))
        for name, s, d in evs:
            if s < hi and s + d > lo:
                # "%fusion.3 = f32[...] fusion(...)": the op's own name
                ops[name.split(" = ")[0]] += (min(s + d, hi)
                                              - max(s, lo)) * 1e-9
        for name, s, d in _events(dev, MODULES_LINE):
            if s < hi and s + d > lo:
                m = modules[name]
                m[0] += 1
                m[1] += (min(s + d, hi) - max(s, lo)) * 1e-9
    merged = _union(_clip(_events(devices[0], OPS_LINE), lo, hi))
    gaps, edge = [], lo
    for a, b in merged + [(hi, hi)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    inner = [ev for ev in notes if ev[0] != WINDOW]

    def label(a: float, b: float) -> str:
        mid = 0.5 * (a + b)
        cover = [ev for ev in inner if ev[1] <= mid <= ev[1] + ev[2]]
        return min(cover, key=lambda ev: ev[2])[0] if cover else WINDOW

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "devices": len(devices),
        "modules": {k: {"calls": int(v[0]), "seconds": v[1]}
                    for k, v in modules.items()},
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label(a, b), (b - a) * 1e-9] for a, b in gaps[:top]],
    }
