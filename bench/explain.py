#!/usr/bin/env python3
"""Run one benchmark cell as ``bench/run.py`` does, and print on standard
error what the program's own spans and counters say of the window:

    python3 bench/explain.py --workload <cell> --seed <n> --seconds <s> --trace 1

Arguments, result line and exit code are ``bench/run.py``'s.  Besides its
lines, a traced run prints

* ``bench: idle_by_span {...}``: the seconds of every idle gap of the
  window on the first device, put down to the innermost ``repro/*`` host
  annotation (the program's spans, mirrored onto the profiler's clock by
  ``repro.obs.tracer``) that covers the gap's middle, ``(none)`` where none
  does; summed by name, the largest ten;
* ``bench: program_counters {...}``: ``flush_batch_rows``, the mean number
  of queued VMs B in one feasibility matrix of the batched flush
  (``flush/batch_rows`` over ``flush/batch_calls``), and ``pick_h2d_bytes``,
  the bytes of the scorer's host arguments that cross to the device per
  device pick (``pick/h2d_bytes`` over the policy's ``device_picks``).
  Both are ratios over the window (``Window.counters``, the counts the
  per-layer readers get as ``ctx["counters"]``), a continuous mix's warm-up
  left out; each is left out where its denominator is 0.

No entry of ``BENCHMARK.json`` names these readings, so they are not
metrics of the benchmark.
"""
import contextlib
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
if not __package__:   # run as a script
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, tracefile, window  # noqa: E402

PROGRAM = "repro/"
NO_SPAN = "(none)"


def idle_gaps(planes: List[dict]) -> List[Tuple[float, float]]:
    """Every stretch of the ``bench/window`` annotation in which no
    operation runs on the first device, ``(start, end)`` in ns, as
    ``tracefile.reduce`` finds them; [] where the trace holds no window or
    no device."""
    wins = [ev for ev in tracefile._annotations(planes)
            if ev[0] == tracefile.WINDOW]
    devices = tracefile._device_planes(planes)
    if not wins or not devices:
        return []
    lo = min(s for _, s, _ in wins)
    hi = max(s + d for _, s, d in wins)
    ops = tracefile._events(devices[0], tracefile.OPS_LINE)
    gaps, edge = [], lo
    for a, b in tracefile._union(tracefile._clip(ops, lo, hi)) + [(hi, hi)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    return gaps


def idle_by_span(planes: List[dict], top: int = 10) -> Dict[str, float]:
    """Idle seconds by the innermost program annotation over each gap's
    middle: one sweep over the gaps in time order with a stack of the
    annotations begun by then, the latest begun on top."""
    notes = sorted((ev for p in planes if p["name"].startswith("/host:")
                    for ln in p["lines"] for ev in ln["events"]
                    if ev[0].startswith(PROGRAM)),
                   key=lambda ev: (ev[1], -ev[2]))
    by: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[str, float, float]] = []
    k = 0
    for a, b in idle_gaps(planes):
        mid = 0.5 * (a + b)
        while k < len(notes) and notes[k][1] <= mid:
            stack.append(notes[k])
            k += 1
        # nested annotations: the latest begun that is still open is the
        # innermost
        while stack and stack[-1][1] + stack[-1][2] < mid:
            stack.pop()
        by[stack[-1][0] if stack else NO_SPAN] += (b - a) * 1e-9
    return dict(sorted(by.items(), key=lambda kv: -kv[1])[:top])


def program_counters(w) -> Dict[str, float]:
    """The two counter ratios of the module docstring, from the window
    ``w`` that ``bench.window.run_window`` returns."""
    c = w.counters
    out = {}
    if c.get("flush/batch_calls", 0) > 0:
        out["flush_batch_rows"] = (c.get("flush/batch_rows", 0)
                                   / c["flush/batch_calls"])
    if c.get("pick/h2d_bytes", 0) > 0 and w.device_picks > 0:
        out["pick_h2d_bytes"] = c["pick/h2d_bytes"] / w.device_picks
    return out


@contextlib.contextmanager
def explaining():
    """Within it, ``bench.run`` prints ``idle_by_span`` as it reduces a
    trace; yields the list that collects each window it runs."""
    held = []
    reduce, run_window = tracefile.reduce, window.run_window

    def reduce_and_print(planes, top=10):
        print("bench: idle_by_span " + json.dumps(idle_by_span(planes, top)),
              file=sys.stderr)
        return reduce(planes, top)

    def run_and_hold(*args, **kwargs):
        held.append(run_window(*args, **kwargs))
        return held[-1]
    tracefile.reduce, window.run_window = reduce_and_print, run_and_hold
    try:
        yield held
    finally:
        tracefile.reduce, window.run_window = reduce, run_window


def main(argv=None) -> int:
    with explaining() as held:
        rc = run.main(argv)
    for w in held:
        if w.profile:   # a traced window
            print("bench: program_counters "
                  + json.dumps(program_counters(w)), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
