#!/usr/bin/env python3
"""Time both layouts of the float64 HLEM pick on the host it runs on.

Run from the repository root:

    PYTHONPATH=src python3 tools/host_pick_bench.py [--m M ...] [--repeats N]

For each candidate count m (40 to 16,384 by default), a pool of
m + m // 3 hosts of three machine sizes, each partly used, with spot
fractions, as a trace run holds them; m of them are candidates, and alpha
is -0.5 as in the trace cell's policy.  ``hlem_pick_candidates_np`` picks
with its cutover above m (``rows``: numpy's reductions over axis 0 of the
(m, 4) candidate rows) and at 0 (``columns``: min and max along a copy
laid out a row per dimension, the sums as ``einsum``), the two
interleaved, ``--repeats`` times each.  One JSON
line per m gives each layout's median and quartiles in microseconds and
whether both picked the same host.  A last line gives ``crossover``, the
smallest m measured from which the column layout is faster at every
larger m measured, beside the module's ``PICK_NP_M_CUTOVER``.

It uses no accelerator and runs on no benchmark cell's path.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import numpy as np

from repro.core import hlem

SIZES = [40, 64, 128, 256, 512, 1024, 1536, 2048, 3072, 4096, 8192, 12600,
         16384]
#: the cutover of each layout's call: never the columns, always them
LAYOUTS = {"rows": 2 ** 62, "columns": 0}


def candidates(m: int, seed: int):
    """(free, idx, spot_frac) of a pool whose m candidates ``idx`` are
    drawn from m + m // 3 hosts."""
    rng = np.random.default_rng(seed)
    n = m + m // 3
    cap = rng.choice([16.0, 32.0, 64.0], size=n)[:, None] * np.array(
        [1.0, 1_536.0, 625.0, 25_000.0])
    free = cap * rng.uniform(0.0, 1.0, (n, 4))
    spot = rng.uniform(0.0, 0.5, (n, 4))
    idx = np.sort(rng.permutation(n)[:m])
    return free, idx, spot


def _us(xs):
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    return {"median": q2, "q1": q1, "q3": q3}


def measure(m: int, repeats: int, seed: int, alpha: float = -0.5) -> dict:
    free, idx, spot = candidates(m, seed)
    times = {name: [] for name in LAYOUTS}
    picks = {}
    for _ in range(repeats):
        for name, cut in LAYOUTS.items():
            t0 = time.perf_counter_ns()
            picks[name] = hlem.hlem_pick_candidates_np(free, idx, spot,
                                                       alpha, cut)
            times[name].append((time.perf_counter_ns() - t0) / 1e3)
    return {"m": m, **{name: _us(ts) for name, ts in times.items()},
            "same_pick": picks["rows"] == picks["columns"]}


def crossover(lines) -> int | None:
    """The smallest m from which ``columns`` is faster at every larger m
    measured, or None where it is slower at the largest."""
    best = None
    for line in sorted(lines, key=lambda r: -r["m"]):
        if line["columns"]["median"] >= line["rows"]["median"]:
            break
        best = line["m"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--m", type=int, nargs="+", default=SIZES)
    ap.add_argument("--repeats", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if min(args.m) < 2 or args.repeats < 2:
        ap.error("every --m needs at least 2 candidates, --repeats 2 runs")
    lines = []
    for m in args.m:
        lines.append(measure(m, args.repeats, args.seed + m))
        print(json.dumps(lines[-1]), flush=True)
    print(json.dumps({"crossover": crossover(lines),
                      "PICK_NP_M_CUTOVER": hlem.PICK_NP_M_CUTOVER,
                      "repeats": args.repeats}))
    return 0 if all(line["same_pick"] for line in lines) else 1


if __name__ == "__main__":
    raise SystemExit(main())
