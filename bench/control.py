#!/usr/bin/env python3
"""Readings from which the limits of ``bench/limits.json`` are set.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

In one process, on the chip, for each seed: one window of the cell as
``bench/run.py`` drives it, then the numbers of the comparison twice over
the same event logs: judged by the float64 reference (the program's
readings, the lower ends) and with the reference computed in float32 in the
program's place (the control's readings, the upper ends).  The
configuration's own checks (``bench/checks/``) are judged both ways too,
each given the precision, so each of their limits gets its two readings.
One JSON line per seed on standard output.  The benchmark's own runs never
run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(c: dict, seed: int, seconds: float) -> dict:
    import numpy as np

    from bench import check
    from bench.window import run_window

    w = run_window(c["config"]["spec"], c["traffic"], seed, seconds)
    t0 = time.perf_counter()
    program = check.judge(w.runs, c["config"])
    t1 = time.perf_counter()
    control = check.judge(w.runs, c["config"], dtype=np.float32)
    return {"seed": seed, "runs": len(w.runs), "sim_s": w.sim_s,
            "device_picks": w.device_picks,
            "attempted": program["attempted"], "check_s": t1 - t0,
            "program": dict(program["numbers"], **program["readings"]),
            "control": dict(control["numbers"], **control["readings"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run

    c = run.load_cell(args.workload)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run.device_info(c["cell"]["chips"])
    for s in args.seeds.split(","):
        print(json.dumps(readings(c, int(s), args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
