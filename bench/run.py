#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell, its configuration
(``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<traffic>.json``) are found by name from
``BENCHMARK.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.

1. Set-up: JAX with the persistent compile cache (``repro.compile_cache``),
   a TPU or nothing (the run stops with a non-zero exit and prints no
   result), then the window's own set-up (``bench/window.py``).
2. The window: ``--seconds`` of wall time.  With ``--trace 1`` the span
   profiler and ``jax.profiler`` are on and the per-layer metrics are
   reported instead of the end-to-end ones.
3. After the window: peak device memory, then the comparison with the plain
   reference (``bench/check.py``) and the configuration's own checks
   (``bench/checks/``), each number beside its limit on the last lines of
   standard error and under ``checks`` in the result.

The last line of standard output is the result, one JSON object.
"""
import time

T_PROCESS = time.perf_counter()
#: perf_counter once JAX has found the chip
T_DEVICE = T_PROCESS

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
#: the profiler writes here, inside the checkout, and the run deletes it
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_cell(name: str) -> dict:
    """The cell, its configuration file, its traffic file and its metric
    entries, by name from ``BENCHMARK.json``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "config": json.loads((ROOT / cfg_entry["file"]).read_text()),
        "traffic": json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def device_info(chips: int) -> dict:
    """The devices JAX finds; exits non-zero unless there are ``chips``
    TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s), JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def scorer_rows(window) -> int:
    """Row count of the pool storage the device scorer sees."""
    return max(r.sim.pool.storage_views()[0].shape[0] for r in window.runs)


def run_cell(c: dict, seed: int, seconds: float, traced: bool,
             device: dict) -> dict:
    """Everything after the device check, for the cell ``c`` that
    :func:`load_cell` gives; returns the result object."""
    from bench import check
    from bench import tracefile
    from bench.window import run_window

    import jax

    # programs compiled, and programs found in the persistent cache, in
    # set-up and in the window (the window should compile none)
    phase = ["setup"]
    compiles = {"setup": 0, "window": 0, "after": 0}
    hits = {"setup": 0, "window": 0, "after": 0}

    def count_compile(event, *args, **kwargs):
        if event == COMPILE_EVENT:
            compiles[phase[0]] += 1

    def count_hit(event, *args, **kwargs):
        if event == CACHE_HIT_EVENT:
            hits[phase[0]] += 1
    jax.monitoring.register_event_duration_secs_listener(count_compile)
    jax.monitoring.register_event_listener(count_hit)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no per-call Python events
    opts.host_tracer_level = 1     # the benchmark's annotations
    shutil.rmtree(TRACE_DIR, ignore_errors=True)

    def on_start():
        phase[0] = "window"
        if traced:
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    def on_stop():
        phase[0] = "after"
        if traced:
            jax.profiler.stop_trace()
    w = run_window(c["config"]["spec"], c["traffic"], seed, seconds,
                   traced=traced, on_start=on_start, on_stop=on_stop)
    setup_s = w.setup_done - T_PROCESS
    steps = dict(device=T_DEVICE, **w.marks, window=w.setup_done)
    last, split = T_PROCESS, {}
    for k, t in steps.items():
        split[k] = t - last
        last = t
    print("bench: setup " + json.dumps(
        {"s": split, "compiles": compiles["setup"],
         "cache_hits": hits["setup"]}), file=sys.stderr)
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    device = dict(device, memory_peak_bytes=memory_peak_bytes())

    result = {"correct": False, "attempted": 0, "failed": 0}
    metrics = {}
    if traced:
        trace = tracefile.reduce(tracefile.load(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = {"window_s": w.wall_s, "profile": w.profile,
               "counters": w.counters, "device_picks": w.device_picks,
               "device_fallbacks": w.device_fallbacks, "trace": trace,
               "device_kind": device["kind"], "scorer_rows": scorer_rows(w)}
        for m in c["per_layer"]:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if trace is not None:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
    else:
        # a metric split by the cells that report it (``<name>.<part>``)
        # is the quantity of its first part
        values = {"host_s_per_sim_day": w.wall_s / w.sim_s * 86400.0
                  if w.sim_s > 0 else float("inf"),
                  "peak_rss_mb": rss_mb, "setup_s": setup_s}
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"].split(".")[0]],
                                  "unit": m["unit"]}
    result.update(metrics=metrics, device=device)

    counts = {"device_picks": w.device_picks,
              "device_fallbacks": w.device_fallbacks,
              "runs": len(w.runs), "sim_s": w.sim_s, "wall_s": w.wall_s,
              "compiles": compiles["window"]}
    print("bench: window " + json.dumps(counts), file=sys.stderr)
    try:
        judged = check.judge(w.runs, c["config"])
        print("bench: readings " + json.dumps(judged["readings"]),
              file=sys.stderr)
        checks = check.verdict(judged["numbers"], c["config"])
        result.update(attempted=judged["attempted"], failed=judged["failed"])
        result["correct"] = (w.sim_s > 0 and judged["attempted"] > 0
                             and all(v["ok"] for v in checks.values()))
    except Exception:   # a check that cannot run judges the run wrong
        traceback.print_exc()
        checks = {}
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['ok'] else 'FAILED'}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    cell = load_cell(args.workload)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = device_info(cell["cell"]["chips"])
    global T_DEVICE
    T_DEVICE = time.perf_counter()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
