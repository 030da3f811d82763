#!/usr/bin/env python3
"""Bring-up smoke test of the simulator's device paths on one TPU chip.

Run from the repository root, on a machine with a TPU:

    python3 chip_smoke.py

One process, no subprocesses.  The phases run in order and each prints its
result on a line of its own; any failed check raises, so the script exits
non-zero and prints no result line.

1. ``device``  JAX must find a TPU (``jax.devices()[0].platform``); on a
   CPU-only machine the script stops here.  ``JAX_PLATFORMS`` is never set.
2. ``trace``   the paper's 12,600-machine Google-trace workload, built and
   run through ``repro.api`` twice at the same seed: HLEM-VMP-adjusted
   scoring on the device (``backend: "jax"``) and on the host
   (``backend: "numpy"``, the float64 oracle).  Only the horizon is cut.
   Passes when the metrics rows (allocations and every ``spot_stats`` value)
   are equal and the two event logs do not diverge: every placement lands
   on the same host.
3. ``scorer``  on the device run's final cluster state, the float32 device
   scores against the float64 oracle: the error of every score difference
   near the top must stay within a tenth of the bound the device pick
   certifies with (``repro.core.hlem.hlem_scores_tol_jax``).
4. ``kernels`` the two Pallas kernels, compiled (never interpreted), at
   n = 12,600 hosts and B = 256 x 12,600, against the numpy oracles.
5. ``fan``     the float64 Monte-Carlo price fan (8 pools, 1,024 paths,
   1,440 ticks) as one device scan against the numpy step loop.

The last line is ``{"ok": true, "device": {...}}``.  The JAX compilation
cache goes where ``repro.compile_cache`` says: ``JAX_COMPILATION_CACHE_DIR``
if set, else ``<repo>/.jax_cache``; a second run finds its programs there.
"""
from __future__ import annotations

import collections
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
# the paper's cluster (§VII-C); TraceConfig defaults for load_per_machine
# (16) and n_spot (2,000).  The cut: 0.004 of the paper's two days
# (345.6 s of trace), run to that horizon.
N_MACHINES = 12_600
SIM_DAYS = 0.004
HORIZON = SIM_DAYS * 86_400.0
N_KERNEL, B_KERNEL = 12_600, 256
FAN_POOLS, FAN_PATHS, FAN_TICKS = 8, 1_024, 1_440
#: largest relative error of the float64 price fan on the chip against the
#: host loop.  The TPU emulates float64: a TPU v5 lite gave 1.57e-14 (the
#: CPU tests hold 1e-12)
FAN_RTOL = 1e-13
#: float32 tolerance of kernel scores against the float64 oracle
KERNEL_RTOL, KERNEL_ATOL = 1e-4, 1e-5

#: JAX monitoring events counted per phase
COMPILES = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"


def say(phase: str, **fields) -> None:
    print(f"{phase}: " + json.dumps(fields, sort_keys=True, default=str),
          flush=True)


class CompileCounter:
    """Counts JAX compile events; ``delta()`` gives those since last call.

    ``compiles`` counts programs sent to the XLA compiler, including those
    the persistent cache then answers (``cache_hits``)."""

    def __init__(self, jax):
        self.counts = collections.Counter()
        self._seen = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda event, **kw: self.counts.update([event]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: self.counts.update([event]))

    def delta(self) -> dict:
        out = {"compiles": self.counts[COMPILES] - self._seen[COMPILES],
               "cache_hits": self.counts[CACHE_HITS] - self._seen[CACHE_HITS],
               "cache_misses": (self.counts[CACHE_MISSES]
                                - self._seen[CACHE_MISSES])}
        self._seen = self.counts.copy()
        return out


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {what}")


def phase_device(jax) -> dict:
    devs = jax.devices()
    d = devs[0]
    dev = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    say("device", **dev)
    check(d.platform == "tpu", f"no TPU: JAX found {d.platform!r} devices")
    return dev


def run_trace(backend: str):
    from repro.api import ObsSpec, PolicySpec, RunSpec, ScenarioSpec, build
    from repro.api.build import collect_row

    spec = RunSpec(
        scenario=ScenarioSpec(
            workload="trace", horizon=HORIZON,
            workload_params={"n_machines": N_MACHINES,
                             "sim_days": SIM_DAYS}),
        policy=PolicySpec("hlem-vmp-adjusted", {"backend": backend}),
        obs=ObsSpec(events=True))
    t0 = time.perf_counter()
    sim = build(spec, SEED)
    t1 = time.perf_counter()
    metrics = sim.run(until=HORIZON)
    t2 = time.perf_counter()
    return sim, collect_row(sim, metrics, spec, SEED), t1 - t0, t2 - t1


def phase_trace(counter) -> object:
    from repro.obs.diff import first_divergence, format_divergence

    say("trace", n_machines=N_MACHINES, sim_days=SIM_DAYS, horizon_s=HORIZON,
        cut=f"the paper's 2 days -> sim_days {SIM_DAYS}, run to "
            f"{HORIZON} s; n_machines, load_per_machine and n_spot as "
            "configured")
    runs = {}
    for backend in ("jax", "numpy"):
        counter.delta()
        sim, row, build_s, run_s = run_trace(backend)
        runs[backend] = (sim, row)
        stats = {k: v for k, v in row.items()
                 if k not in ("policy", "regime", "migration", "seed")}
        extra = ({"device_picks": sim.policy.device_picks,
                  "device_fallbacks": sim.policy.device_fallbacks}
                 if backend == "jax" else {})
        say(f"trace[{backend}]", build_s=build_s, run_s=run_s,
            vms=len(sim.vms), events=len(sim.events), stats=stats,
            **extra, **counter.delta())
    (sim_jx, row_jx), (sim_np, row_np) = runs["jax"], runs["numpy"]
    div = first_divergence(sim_np.events, sim_jx.events)
    say("trace[compare]", rows_equal=row_jx == row_np,
        event_logs=format_divergence(div, "numpy", "jax"))
    check(row_jx == row_np, "jax and numpy trace rows differ")
    check(div is None, "jax and numpy event logs diverge")
    check(sim_jx.policy.device_picks > 0, "no placement was scored on the "
          "device")
    return sim_jx


def phase_scorer(sim) -> None:
    """Float32 device scores vs the float64 oracle on the final cluster
    state: error of score differences near the top, over sampled demands,
    against the bound the device pick certifies with."""
    import numpy as np

    from repro.core.hlem import hlem_scores_np, hlem_scores_tol_jax

    pool, policy = sim.pool, sim.policy
    rng = np.random.default_rng(SEED)
    vms = list(sim.vms.values())
    worst_err, worst_ratio, n_scored = 0.0, 0.0, 0
    for i in rng.choice(len(vms), size=64, replace=False):
        vm = vms[int(i)]
        mask = pool.direct_mask_into(vm.demand).copy()
        if mask.sum() < 2:
            continue
        alpha = policy._alpha_for(vm)
        s64 = hlem_scores_np(pool.free(), mask, pool.spot_frac_view(), alpha)
        free, spot = pool.storage_views()
        padded = np.zeros(free.shape[0], dtype=bool)
        padded[: mask.size] = mask
        out, tol = hlem_scores_tol_jax(free, padded, spot, np.float32(alpha))
        check(next(iter(out.devices())).platform == "tpu",
              "device scores are not on the TPU")
        s32 = np.asarray(out, dtype=np.float64)[: mask.size]
        b = int(np.argmax(s64))
        near = mask & (s64 >= s64[b] - 1e-2)
        err = float(np.abs((s32 - s32[b]) - (s64 - s64[b]))[near].max())
        worst_err = max(worst_err, err)
        worst_ratio = max(worst_ratio, err / float(tol))
        n_scored += 1
    say("scorer", demands=n_scored, max_diff_err=worst_err,
        max_err_over_tol=worst_ratio)
    check(n_scored > 0, "no demand had two candidate hosts")
    check(worst_ratio <= 0.1,
          "float32 device score error exceeds a tenth of its bound")


def _untied_rows(want, masks):
    """Rows whose top two oracle scores differ by more than the float32
    tolerance (an argmax on a closer tie may go either way)."""
    import numpy as np

    top2 = -np.sort(-np.where(masks, want, -np.inf), axis=1)[:, :2]
    return (top2[:, 0] - top2[:, 1]) > (KERNEL_ATOL
                                        + KERNEL_RTOL * np.abs(top2[:, 0]))


def _timed(fn, *args, **kw):
    out = fn(*args, **kw)
    out.block_until_ready()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        fn(*args, **kw).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return out, best


def phase_kernels(jax, counter) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.hlem import hlem_scores_batch_np, hlem_scores_np
    from repro.kernels.hlem_score import (hlem_score_pallas,
                                          hlem_score_pallas_batch)

    rng = np.random.default_rng(SEED)
    n, b = N_KERNEL, B_KERNEL
    free = rng.uniform(0, 100, (n, 4)).astype(np.float32)
    spot = rng.uniform(0, 1, (n, 4)).astype(np.float32)
    masks = rng.random((b, n)) < 0.7
    alphas = np.where(rng.random(b) < 0.5, -0.5, 0.0).astype(np.float32)
    dev = jax.devices()[0]
    args = [jax.device_put(x, dev) for x in (free, masks, spot, alphas)]
    counter.delta()

    results = {}
    single, t_single = _timed(hlem_score_pallas, args[0], args[1][0],
                              args[2], args[3][0], interpret=False)
    batch, t_batch = _timed(hlem_score_pallas_batch, *args, interpret=False)
    for name, out, want, m, secs in (
            ("hlem_score_pallas", single[None],
             hlem_scores_np(free, masks[0], spot, alphas[0])[None],
             masks[:1], t_single),
            ("hlem_score_pallas_batch", batch,
             hlem_scores_batch_np(free, masks, spot, alphas), masks,
             t_batch)):
        check({d.platform for d in out.devices()} == {"tpu"},
              f"{name} output is not on the TPU")
        got = np.asarray(out, dtype=np.float64)
        err = np.abs(got - want)[m]
        tol = KERNEL_ATOL + KERNEL_RTOL * np.abs(want[m])
        untied = _untied_rows(want, m)
        argmax_ok = bool((got.argmax(1) == want.argmax(1))[untied].all())
        within_tol = bool((err <= tol).all())
        results[name] = dict(shape=list(got.shape),
                             max_abs_err=float(err.max()),
                             within_tol=within_tol,
                             untied_rows=int(untied.sum()),
                             argmax_equal=argmax_ok, best_call_s=secs)
        check(within_tol, f"{name} scores off the oracle")
        check(argmax_ok, f"{name} argmax differs on an untied row")
        check(bool((got[~m] <= -1e37).all()), f"{name} scores a masked host")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", "not reported")
    say("kernels", **results, peak_bytes_in_use=peak, **counter.delta())


def phase_fan(jax, counter) -> None:
    import numpy as np

    from repro.api import ScenarioSpec
    from repro.api.build import build_engine
    from repro.market.risk import simulated_price_fan

    engine = build_engine(ScenarioSpec(workload="market", regime="volatile",
                                       n_pools=FAN_POOLS), SEED)
    util_rng = np.random.default_rng(SEED)

    class _Cluster:  # seeded pool utilizations driving the warm-up ticks
        def pool_cpu_utilization(self):
            return util_rng.uniform(0.3, 0.9, FAN_POOLS)

    for k in range(30):
        engine.tick(_Cluster(), engine.config.tick_interval * k)
    counter.delta()
    fans = {}
    for backend in ("jax", "numpy"):
        t0 = time.perf_counter()
        fans[backend] = simulated_price_fan(
            engine, n_ticks=FAN_TICKS, n_paths=FAN_PATHS, seed=SEED,
            backend=backend)
        fans[backend + "_s"] = time.perf_counter() - t0
    got, want = fans["jax"], fans["numpy"]
    rel = float((np.abs(got - want) / np.abs(want)).max())
    say("fan", shape=list(got.shape), max_rel_err=rel, rtol=FAN_RTOL,
        jax_s=fans["jax_s"], numpy_s=fans["numpy_s"],
        finite=bool(np.isfinite(got).all()), **counter.delta())
    check(bool(np.isfinite(got).all()), "non-finite fan quantiles")
    check(rel <= FAN_RTOL, f"price fan relative error {rel} > {FAN_RTOL}")


def main() -> int:
    t_start = time.perf_counter()
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    counter = CompileCounter(jax)
    dev = phase_device(jax)
    say("cache", dir=cache_dir)
    sim = phase_trace(counter)
    phase_scorer(sim)
    phase_kernels(jax, counter)
    phase_fan(jax, counter)
    say("done", wall_s=time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
