"""The measured window: drives simulators built from a configuration under a
traffic mix, for a stretch of wall time.

A traffic mix is a file of parameters (``bench/traffic/<name>.json``):

* ``mode`` ``"continuous"``: one simulator, built from ``--seed``, run to
  ``warmup_sim_s`` during set-up, then run in chunks of ``chunk_sim_s``
  simulated seconds until the wall time is up, or earlier where the next
  chunk would pass the configuration's horizon.
* ``mode`` ``"sweep"``: back-to-back runs with the seeds ``seed``,
  ``seed + 1``, ..., each built (inside the window: users pay for it) and
  then run in chunks of ``chunk_sim_s`` to the configuration's horizon.  A
  run cut off by the end of the window counts the simulated seconds it
  reached.  Set-up runs one simulator, built with the seed
  ``seed + WARMUP_SEED_OFFSET``, to ``warmup_sim_s`` and drops it.

Every simulator records its event log, which the check reads after the
window; with ``traced`` the span profiler and the program's counters are on
too, and the window keeps what each took inside it: a continuous mix's
readings less those at the window's start, a sweep's days whole (each is
built inside the window).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: the sweep's warm-up day is built with ``seed + WARMUP_SEED_OFFSET``: far
#: past any day a window reaches, so it never reuses a seed that the window
#: runs and checks
WARMUP_SEED_OFFSET = 1_000_003


@dataclass
class Run:
    """One simulator of the window and how far the window took it."""
    sim: object
    seed: int
    t_start: float            # simulated time at which the window took it up
    t_end: float              # simulated time the window ran it to
    n_hosts0: int = 0         # hosts that existed when it was built
    #: realized spot bill, when the run reached the horizon in the window
    spot_cost: Optional[float] = None


@dataclass
class Window:
    runs: List[Run] = field(default_factory=list)
    wall_s: float = 0.0
    sim_s: float = 0.0
    device_picks: int = 0
    device_fallbacks: int = 0
    #: span ``(cat, name) -> [count, total_s, self_s]`` over the window
    profile: Dict[Tuple[str, str], list] = field(default_factory=dict)
    #: the program's counters ``name -> value`` over the window
    counters: Dict[str, float] = field(default_factory=dict)
    setup_done: float = 0.0   # perf_counter at the first timed chunk
    #: perf_counter at the ends of set-up's steps: ``built``, ``warm``
    marks: Dict[str, float] = field(default_factory=dict)


def _spec(spec_dict: dict, traced: bool):
    from repro.api import RunSpec

    d = dict(spec_dict)
    d["obs"] = {"trace": False, "profile": bool(traced),
                "counters_every": None, "events": True}
    return RunSpec.from_dict(d)


def _picks(sim) -> Tuple[int, int]:
    pol = sim.policy
    return (getattr(pol, "device_picks", 0), getattr(pol, "device_fallbacks",
                                                       0))


def _profile(sim) -> Dict[Tuple[str, str], list]:
    prof = sim.obs.profile() if getattr(sim.obs, "enabled", False) else {}
    return {k: list(v) for k, v in prof.items()}


def _add_profile(into: dict, now: dict, before: Optional[dict] = None):
    for k, v in now.items():
        b = (before or {}).get(k, [0, 0.0, 0.0])
        cur = into.setdefault(k, [0, 0.0, 0.0])
        for i in range(3):
            cur[i] += v[i] - b[i]


def _counters(sim) -> Dict[str, float]:
    on = getattr(sim.obs, "enabled", False)
    return dict(sim.obs.counters.values) if on else {}


def _add_counters(into: dict, now: dict, before: Optional[dict] = None):
    for k, v in now.items():
        into[k] = into.get(k, 0) + v - (before or {}).get(k, 0)


def _horizon(spec) -> float:
    if spec.scenario.horizon is None:
        raise ValueError("a configuration under a sweep mix needs a horizon")
    return float(spec.scenario.horizon)


def run_window(spec_dict: dict, traffic: dict, seed: int, seconds: float,
               traced: bool = False, on_start=None, on_stop=None) -> Window:
    """Set up, then measure for ``seconds`` of wall time.  ``on_start`` and
    ``on_stop`` are called right at the window's edges (the profiler)."""
    import jax
    from repro.api import build
    from repro.market.pricing import realized_cost_stats

    spec = _spec(spec_dict, traced)
    chunk = float(traffic["chunk_sim_s"])
    warm = float(traffic["warmup_sim_s"])
    mode = traffic["mode"]
    annotate = jax.profiler.TraceAnnotation
    w = Window()

    if mode == "continuous":
        sim = build(spec, seed)
        w.marks["built"] = time.perf_counter()
        n0 = sim.pool.n
        sim.run(until=warm)
        w.marks["warm"] = time.perf_counter()
        run = Run(sim, seed, t_start=warm, t_end=warm, n_hosts0=n0)
        w.runs.append(run)
        picks0, prof0, count0 = _picks(sim), _profile(sim), _counters(sim)
        horizon = spec.scenario.horizon
        if on_start:
            on_start()
        w.setup_done = t0 = time.perf_counter()
        with annotate("bench/window"):
            k = 0
            while time.perf_counter() - t0 < seconds:
                k += 1
                target = warm + k * chunk
                if horizon is not None and target > horizon:
                    break     # the trace is used up: the window ends early
                with annotate("bench/chunk"):
                    sim.run(until=target)
                run.t_end = target
        w.wall_s = time.perf_counter() - t0
        if on_stop:
            on_stop()
        p1 = _picks(sim)
        w.device_picks, w.device_fallbacks = (p1[0] - picks0[0],
                                              p1[1] - picks0[1])
        _add_profile(w.profile, _profile(sim), prof0)
        _add_counters(w.counters, _counters(sim), count0)
        w.sim_s = run.t_end - run.t_start
        return w

    if mode != "sweep":
        raise ValueError(f"unknown traffic mode {mode!r}")
    horizon = _horizon(spec)
    warm_sim = build(spec, seed + WARMUP_SEED_OFFSET)
    w.marks["built"] = time.perf_counter()
    warm_sim.run(until=warm)
    del warm_sim
    w.marks["warm"] = time.perf_counter()
    if on_start:
        on_start()
    w.setup_done = t0 = time.perf_counter()
    with annotate("bench/window"):
        day = 0
        while time.perf_counter() - t0 < seconds:
            with annotate("bench/build"):
                sim = build(spec, seed + day)
            run = Run(sim, seed + day, t_start=0.0, t_end=0.0,
                      n_hosts0=sim.pool.n)
            w.runs.append(run)
            day += 1
            k = 0
            while run.t_end < horizon and time.perf_counter() - t0 < seconds:
                k += 1
                target = min(k * chunk, horizon)
                with annotate("bench/chunk"):
                    sim.run(until=target)
                run.t_end = target
            if run.t_end >= horizon and sim.engine is not None:
                with annotate("bench/billing"):
                    run.spot_cost = realized_cost_stats(
                        sim.vms.values(), sim.engine, sim.pool)["spot_cost"]
    w.wall_s = time.perf_counter() - t0
    if on_stop:
        on_stop()
    for run in w.runs:
        p = _picks(run.sim)
        w.device_picks += p[0]
        w.device_fallbacks += p[1]
        _add_profile(w.profile, _profile(run.sim))
        _add_counters(w.counters, _counters(run.sim))
        w.sim_s += run.t_end - run.t_start
    return w
