"""Tracing is observation-only: at fixed (spec, seed) the metrics row must
be byte-identical whether the run carries no tracer, a constructed-but-off
ObsSpec, or a fully enabled tracer (spans + profile + counters).  This is
the PR-7 overhead contract's correctness half — the perf half lives in
``benchmarks/trace_scale.py`` (``obs/tracing_overhead``)."""
import json

import pytest

from repro.api import (
    FaultSpec,
    FleetSpec,
    MigrationSpec,
    ObsSpec,
    PolicySpec,
    RunSpec,
    ScenarioSpec,
    build,
    run_one,
)

OBS_ON = ObsSpec(trace=True, profile=True, counters_every=600.0)


def _rows(spec_kwargs, seed, until):
    """The run's metrics JSON under: no obs / obs-off / obs-on."""
    out = []
    for obs in (None, ObsSpec(), OBS_ON):
        row = run_one(RunSpec(**spec_kwargs, obs=obs), seed, until=until)
        out.append(json.dumps(row, sort_keys=True))
    return out


def _market_kwargs(**overrides):
    kw = dict(
        scenario=ScenarioSpec(workload="market", regime="volatile"),
        policy=PolicySpec("hlem-vmp-adjusted", {"alpha": -0.5}),
        migration=MigrationSpec("gradient-aware"))
    kw.update(overrides)
    return kw


def test_synthetic_identity():
    plain, off, on = _rows(
        dict(scenario=ScenarioSpec(workload="synthetic"),
             policy=PolicySpec("hlem-vmp-adjusted", {"alpha": -0.5})),
        seed=3, until=1500.0)
    assert plain == off == on


def test_market_migration_identity():
    plain, off, on = _rows(_market_kwargs(), seed=5, until=3600.0)
    assert plain == off == on


def test_fleet_faults_identity():
    plain, off, on = _rows(
        _market_kwargs(
            migration=MigrationSpec("none"),
            fleet=FleetSpec(strategy="diversified",
                            params={"target_capacity": 48.0}),
            faults=FaultSpec(scenario="storm")),
        seed=7, until=3600.0)
    assert plain == off == on


def test_off_spec_builds_plain_untraced_loop():
    # ObsSpec with everything off must not even construct a tracer: the
    # simulator gets NULL_TRACER, whose spans and counters do nothing
    sim = build(RunSpec(**_market_kwargs(), obs=ObsSpec()), 0)
    assert sim.obs.enabled is False
    sim_on = build(RunSpec(**_market_kwargs(), obs=OBS_ON), 0)
    assert sim_on.obs.enabled is True
    # one tracer instance shared by every subsystem
    assert sim_on.policy.tracer is sim_on.obs
    assert sim_on.engine.tracer is sim_on.obs
    assert sim_on.migration.tracer is sim_on.obs


def test_traced_runs_are_deterministic():
    # same spec + seed => identical deterministic view (sim-time ordering,
    # span names, counter values); wall-clock fields are excluded by design
    views = []
    for _ in range(2):
        sim = build(RunSpec(**_market_kwargs(), obs=OBS_ON), 11)
        sim.run(until=3600.0)
        views.append(json.dumps(sim.obs.deterministic_view(),
                                sort_keys=True, default=list))
    assert views[0] == views[1]
