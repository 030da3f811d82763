"""Event flight recorder: columnar log, round-trips, validation, and the
observation-only invariant — at fixed (spec, seed) the metrics row must be
byte-identical whether the run records the event log or not, and a log-off
run must keep the inert recorder (the PR 7 overhead contract extended to
ISSUE 8's recorder)."""
import json

import numpy as np
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
from repro.obs import (
    EVENT_KINDS,
    NULL_RECORDER,
    EventLog,
    first_divergence,
    load_event_log,
    read_manifest,
    validate_event_log,
)

EVENTS_ON = ObsSpec(events=True)


def _market_kwargs(**overrides):
    kw = dict(
        scenario=ScenarioSpec(workload="market", regime="volatile"),
        policy=PolicySpec("hlem-vmp-adjusted", {"alpha": -0.5}),
        migration=MigrationSpec("gradient-aware"))
    kw.update(overrides)
    return kw


# ---------------------------------------------------------------------------
# the log itself
# ---------------------------------------------------------------------------
def test_emit_and_columns():
    log = EventLog()
    log.emit(0.0, "submit", vm=1, a=0.5, aux="spot")
    log.emit(1.0, "start", vm=1, pool=2, host=7, a=0.5)
    log.emit(2.0, "interrupt", vm=1, pool=2, host=7, aux="price")
    assert len(log) == 3
    arr = log.to_arrays()
    assert arr["t"].tolist() == [0.0, 1.0, 2.0]
    assert [str(arr["kinds"][k]) for k in arr["kind"]] == [
        "submit", "start", "interrupt"]
    assert arr["vm"].tolist() == [1, 1, 1]
    assert arr["pool"].tolist() == [-1, 2, 2]
    # aux interning: "spot" and "price" present, None rows are -1
    assert log.aux_id("spot") >= 0 and log.aux_id("price") >= 0
    assert arr["aux"][1] == -1
    assert log.kind_id("never-emitted") == -1
    assert log.aux_id("never-emitted") == -1


def test_window_drops_out_of_range_events():
    log = EventLog(t_min=10.0, t_max=20.0)
    log.emit(5.0, "start", vm=1)
    log.emit(10.0, "start", vm=2)
    log.emit(19.9, "start", vm=3)
    log.emit(20.0, "start", vm=4)    # t_max is exclusive
    assert [r[2] for r in log.records()] == [2, 3]


@pytest.mark.parametrize("ext", ["ndjson", "npz"])
def test_round_trip(tmp_path, ext):
    log = EventLog()
    log.emit(0.0, "submit", vm=3, a=0.123456789012345, aux="spot")
    log.emit(0.5, "price-tick", pool=1, a=1.0 / 3.0)
    log.emit(1.5, "wave", pool=1, a=0.9, b=4.0)
    path = str(tmp_path / f"log.{ext}")
    log.save(path, manifest={"seed": 42})
    back = load_event_log(path)
    # bit-identity through the round-trip: exact tuple equality
    assert first_divergence(log, back) is None
    assert read_manifest(path) == {"seed": 42}
    assert validate_event_log(path) == []


def test_validate_catches_problems(tmp_path):
    log = EventLog()
    log.emit(5.0, "start", vm=1)
    log.emit(3.0, "no-such-kind", vm=2)      # time backwards + bad kind
    log.emit(4.0, "wave", a=float("inf"))    # non-finite payload
    problems = validate_event_log(log)
    assert any("unknown event kind" in p for p in problems)
    assert any("time goes backwards" in p for p in problems)
    assert any("not finite" in p for p in problems)
    # a real run's log is clean
    sim = build(RunSpec(**_market_kwargs(), obs=EVENTS_ON), 0)
    sim.run(until=1800.0)
    assert validate_event_log(sim.events) == []
    # and every recorded kind is in the public vocabulary
    assert set(sim.events.to_arrays()["kinds"]) <= set(EVENT_KINDS)


def test_null_recorder_is_inert():
    assert NULL_RECORDER.enabled is False
    NULL_RECORDER.emit(0.0, "start", vm=1)
    assert len(NULL_RECORDER) == 0
    assert list(NULL_RECORDER.records()) == []


# ---------------------------------------------------------------------------
# observation-only invariant (metrics byte-identity, three regimes)
# ---------------------------------------------------------------------------
def _rows(spec_kwargs, seed, until):
    out = []
    for obs in (None, ObsSpec(), EVENTS_ON):
        row = run_one(RunSpec(**spec_kwargs, obs=obs), seed, until=until)
        out.append(json.dumps(row, sort_keys=True))
    return out


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


def test_events_only_spec_keeps_plain_loop():
    # events alone must NOT build a tracer: the simulator keeps NULL_TRACER,
    # whose spans and counters do nothing — recording rides inside the
    # ordinary handlers
    sim = build(RunSpec(**_market_kwargs(), obs=EVENTS_ON), 0)
    assert sim.obs.enabled is False
    assert sim.events.enabled is True
    # one recorder shared by every subsystem
    assert sim.engine.events is sim.events
    assert sim.migration.events is sim.events
    # off spec leaves the inert singleton everywhere
    sim_off = build(RunSpec(**_market_kwargs(), obs=ObsSpec()), 0)
    assert sim_off.events is NULL_RECORDER
    assert sim_off.engine.events is NULL_RECORDER


def test_recorded_runs_are_deterministic():
    logs = []
    for _ in range(2):
        sim = build(RunSpec(**_market_kwargs(), obs=EVENTS_ON), 11)
        sim.run(until=3600.0)
        logs.append(sim.events)
    assert len(logs[0]) > 0
    assert first_divergence(logs[0], logs[1]) is None


def test_fleet_fault_kinds_recorded():
    sim = build(RunSpec(
        **_market_kwargs(
            migration=MigrationSpec("none"),
            fleet=FleetSpec(strategy="diversified",
                            params={"target_capacity": 48.0}),
            faults=FaultSpec(scenario="storm")),
        obs=EVENTS_ON), 7)
    sim.run(until=7200.0)
    kinds = set(str(k) for k in sim.events.to_arrays()["kinds"])
    assert "fault" in kinds
    assert "fleet-launch" in kinds
    assert "price-tick" in kinds
