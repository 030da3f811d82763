"""Tracer mechanics (self-time, cadence, profile math — driven by a fake
clock so assertions are exact) and Chrome-trace schema validity for real
simulator runs."""
import json

import pytest

from repro.api import (
    MigrationSpec,
    ObsSpec,
    PolicySpec,
    RunSpec,
    ScenarioSpec,
    build,
)
from repro.obs import (
    Tracer,
    chrome_trace,
    profile_report,
    profile_table,
    validate_chrome_trace,
    write_chrome_trace,
)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


# -- span self-time -----------------------------------------------------------
def test_nested_span_self_time():
    clk = FakeClock()
    tr = Tracer(profile=True, clock=clk)
    tr.begin("outer", "parent")
    clk.tick(1.0)
    tr.begin("inner", "child")
    clk.tick(3.0)
    tr.end(sim_t=10.0)            # child: dur 3, self 3
    clk.tick(2.0)
    tr.end(sim_t=10.0)            # parent: dur 6, self 6 - 3 = 3
    spans = {(c, n): (dur, self_t)
             for c, n, _t0, dur, _sim, self_t, _a in tr.spans}
    assert spans[("inner", "child")] == (3.0, 3.0)
    assert spans[("outer", "parent")] == (6.0, 3.0)
    prof = tr.profile()
    assert prof[("outer", "parent")] == [1, 6.0, 3.0]
    assert prof[("inner", "child")] == [1, 3.0, 3.0]


def test_untimed_span_takes_its_enclosing_spans_simulated_time():
    tr = Tracer(clock=FakeClock())
    tr.begin("event-loop", "dispatch")
    tr.begin("allocation", "place")
    tr.begin("allocation", "pick/call")
    tr.end()
    tr.end()                      # untimed too: both wait for dispatch
    tr.begin("allocation", "pick/call")
    tr.end()
    tr.end(sim_t=42.0)
    tr.begin("event-loop", "dispatch")
    tr.begin("allocation", "place")
    tr.end()
    tr.end(sim_t=43.0)
    assert [(n, sim) for _c, n, _t0, _d, sim, _s, _a in tr.spans] == [
        ("pick/call", 42.0), ("place", 42.0), ("pick/call", 42.0),
        ("dispatch", 42.0), ("place", 43.0), ("dispatch", 43.0)]
    assert tr._untimed == 0
    tr.begin("allocation", "place")
    with pytest.raises(ValueError):
        tr.end()


def test_profile_only_mode_keeps_no_records():
    clk = FakeClock()
    tr = Tracer(keep_records=False, profile=True, clock=clk)
    for _ in range(100):
        tr.begin("cat", "site")
        clk.tick(0.5)
        tr.end(sim_t=0.0)
        tr.instant("cat", "mark", 0.0)
    assert tr.spans == [] and tr.instants == []
    assert tr.profile()[("cat", "site")] == [100, 50.0, 50.0]


def test_profile_table_math():
    clk = FakeClock()
    tr = Tracer(keep_records=False, profile=True, clock=clk)
    tr.begin("a", "hot")
    clk.tick(9.0)
    tr.end(0.0)
    tr.begin("b", "cold")
    clk.tick(1.0)
    tr.end(0.0)
    rows = profile_table(tr)
    assert [r["name"] for r in rows] == ["hot", "cold"]   # self desc
    assert rows[0]["self_pct"] == 90.0
    rep = profile_report(tr)
    assert rep["dominant"]["name"] == "hot"
    assert rep["total_self_ms"] == pytest.approx(10000.0)


# -- counters -----------------------------------------------------------------
def test_counter_cadence():
    clk = FakeClock()
    tr = Tracer(counters_every=100.0, clock=clk)
    seen = []
    tr.on_snapshot = lambda t, snap: seen.append(t)
    assert tr.counters_due(0.0)          # first boundary at t=0
    tr.counters.inc("x")
    tr.snapshot(0.0)
    assert not tr.counters_due(99.9)
    assert tr.counters_due(100.0)
    tr.snapshot(250.0, gauges={"g": 7})  # late snapshot re-anchors
    assert not tr.counters_due(299.0)
    assert tr.counters_due(300.0)
    assert seen == [0.0, 250.0]
    (t0, _w0, s0), (t1, _w1, s1) = tr.counters.series
    assert (t0, s0["x"]) == (0.0, 1)
    assert (t1, s1["g"]) == (250.0, 7)


def test_counters_every_validation():
    with pytest.raises(ValueError):
        Tracer(counters_every=0.0)
    with pytest.raises(ValueError):
        Tracer(counters_every=-5.0)


# -- chrome export ------------------------------------------------------------
def _traced_run(seed=3, until=2400.0):
    sim = build(RunSpec(
        scenario=ScenarioSpec(workload="market", regime="volatile"),
        policy=PolicySpec("hlem-vmp-adjusted", {"alpha": -0.5}),
        migration=MigrationSpec("gradient-aware"),
        obs=ObsSpec(trace=True, profile=True, counters_every=600.0)), seed)
    sim.run(until=until)
    return sim


def test_chrome_trace_schema_valid(tmp_path):
    sim = _traced_run()
    doc = write_chrome_trace(sim.obs, str(tmp_path / "t.json"),
                             manifest={"seed": 3})
    assert validate_chrome_trace(doc) == []
    reloaded = json.loads((tmp_path / "t.json").read_text())
    assert validate_chrome_trace(reloaded) == []
    assert reloaded["otherData"] == {"seed": 3}


def test_chrome_trace_dual_clock_tracks():
    doc = chrome_trace(_traced_run().obs)
    evs = doc["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert pids == {1, 2}
    names = {(e["pid"], e["args"]["name"]) for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert names == {(1, "wall-time"), (2, "sim-time")}
    # every span is mirrored on both clocks; sim-time spans carry wall_ms
    xs = [e for e in evs if e["ph"] == "X"]
    assert len([e for e in xs if e["pid"] == 1]) == \
        len([e for e in xs if e["pid"] == 2])
    assert all(e["dur"] == 0 and "wall_ms" in e["args"]
               for e in xs if e["pid"] == 2)
    # counter samples exist for the core live counters
    counter_names = {e["name"] for e in evs if e["ph"] == "C"}
    assert "events/total" in counter_names
    assert "gauge/queue_depth" in counter_names


def test_validator_catches_malformed_events():
    assert validate_chrome_trace({}) != []
    bad = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 1, "name": "n", "cat": "c",
         "ts": -5.0, "dur": 1.0},
        {"ph": "??", "pid": 1, "tid": 1, "name": "n"},
        {"ph": "C", "pid": 9, "tid": 1, "name": "k", "ts": 0,
         "args": {"value": "not-a-number"}},
    ]}
    probs = validate_chrome_trace(bad)
    assert any("bad ts" in p for p in probs)
    assert any("unknown ph" in p for p in probs)
    assert any("not numeric" in p for p in probs)
    assert any("no process_name" in p for p in probs)


# -- expected instrumentation content -----------------------------------------
def test_trace_covers_subsystem_boundaries():
    tr = _traced_run().obs
    cats = {c for c, *_ in tr.spans}
    assert {"event-loop", "market-tick", "market-engine",
            "migration", "allocation"} <= cats
    names = {n for _c, n, *_ in tr.spans}
    assert "dispatch/price-tick" in names
    assert "plan/gradient-aware" in names
    c = tr.counters.values
    assert c["events/total"] > 0 and c["events/price-tick"] > 0
    assert any(k.startswith("interruptions/") for k in c)
    assert c.get("migrations/planned", 0) == c.get("migrations/started", 0)
