"""The readers of the program's own spans, and ``bench/explain.py``'s
readings of the program's spans on the profiler's clock and of its
counters."""
import json
import types

import pytest

from bench import explain, run, tracefile, window
from bench.tests._small import CELLS, CPU, small_cell
from bench.tests.test_bench_tracefile import synthetic


def with_program_spans():
    """The synthetic trace with the program's mirrored spans on the host
    plane's thread: a dispatch over both later gaps, a placement inside it
    over the middle one, a pick that ends before any gap's middle; the
    first gap lies under no program span."""
    host, dev, meta = synthetic()
    host["lines"].append({"name": "python3 (program)", "events": [
        ("repro/event-loop/dispatch/vm-submit", 3_000, 8_000),
        ("repro/allocation/place", 5_000, 1_500),
        ("repro/allocation/pick/call", 5_000, 500)]})
    dev["lines"].append({"name": "XLA Modules (noise)", "events": [
        ("repro/not-a-host-span", 1_000, 10_000)]})
    return [host, dev, meta]


def test_idle_by_span_puts_each_gap_under_the_innermost_program_span():
    planes = with_program_spans()
    by = explain.idle_by_span(planes)
    assert by == pytest.approx({
        "repro/event-loop/dispatch/vm-submit": 3.3e-6,
        "(none)": 3.0e-6,
        "repro/allocation/place": 2.2e-6})
    assert list(by) == ["repro/event-loop/dispatch/vm-submit", "(none)",
                        "repro/allocation/place"]
    r = tracefile.reduce(planes)
    assert sum(by.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    # the gaps are the reduction's, which the program's spans leave alone
    assert sorted(g[1] for g in r["idle_gaps"]) == pytest.approx(sorted(
        (b - a) * 1e-9 for a, b in explain.idle_gaps(planes)))
    assert r["idle_gaps"] == tracefile.reduce(synthetic())["idle_gaps"]
    assert explain.idle_by_span(synthetic()) == pytest.approx(
        {"(none)": 8.5e-6})
    assert list(explain.idle_by_span(planes, top=1)) == [
        "repro/event-loop/dispatch/vm-submit"]
    host, dev, meta = planes
    assert explain.idle_by_span([host, meta]) == {}


def _fixed_ctx():
    prof = {("event-loop", "dispatch/vm-submit"): [10, 3.0, 1.5],
            ("allocation", "place"): [8, 1.6, 0.4],
            ("allocation", "pick/call"): [8, 0.8, 0.8],
            ("allocation", "pick/readback"): [8, 0.2, 0.2],
            ("allocation", "pick/host-exact"): [2, 0.2, 0.2],
            ("allocation", "flush/batched"): [5, 1.0, 0.5],
            ("allocation", "flush/memo"): [6, 0.25, 0.25],
            ("allocation", "flush/feasibility"): [4, 0.1, 0.1],
            ("market-tick", "tick/wave"): [2, 0.2, 0.2]}
    return {"profile": prof, "window_s": 5.0, "device_picks": 8,
            "device_fallbacks": 2, "trace": tracefile.reduce(synthetic()),
            "scorer_rows": 256, "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("metric,value", [
    ("placement_us", 2e5),
    ("placement_us.sweep", 2e5),
    ("pick_call_us", 1e5),
    ("pick_readback_us", 2.5e4),
    ("host_exact_pick_share", 4.0),
    ("flush_memo_share", 5.0),
    ("flush_feasibility_share", 2.0),
])
def test_program_span_and_counter_readers(metric, value):
    assert run.reader(metric)(_fixed_ctx()) == pytest.approx(value)


@pytest.mark.parametrize("metric", [
    "placement_us", "placement_us.sweep", "pick_call_us", "pick_readback_us",
    "host_exact_pick_share", "flush_memo_share", "flush_feasibility_share"])
def test_program_readers_read_nothing_from_a_program_without_them(metric):
    # the spans a program before them does not have
    ctx = _fixed_ctx()
    ctx["profile"] = {k: v for k, v in ctx["profile"].items()
                      if k[1] in ("dispatch/vm-submit", "flush/batched",
                                  "tick/wave")}
    assert run.reader(metric)(ctx) is None


def _window(counters, picks):
    return types.SimpleNamespace(counters=counters, device_picks=picks)


def test_program_counters_are_ratios_over_the_windows_runs():
    c = {}
    window._add_counters(c, {"flush/batch_calls": 3, "flush/batch_rows": 20,
                             "pick/h2d_bytes": 10 * 8452})
    # a continuous mix's run: what it counted before the window is left out
    window._add_counters(c, {"flush/batch_calls": 2, "flush/batch_rows": 9,
                             "pick/h2d_bytes": 7 * 8452},
                         {"flush/batch_calls": 1, "flush/batch_rows": 3,
                          "pick/h2d_bytes": 8452})
    assert c == {"flush/batch_calls": 4, "flush/batch_rows": 26,
                 "pick/h2d_bytes": 16 * 8452}
    assert explain.program_counters(_window(c, 16)) == pytest.approx(
        {"flush_batch_rows": 6.5, "pick_h2d_bytes": 8452.0})
    # a program without the counters, or a window without a tracer
    assert explain.program_counters(_window(
        {"alloc/batch_calls": 4, "alloc/batch_rows": 26}, 10)) == {}
    assert explain.program_counters(_window({}, 10)) == {}


@pytest.mark.parametrize("cell", CELLS)
def test_a_reader_sees_the_window_counters_that_explain_prints(
        cell, monkeypatch, tmp_path):
    """A per-layer reader gets ``ctx["counters"]``: the counts behind
    ``bench/explain.py``'s ratios, taken over the window alone."""
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    seen = []

    def reader(metric):
        return seen.append
    monkeypatch.setattr(run, "reader", reader)
    with explain.explaining() as held:
        run.run_cell(small_cell(cell), 23, 0.5, True, CPU)
    w = held[0]
    ctx = seen[0]
    c = ctx["counters"]
    assert c == w.counters and c["pick/h2d_bytes"] > 0
    from_ctx = {"pick_h2d_bytes": c["pick/h2d_bytes"] / ctx["device_picks"]}
    if c.get("flush/batch_calls", 0) > 0:
        from_ctx["flush_batch_rows"] = (c["flush/batch_rows"]
                                        / c["flush/batch_calls"])
    assert explain.program_counters(w) == from_ctx
    whole = {}
    for r in w.runs:
        window._add_counters(whole, r.sim.obs.counters.values)
    if small_cell(cell)["traffic"]["mode"] == "continuous":
        # the warm-up's picks are the simulator's, not the window's
        assert 0 < c["pick/h2d_bytes"] < whole["pick/h2d_bytes"]
        assert 0 < ctx["device_picks"] < w.runs[0].sim.policy.device_picks
    else:
        assert c == whole


def test_explaining_prints_idle_by_span_and_holds_the_window(
        capsys, monkeypatch, tmp_path):
    # a trace directory of its own: every run_cell clears the shared one
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    reduce = tracefile.reduce
    with explain.explaining() as held:
        res = run.run_cell(small_cell("market-day-4pool.seed-sweep"), 17,
                           0.5, True, CPU)
    assert tracefile.reduce is reduce
    assert res["correct"]
    err = capsys.readouterr().err
    line = [ln for ln in err.splitlines()
            if ln.startswith("bench: idle_by_span ")]
    assert len(line) == 1
    json.loads(line[0].split(" ", 2)[2])
    assert len(held) == 1 and held[0].profile
    got = explain.program_counters(held[0])
    assert got["pick_h2d_bytes"] > 0
    # a window this short may end before the first wave's flush; a counted
    # matrix holds two queued VMs or more
    assert got.get("flush_batch_rows", 2) >= 2
