"""Sweep runner: deterministic reports, serial == multiprocessing, CI
aggregation math."""
import json
import math

import pytest

from repro.api import (
    BidSpec,
    ExperimentSpec,
    MigrationSpec,
    PolicySpec,
    RunSpec,
    ScenarioSpec,
    aggregate_rows,
    mean_ci95,
    run_experiment,
    run_one,
    write_report,
)
from repro.api.sweep import format_report, t_crit95

UNTIL = 1200.0


def _mini_experiment() -> ExperimentSpec:
    """3 seeds × 2 policies over the synthetic scenario (fast, no engine)."""
    return ExperimentSpec(
        name="mini",
        scenario=ScenarioSpec(workload="synthetic", horizon=UNTIL),
        policies=(PolicySpec("first-fit"),
                  PolicySpec("hlem-vmp-adjusted", {"alpha": -0.5})),
        seeds=(0, 1, 2))


def test_mini_sweep_deterministic_report():
    exp = _mini_experiment()
    r1 = run_experiment(exp, processes=0)
    r2 = run_experiment(exp, processes=0)
    assert json.dumps(r1, sort_keys=True) == json.dumps(r2, sort_keys=True)
    assert r1["n_runs"] == 6
    assert [c["policy"] for c in r1["cells"]] == ["first-fit",
                                                  "hlem-vmp-adjusted"]
    for cell in r1["cells"]:
        assert cell["n_seeds"] == 3
        assert [row["seed"] for row in cell["rows"]] == [0, 1, 2]
        m = cell["metrics"]["interruptions"]
        assert m["n"] == 3
        assert m["min"] <= m["mean"] <= m["max"]
        # identifier keys never aggregate
        assert "seed" not in cell["metrics"]
        assert "policy" not in cell["metrics"]


@pytest.mark.parametrize("backend,forks", [("jax", False), ("numpy", True)])
def test_device_backed_sweeps_run_in_process(monkeypatch, backend, forks):
    """A device belongs to one process: a sweep with a ``backend: "jax"``
    job never opens a worker pool; a host-only sweep still forks."""
    import multiprocessing

    from repro.api import sweep
    opened = []
    real = multiprocessing.get_context

    def spy(method=None):
        ctx = real(method)
        opened.append(method)
        return ctx

    monkeypatch.setattr(sweep.multiprocessing, "get_context", spy)
    exp = ExperimentSpec(
        name="device",
        scenario=ScenarioSpec(workload="synthetic", horizon=300.0),
        policies=(PolicySpec("first-fit"),
                  PolicySpec("hlem-vmp", {"backend": backend})),
        seeds=(0, 1))
    report = run_experiment(exp, processes=2)
    assert bool(opened) is forks
    assert report["n_runs"] == 4


def test_sweep_parallel_equals_serial():
    exp = _mini_experiment()
    serial = run_experiment(exp, processes=0)
    parallel = run_experiment(exp, processes=2)
    assert json.dumps(serial, sort_keys=True) == \
        json.dumps(parallel, sort_keys=True)


def test_sweep_rows_match_run_one():
    exp = _mini_experiment()
    report = run_experiment(exp, processes=0)
    cell = report["cells"][1]
    spec = RunSpec(scenario=exp.scenario, policy=exp.policies[1])
    assert cell["rows"][2] == run_one(spec, seed=2, until=UNTIL)


def test_sweep_report_json_artifact(tmp_path):
    exp = _mini_experiment()
    report = run_experiment(exp, processes=0)
    path = write_report(report, str(tmp_path / "report.json"))
    with open(path) as f:
        loaded = json.load(f)
    assert loaded == json.loads(json.dumps(report))
    # the embedded experiment spec round-trips from the artifact
    assert ExperimentSpec.from_dict(loaded["experiment"]) == exp
    assert "first-fit" in format_report(report)


def test_market_sweep_cells_fan_regimes_and_migrations():
    exp = ExperimentSpec(
        name="market-mini",
        scenario=ScenarioSpec(workload="market", regime="volatile",
                              bid=BidSpec("randomized", {"lo": 0.45})),
        policies=(PolicySpec("hlem-vmp-adjusted", {"alpha": -0.5}),),
        migrations=(MigrationSpec(), MigrationSpec("gradient-aware")),
        regimes=("calm", "volatile"),
        seeds=(0, 1))
    report = run_experiment(exp, until=900.0)
    assert [(c["regime"], c["migration"]) for c in report["cells"]] == [
        ("calm", "none"), ("calm", "gradient-aware"),
        ("volatile", "none"), ("volatile", "gradient-aware")]
    for cell in report["cells"]:
        assert {row["seed"] for row in cell["rows"]} == {0, 1}
        assert "realized_spot_cost" in cell["metrics"]


# -- aggregation math ---------------------------------------------------------
def test_mean_ci95_known_values():
    stats = mean_ci95([1.0, 2.0, 3.0])
    assert stats["mean"] == 2.0
    assert stats["n"] == 3
    # sd = 1, se = 1/sqrt(3), t(df=2) = 4.303
    assert stats["ci95"] == pytest.approx(4.303 / math.sqrt(3), abs=1e-6)
    assert stats["min"] == 1.0 and stats["max"] == 3.0


def test_mean_ci95_single_sample_has_zero_ci():
    stats = mean_ci95([5.0])
    assert stats == {"mean": 5.0, "ci95": 0.0, "min": 5.0, "max": 5.0,
                     "n": 1}


def test_t_crit_table():
    assert t_crit95(1) == pytest.approx(12.706)
    assert t_crit95(19) == pytest.approx(2.093)   # the >=20-seed sweeps
    # beyond the table: continuous at the boundary, no drop to 1.96
    assert t_crit95(31) == pytest.approx(t_crit95(30), abs=0.01)
    assert t_crit95(40) == pytest.approx(2.021, abs=0.005)
    assert t_crit95(10_000) == pytest.approx(1.96, abs=0.001)
    # monotone decreasing toward the normal limit
    assert t_crit95(30) > t_crit95(31) > t_crit95(60) > 1.96


def test_aggregate_rows_skips_identifiers_and_non_numeric():
    rows = [
        {"policy": "p", "regime": "calm", "migration": "none", "seed": 0,
         "interruptions": 4, "note": "x", "flag": True},
        {"policy": "p", "regime": "calm", "migration": "none", "seed": 1,
         "interruptions": 6, "note": "y", "flag": False},
    ]
    agg = aggregate_rows(rows)
    assert set(agg) == {"interruptions"}
    assert agg["interruptions"]["mean"] == 5.0


# ---------------------------------------------------------------------------
# PR 5: incremental report writing + crash resume + grid-axis metadata
# ---------------------------------------------------------------------------
def test_report_path_writes_final_report_atomically(tmp_path):
    exp = _mini_experiment()
    path = str(tmp_path / "report.json")
    report = run_experiment(exp, processes=0, report_path=path)
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk == json.loads(json.dumps(report))
    assert "partial" not in on_disk
    assert not (tmp_path / "report.json.tmp").exists()


def test_partial_report_resumes_and_matches_fresh_run(tmp_path, monkeypatch):
    exp = _mini_experiment()
    path = str(tmp_path / "report.json")
    fresh = run_experiment(exp, processes=0)

    # simulate a crash after the first completed cell: a partial file with
    # the prefix of the grid, marked partial
    partial = json.loads(json.dumps(fresh))
    partial["cells"] = partial["cells"][:1]
    partial["partial"] = True
    with open(path, "w") as f:
        json.dump(partial, f)

    calls = []
    import repro.api.sweep as sweep_mod
    real = sweep_mod._run_job

    def counting(job):
        calls.append(job)
        return real(job)

    monkeypatch.setattr(sweep_mod, "_run_job", counting)
    resumed = run_experiment(exp, processes=0, report_path=path)
    # only the second cell's seeds ran; the report is byte-identical
    assert len(calls) == len(exp.seeds)
    assert json.dumps(resumed, sort_keys=True) == \
        json.dumps(fresh, sort_keys=True)
    with open(path) as f:
        assert json.load(f) == json.loads(json.dumps(fresh))


def test_mismatched_partial_is_ignored(tmp_path, monkeypatch):
    exp = _mini_experiment()
    other = exp.replace(seeds=(5, 6, 7))
    path = str(tmp_path / "report.json")
    run_experiment(other, processes=0, report_path=path, until=UNTIL / 2)

    calls = []
    import repro.api.sweep as sweep_mod
    real = sweep_mod._run_job

    def counting(job):
        calls.append(job)
        return real(job)

    monkeypatch.setattr(sweep_mod, "_run_job", counting)
    report = run_experiment(exp, processes=0, report_path=path)
    assert len(calls) == len(exp.cells()) * len(exp.seeds)
    assert json.dumps(report, sort_keys=True) == json.dumps(
        run_experiment(exp, processes=0), sort_keys=True)


def test_resume_false_recomputes(tmp_path, monkeypatch):
    exp = _mini_experiment()
    path = str(tmp_path / "report.json")
    run_experiment(exp, processes=0, report_path=path)
    calls = []
    import repro.api.sweep as sweep_mod
    real = sweep_mod._run_job

    def counting(job):
        calls.append(job)
        return real(job)

    monkeypatch.setattr(sweep_mod, "_run_job", counting)
    run_experiment(exp, processes=0, report_path=path, resume=False)
    assert len(calls) == len(exp.cells()) * len(exp.seeds)


def test_grid_axis_cells_carry_identifying_metadata():
    exp = ExperimentSpec(
        name="axes",
        scenario=ScenarioSpec(workload="market", regime="volatile",
                              bid=BidSpec("randomized", {"lo": 0.45})),
        policies=(PolicySpec("first-fit"),),
        bids=(BidSpec("randomized", {"lo": 0.45}),
              BidSpec("on-demand-cap", {"fraction": 0.7})),
        workload_grid={"fleet_scale": (0.5, 1.0)},
        seeds=(0,))
    report = run_experiment(exp, processes=0, until=600.0)
    assert [(c["bid"]["strategy"], c["workload_params"]["fleet_scale"])
            for c in report["cells"]] == [
        ("randomized", 0.5), ("randomized", 1.0),
        ("on-demand-cap", 0.5), ("on-demand-cap", 1.0)]
    # the full bid spec (params included) identifies the cell: two specs
    # sharing a strategy stay distinguishable
    assert report["cells"][2]["bid"]["params"] == {"fraction": 0.7}
    # inert axes add no cell keys (PR 4 report shape preserved)
    plain = run_experiment(_mini_experiment(), processes=0, until=600.0)
    assert all("bid" not in c and "workload_params" not in c
               for c in plain["cells"])


# ---------------------------------------------------------------------------
# PR 6: fleet axis + fault injection through the sweep runner
# ---------------------------------------------------------------------------
def _resilience_experiment() -> ExperimentSpec:
    from repro.api import FaultSpec, FleetSpec
    return ExperimentSpec(
        name="resilience-mini",
        scenario=ScenarioSpec(workload="market", regime="volatile",
                              n_pools=2, horizon=1800.0),
        policies=(PolicySpec("first-fit"),),
        fleets=(None, FleetSpec(params={"target_capacity": 8.0})),
        faults=FaultSpec("storm", {"first": 600.0, "every": 600.0,
                                   "count": 2, "fraction": 0.5}),
        seeds=(0, 1))


def test_fleet_fault_sweep_parallel_equals_serial():
    """Chaos-determinism through the sweep runner: a fleet axis under
    injected storms produces byte-identical reports serial vs
    multiprocessing."""
    exp = _resilience_experiment()
    serial = run_experiment(exp, processes=0)
    parallel = run_experiment(exp, processes=2)
    assert json.dumps(serial, sort_keys=True) == \
        json.dumps(parallel, sort_keys=True)


def test_fleet_cells_carry_spec_and_resilience_metrics():
    exp = _resilience_experiment()
    report = run_experiment(exp, processes=0)
    baseline, fleet_cell = report["cells"]
    assert baseline["fleet"] is None
    assert fleet_cell["fleet"]["strategy"] == "diversified"
    # resilience columns appear only where a fleet manager ran
    assert "time_below_target_s" not in baseline["metrics"]
    for key in ("time_below_target_s", "shortfall_area", "mean_recovery_s",
                "faults_fired", "fleet_launches", "fleet_spot_cost"):
        assert key in fleet_cell["metrics"], key
    # every cell saw the same number of injected faults
    assert all(r["faults_fired"] == 2 for r in fleet_cell["rows"])
    # the report renders with fleet + recovery columns
    txt = format_report(report)
    assert "per-vm" in txt and "diversified" in txt and "below_tgt_s" in txt
    # inert-axis reports keep the old column set
    assert "below_tgt_s" not in format_report(
        run_experiment(_mini_experiment(), processes=0, until=600.0))
