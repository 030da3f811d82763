"""A configuration's own checks (``bench/checks/``): with none named, every
cell judges as with an empty list; a check that claims a fleet's VMs holds them to its
own part of ``stated`` and lifts the base input rules off them alone; and
every way a check could weaken the comparison judges the run wrong."""
import copy
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import check, run
from bench.tests._small import CELLS, CPU, small_cell

#: check modules written for the tests
TEST_CHECKS = Path(__file__).resolve().parent / "checks"
MARKET = "market-day-4pool.seed-sweep"
FLEET = {"size": [2, 2048, 10, 1024], "spot_bid_of_od_rate": 0.6,
         "od_lease_s": 1800.0}
#: a fleet of 64 CPU units under the storm scenario, on the market day
SEED, HORIZON = 5, 14400.0


@pytest.mark.parametrize("cell", CELLS)
def test_no_checks_judge_as_an_empty_list(cell):
    """A configuration without its ``checks`` key, and with ``"checks":
    []``, judge alike; one that names no check judges like both."""
    from bench.window import run_window

    c = small_cell(cell)
    w = run_window(c["config"]["spec"], c["traffic"], 2_147_483_693, 1.0)
    bare = {k: v for k, v in c["config"].items() if k != "checks"}
    empty = dict(bare, checks=[])
    base = check.judge(w.runs, bare)
    assert base["attempted"] > 0
    assert check.judge(w.runs, empty) == base
    assert check.verdict(base["numbers"], empty) == \
        check.verdict(base["numbers"]) == \
        check.verdict(base["numbers"], bare)
    assert check.limits(empty) == check.limits(bare) == check.limits()
    if not check.check_names(c["config"]):
        assert check.judge(w.runs, c["config"]) == base
        assert check.verdict(base["numbers"], c["config"]) == \
            check.verdict(base["numbers"])
        assert check.limits(c["config"]) == check.limits()


def fleet_config(checks=()):
    c = copy.deepcopy(run.load_cell(MARKET)["config"])
    c["spec"]["fleet"] = {"strategy": "diversified",
                          "params": {"target_capacity": 64.0}}
    c["spec"]["faults"] = {"scenario": "storm", "params": {}}
    c["stated"]["fleet"] = dict(FLEET)
    c["checks"] = list(checks)
    return c


@pytest.fixture(scope="module")
def fleet_day():
    """One whole market day with the fleet under storms, at full size."""
    from repro.api import build
    from repro.market.pricing import realized_cost_stats
    from bench.window import Run, _spec

    sim = build(_spec(fleet_config()["spec"], False), SEED)
    n0 = sim.pool.n
    sim.run(until=HORIZON)
    cost = realized_cost_stats(sim.vms.values(), sim.engine,
                               sim.pool)["spot_cost"]
    return [Run(sim, SEED, 0.0, HORIZON, n_hosts0=n0, spot_cost=cost)]


@pytest.fixture
def checks_dir(monkeypatch, tmp_path):
    """The test checks, in a directory where a test may add more."""
    for f in TEST_CHECKS.iterdir():
        if f.suffix in (".py", ".json"):
            shutil.copy(f, tmp_path / f.name)
    monkeypatch.setattr(check, "CHECKS", tmp_path)
    return tmp_path


def test_fleet_check_holds_the_fleet_and_leaves_the_rest(fleet_day,
                                                         checks_dir):
    kinds = [r[1] for r in fleet_day[0].sim.events.records()]
    storm = [r for r in fleet_day[0].sim.events.records()
             if r[1] == "interrupt" and r[7] == "fault-storm"]
    assert kinds.count("fleet-launch") == 584 and len(storm) == 504
    without = check.judge(fleet_day, fleet_config())
    assert without["numbers"]["input_errors"] == 1154
    got = check.judge(fleet_day, fleet_config(["fleet_units"]))
    assert got["numbers"]["input_errors"] == 0
    assert got["numbers"]["fleet_launch_diff"] == 0
    assert got["failed"] == 0
    assert got["attempted"] == without["attempted"] + 584
    base = {k: v for k, v in got["numbers"].items()
            if k not in ("input_errors", "fleet_launch_diff")}
    assert base == {k: v for k, v in without["numbers"].items()
                    if k != "input_errors"}
    assert got["readings"] == without["readings"]
    v = check.verdict(got["numbers"], fleet_config(["fleet_units"]))
    assert all(x["ok"] for x in v.values()), v
    assert v["fleet_launch_diff"]["limit"] == 0
    ctl = check.judge(fleet_day, fleet_config(["fleet_units"]),
                      dtype=np.float32)
    assert ctl["numbers"]["input_errors"] == 0


def test_a_fleet_vm_of_another_size_is_wrong(fleet_day, checks_dir,
                                             monkeypatch):
    sim = fleet_day[0].sim
    vid = sim.metrics.fleet_spot_ids[-1]
    monkeypatch.setattr(sim.vms[vid], "demand",
                        np.array([4.0, 2048.0, 10.0, 1024.0]))
    cfg = fleet_config(["fleet_units"])
    got = check.judge(fleet_day, cfg)
    assert got["numbers"]["input_errors"] == 1 and got["failed"] >= 1
    assert not all(x["ok"] for x in
                   check.verdict(got["numbers"], cfg).values())


#: checks that would weaken the comparison, and what each raises
BROKEN = {
    "no_limit": (KeyError, '''
def check(run, config, dtype):
    return {"counts": {"fleet_unlimited": 0}}
'''),
    "takes_placement_errors": (ValueError, '''
def check(run, config, dtype):
    return {"counts": {"placement_errors": 0}}
'''),
    "takes_price_rel_err": (ValueError, '''
def check(run, config, dtype):
    return {"widest": {"price_rel_err": 0.0}}
'''),
    "raises": (RuntimeError, '''
def check(run, config, dtype):
    raise RuntimeError("a check that cannot run")
'''),
    "claims_workload": (ValueError, '''
def check(run, config, dtype):
    return {"claims": [0]}
'''),
}


def add_check(directory, name):
    (directory / f"{name}.py").write_text(BROKEN[name][1])
    (directory / f"{name}.limits.json").write_text(
        '{"why": "test", "limits": {}}')


@pytest.mark.parametrize("name", sorted(BROKEN))
def test_a_check_that_would_weaken_the_comparison_is_wrong(
        name, fleet_day, checks_dir):
    add_check(checks_dir, name)
    cfg = fleet_config(["fleet_units", name])
    with pytest.raises(BROKEN[name][0]):
        check.verdict(check.judge(fleet_day, cfg)["numbers"], cfg)


def test_a_limit_of_a_base_number_in_a_check_file_is_an_error(checks_dir):
    (checks_dir / "fleet_units.limits.json").write_text(
        '{"why": "test", "limits": {"price_rel_err": {"limit": 1.0}}}')
    with pytest.raises(ValueError):
        check.limits(fleet_config(["fleet_units"]))


def test_a_claim_where_the_workload_count_is_unstated_is_an_error():
    c = copy.deepcopy(small_cell(CELLS[0])["config"])
    assert check.workload_vms(c["stated"]) is None
    assert check.workload_vms(fleet_config()["stated"]) == 2007
    with pytest.raises(ValueError):
        check._claims(None, c["stated"], "any", [10**9], set())


def test_a_run_whose_check_raises_is_not_correct(checks_dir):
    add_check(checks_dir, "raises")
    c = small_cell(MARKET)
    c["config"]["checks"] = ["raises"]
    res = run.run_cell(c, 2_147_483_711, 0.5, False, CPU)
    assert not res["correct"] and res["checks"] == {}
