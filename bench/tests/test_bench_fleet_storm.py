"""The fleet-under-storms check (``bench/checks/fleet-storm.py``) on the
market day with a diversified fleet of 64 CPU units under the storm
scenario, at full size on the CPU: the program's day judges with every
number 0, and each program fault planted here is caught by its own
number."""
import copy
import dataclasses
from collections import Counter

import numpy as np
import pytest

from bench import check, run
from bench.tests._small import CPU

MARKET = "market-day-4pool.seed-sweep"
SEED, HORIZON = 5, 14400.0
#: a fault run stops here: past all three storms
FAULT_UNTIL = 9000.0
FLEET = {"strategy": "diversified", "target_capacity": 64.0,
         "size": [2, 2048, 10, 1024], "pool_weights": [1, 1, 1, 1],
         "spot_bid_of_od_rate": 0.6,
         "ladder": [["same-pool", 2], ["cheaper-pool", 2], ["on-demand", 1],
                    ["queue", 2], ["scale-down", 1]],
         "backoff_s": {"base": 60.0, "mult": 2.0, "cap": 960.0},
         "od_lease_s": 1800.0}
STORMS = {"scenario": "storm", "first_s": 3600.0, "every_s": 2400.0,
          "count": 3, "fraction": 0.5, "pools": "all"}
NUMBERS = ("storm_victim_diff", "fleet_rung_errors", "fleet_backoff_errors",
           "fleet_pool_errors")


def fleet_config():
    c = copy.deepcopy(run.load_cell(MARKET)["config"])
    c["spec"]["fleet"] = {"strategy": "diversified",
                          "params": {"target_capacity": 64.0}}
    c["spec"]["faults"] = {"scenario": "storm", "params": {}}
    c["stated"]["fleet"] = copy.deepcopy(FLEET)
    c["stated"]["faults"] = dict(STORMS)
    c["checks"] = ["fleet-storm"]
    return c


def day(until):
    from repro.api import build
    from repro.market.pricing import realized_cost_stats
    from bench.window import Run, _spec

    sim = build(_spec(fleet_config()["spec"], False), SEED)
    n0 = sim.pool.n
    sim.run(until=until)
    cost = (realized_cost_stats(sim.vms.values(), sim.engine,
                                sim.pool)["spot_cost"]
            if until >= HORIZON else None)
    return [Run(sim, SEED, 0.0, until, n_hosts0=n0, spot_cost=cost)]


@pytest.fixture(scope="module")
def sound_day():
    return day(HORIZON)


def test_the_programs_day_judges_with_every_number_zero(sound_day):
    recs = list(sound_day[0].sim.events.records())
    kinds = Counter(r[1] for r in recs)
    rungs = Counter(r[7] for r in recs if r[1] == "fleet-rung")
    storm = [r for r in recs if r[1] == "interrupt" and r[7] == "fault-storm"]
    assert kinds["fleet-launch"] == 584 and len(storm) == 504
    assert (rungs["same-pool"], rungs["cheaper-pool"],
            rungs["on-demand"]) == (291, 186, 46)
    cfg = fleet_config()
    got = check.judge(sound_day, cfg)
    v = check.verdict(got["numbers"], cfg)
    assert all(x["ok"] for x in v.values()), v
    assert got["failed"] == 0
    for k in NUMBERS:
        assert v[k] == {"value": 0, "limit": 0.0, "ok": True}
    res = check.load_check("fleet-storm")(sound_day[0], cfg, np.float64)
    assert len(res["claims"]) == 584 and res["input_errors"] == 0
    assert min(res["claims"]) >= check.workload_vms(cfg["stated"])
    # the control's precision runs through
    ctl = check.judge(sound_day, cfg, dtype=np.float32)
    assert set(NUMBERS) <= set(ctl["numbers"])


def storms_take_the_highest_bids(registry, pools, fraction):
    out = []
    for p in pools:
        rows = np.flatnonzero(registry["pool"] == p)
        k = int(np.ceil(fraction * rows.size))
        order = np.lexsort((registry["vid"][rows], -registry["bid"][rows]))
        out.append(registry["vid"][rows[order[:k]]])
    return np.concatenate(out) if out else np.zeros(0, dtype=np.int64)


def dearest_other(self, home, prices, bids, free_cpu):
    best = -1
    for p in range(self.n_pools):
        if p != home and self._admissible(p, prices, bids, free_cpu) and (
                best < 0 or float(prices[p]) > float(prices[best])):
            best = p
    return best


def backoff_capped_at(cap):
    def backoff(self, fails):
        cfg = self.config
        return min(cap, cfg.backoff_base * cfg.backoff_mult ** (fails - 1))
    return backoff


def built_with(change):
    from repro.market.fleet import FleetManager

    init = FleetManager.__init__

    def build(self, config, n_pools):
        init(self, config, n_pools)
        change(self)
    return build


def same_pool_once(fleet):
    fleet._ladder = (("same-pool", 1),) + fleet._ladder[1:]


def bid_half(fleet):
    fleet.config = dataclasses.replace(fleet.config, bid_fraction=0.5)


#: each fault, where it is planted, and the number that catches it.  The
#: stated backoff cap is 960 s; a cap of 480 s leaves this day's log as it
#: is (no episode waits past its fifth attempt), so the planted cap is 240 s
FAULTS = {
    "storms_take_the_highest_bids": (
        "repro.market.faults", "storm_victims",
        storms_take_the_highest_bids, "storm_victim_diff"),
    "same_pool_budget_of_one": (
        "repro.market.fleet.FleetManager", "__init__",
        "same_pool_once", "fleet_rung_errors"),
    "backoff_cap_240_s": (
        "repro.market.fleet.FleetManager", "_backoff",
        backoff_capped_at(240.0), "fleet_backoff_errors"),
    "cheaper_pool_takes_the_dearest": (
        "repro.market.fleet.FleetManager", "_cheapest_other",
        dearest_other, "fleet_pool_errors"),
    "fleet_bid_half_the_rate": (
        "repro.market.fleet.FleetManager", "__init__",
        "bid_half", "input_errors"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_program_fault_is_caught_by_its_own_number(fault, monkeypatch):
    target, attr, fn, number = FAULTS[fault]
    if isinstance(fn, str):
        fn = built_with(globals()[fn])
    monkeypatch.setattr(f"{target}.{attr}", fn)
    runs = day(FAULT_UNTIL)
    res = check.load_check("fleet-storm")(runs[0], fleet_config(),
                                          np.float64)
    got = dict(res["counts"], input_errors=res["input_errors"])
    assert got[number] > 0, got


def test_the_harness_judges_a_window_of_the_fleet_day():
    """Through ``bench/run.py``'s path, days cut by the window's end."""
    c = copy.deepcopy(run.load_cell(MARKET))
    c["config"] = fleet_config()
    res = run.run_cell(c, 2_147_483_747, 1.0, False, CPU)
    assert res["correct"], res["checks"]
    assert set(NUMBERS) <= set(res["checks"])
