"""A run with the timed path broken underneath comes out not correct, in
every cell of ``BENCHMARK.json``: a simulator that returns its state
unchanged; a placement altered where it is made; an input prepared unlike
the configuration states it; in a cell with a market, a price process
other than the stated one.  The harness's look for a chip is skipped; the
rest of a run is driven as ``bench/run.py`` drives it."""
import numpy as np
import pytest

from bench import run
from bench.tests._small import CELLS, CPU, small_cell

MARKET_CELLS = [c for c in CELLS
                if "market" in small_cell(c)["config"].get("stated", {})]


def unchanged(self, until=None):
    return self.metrics


def altered(self, mask, vm, pool):
    """The last candidate host instead of the HLEM pick."""
    idx = np.flatnonzero(mask)
    return int(idx[-1]) if idx.size else -1


def doubled_first_host(add_host):
    def add(self, capacity, pool=0):
        if self.pool.n == 0:
            capacity = 2.0 * np.asarray(capacity)
        return add_host(self, capacity, pool)
    return add


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    c = small_cell(cell)
    res = run.run_cell(c, 17, 1.0, False, CPU)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {m["name"] for m in c["end_to_end"]}
    assert res["checks"]["input_errors"]["value"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "altered", "input"])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    from repro.core.allocation import HlemVmp
    from repro.core.simulator import MarketSimulator

    if fault == "unchanged":
        monkeypatch.setattr(MarketSimulator, "run", unchanged)
    elif fault == "altered":
        monkeypatch.setattr(HlemVmp, "_score_pick", altered)
    else:
        monkeypatch.setattr(MarketSimulator, "add_host",
                            doubled_first_host(MarketSimulator.add_host))
    res = run.run_cell(small_cell(cell), 17, 0.5, False, CPU)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", MARKET_CELLS)
def test_unstated_price_persistence_is_not_correct(cell, monkeypatch):
    import importlib

    build_mod = importlib.import_module("repro.api.build")
    make_market = build_mod.make_market

    def other_rho(*args, **kwargs):
        cfg = make_market(*args, **kwargs)
        for pc in cfg.pools:
            pc.process_kwargs["shock_rho"] = 0.5
        return cfg
    monkeypatch.setattr(build_mod, "make_market", other_rho)
    res = run.run_cell(small_cell(cell), 17, 0.5, False, CPU)
    assert not res["correct"], res["checks"]
    assert res["checks"]["price_rel_err"]["value"] > \
        res["checks"]["price_rel_err"]["limit"]
