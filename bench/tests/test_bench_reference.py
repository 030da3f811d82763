"""The plain reference on its own: its scores against the program's
float64 oracle, and each check on small hand-made logs."""
import math

import numpy as np
import pytest

from bench import reference as ref

POLICY = ref.PolicyRules(rc=0.95, threshold=0.0, alpha=-0.5,
                         adjust_spot_only=True)
CAP = np.array([[16, 24576, 10000, 4e5], [32, 49152, 10000, 4e5],
                [16, 24576, 10000, 4e5]], dtype=np.float64)


@pytest.mark.parametrize("alpha", [0.0, -0.5])
@pytest.mark.parametrize("m", [1, 2, 50, 3000])
def test_scores_match_the_program_oracle(alpha, m):
    from repro.core.hlem import hlem_scores_np

    rng = np.random.default_rng(m)
    free = rng.uniform(0, 100, (m, 4)) * np.array([1, 1e3, 1e2, 1e4])
    free[: m // 3] = free[0]            # exact ties
    spot = rng.uniform(0, 1, (m, 4))
    want = hlem_scores_np(free, np.ones(m, bool), spot, alpha)
    got = ref.hlem_scores(free.T, spot.T, alpha)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-14)


def vm(cpu, spot=False, duration=100.0, bid=math.inf, t=0.0):
    return ref.VmSpec(demand=np.array([cpu, cpu * 1024, 10, 1000.0]),
                      spot=spot, duration=duration, bid=bid, pin=-1,
                      min_running_time=0.0, submit_time=t)


def judge(records, vms, t_end=50.0, **kw):
    return ref.replay(records, t_end, CAP, np.zeros(3, np.int64), 3, vms,
                      POLICY, **kw)


def rec(t, kind, v=-1, host=-1, pool=-1, a=0.0, aux=None):
    return (t, kind, v, pool, host, a, 0.0, aux)


def test_best_host_reads_zero_and_a_worse_one_reads_its_gap():
    vms = {0: vm(4)}
    # the 32-core host has the most free capacity: HLEM picks it
    good = judge([rec(0, "submit", 0), rec(0, "start", 0, host=1)], vms)
    assert good["placement_gap"] == 0.0 and good["placement_errors"] == 0
    bad = judge([rec(0, "submit", 0), rec(0, "start", 0, host=2)], vms)
    assert bad["placement_gap"] > 0.1 and bad["placement_errors"] == 1


def test_unplaced_and_off_the_candidates():
    vms = {0: vm(20), 1: vm(4, t=60.0)}
    # 20 cores fit only host 1; host 0 is over capacity
    off = judge([rec(0, "submit", 0), rec(0, "start", 0, host=0)], vms)
    assert off["placement_errors"] == 1
    # a VM that fits but is left waiting, and one never submitted
    left = judge([rec(0, "submit", 0), rec(0, "alloc-flush")], vms,
                 t_end=60.0)
    assert left["placement_errors"] == 2


def test_runtime():
    vms = {0: vm(4, duration=10.0)}
    ok = judge([rec(0, "submit", 0), rec(0, "start", 0, host=1),
                rec(10.0, "finish", 0, host=1)], vms)
    assert ok["runtime_err_s"] == 0.0
    late = judge([rec(0, "submit", 0), rec(0, "start", 0, host=1),
                  rec(12.5, "finish", 0, host=1)], vms)
    assert late["runtime_err_s"] == pytest.approx(2.5)
    overdue = judge([rec(0, "submit", 0), rec(0, "start", 0, host=1)], vms,
                    t_end=30.0)
    assert overdue["runtime_err_s"] == pytest.approx(20.0)


def market_price(seed, util, sigma, rho, od=1.0):
    z = np.random.default_rng(seed).standard_normal()
    s = sigma * math.sqrt(1 - rho ** 2) * z
    return min(od * (0.1 + 0.9 * util ** 3) * math.exp(s), od)


def test_prices_and_wave_victims():
    mk = ref.MarketRules(od=np.array([1.0]), sigma=np.array([0.3]),
                         rho=np.array([0.75]), seeds=[11])
    billing = ref.BillingRules(0.0425, 0.0057, 0.01, 0.05)
    vms = {0: vm(16, spot=True, bid=0.5), 1: vm(16, spot=True, bid=0.05)}
    pool = np.zeros(3, np.int64)
    # both VMs on host 0 (16 of 16 cores): pool utilization 16/64
    p = market_price(11, 16 / 64, 0.3, 0.75)
    assert 0.05 < p < 0.5
    base = [rec(0, "submit", 0), rec(0, "start", 0, host=1),
            rec(0, "submit", 1), rec(0, "start", 1, host=0)]

    def run(price, victims):
        logs = base + [rec(60, "price-tick", pool=0, a=price)]
        logs += [rec(60, "interrupt", v, host=0, aux="price-wave")
                 for v in victims]
        return ref.replay(logs, 60.0, CAP, pool, 3, vms, POLICY, market=mk,
                          billing=billing)

    # VM 0 took host 1 (32 cores): utilization is (32 + 16) used of 64
    p = market_price(11, 32 / 64, 0.3, 0.75)
    good = run(p, [1])
    assert good["price_rel_err"] < 1e-15 and good["wave_victim_diff"] == 0
    assert run(p * 1.001, [1])["price_rel_err"] == pytest.approx(
        0.001 / 1.001, rel=1e-6)
    assert run(p, [])["wave_victim_diff"] == 1
    assert run(p, [0, 1])["wave_victim_diff"] == 1
