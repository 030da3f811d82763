"""Market fault injection (PR 6): event validation, seeded schedule
determinism, per-kind fault mechanics through the PRICE_TICK machinery
(crunch bias, spike bias, pool outage, correlated storm), empty-injector
bit-identity, and the chaos-determinism contract (two identical runs under
injected faults are bit-identical)."""
import json

import numpy as np
import pytest

from repro.api import FaultSpec, FleetSpec, PolicySpec, RunSpec, ScenarioSpec, build
from repro.core import (
    FirstFit,
    MarketSimulator,
    SimConfig,
    VmState,
    dynamic_vm_table,
    make_spot,
    resources,
    to_json,
)
from repro.core.causes import InterruptionCause
from repro.market import (
    FaultEvent,
    FaultInjector,
    MarketConfig,
    MarketEngine,
    PoolConfig,
    make_fault_injector,
    make_market,
    storm_victims,
)

BIG = resources(64, 131_072, 40_000, 1_600_000)
SMALL = resources(2, 2048, 1000, 10_000)


class ScriptedProcess:
    """Price process stub: scripted sequence, then holds the last value."""

    def __init__(self, seq):
        self.seq = list(seq)
        self.last = self.seq[-1]

    def price(self, utilization: float) -> float:
        if self.seq:
            self.last = self.seq.pop(0)
        return self.last


def scripted_engine(*pool_price_seqs, tick=10.0) -> MarketEngine:
    pools = [PoolConfig(f"p{i}") for i in range(len(pool_price_seqs))]
    eng = MarketEngine(MarketConfig(pools, tick_interval=tick))
    eng.processes = [ScriptedProcess(s) for s in pool_price_seqs]
    return eng


def fault_sim(engine, faults, **sim_kw):
    return MarketSimulator(
        policy=FirstFit(),
        config=SimConfig(strict_invariants=True, **sim_kw),
        engine=engine, faults=faults)


# ---------------------------------------------------------------------------
# event validation + schedule
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("event, match", [
    (FaultEvent("meteor", 0.0), "unknown fault kind"),
    (FaultEvent("storm", -1.0, magnitude=0.5), "t0 must be >= 0"),
    (FaultEvent("pool-outage", 0.0, duration=-5.0), "duration must be >= 0"),
    (FaultEvent("storm", 0.0, pools=(0, 7), magnitude=0.5),
     r"unknown pool\(s\) \[7\] \(known pools: 0\.\.3\)"),
    (FaultEvent("storm", 0.0, magnitude=0.0), "storm fraction"),
    (FaultEvent("storm", 0.0, magnitude=1.5), "storm fraction"),
    (FaultEvent("capacity-crunch", 0.0, magnitude=0.0), "utilization bias"),
])
def test_fault_event_validation(event, match):
    with pytest.raises(ValueError, match=match):
        FaultInjector([event], n_pools=4)


def test_injector_sorts_schedule_and_coerces_dicts():
    fi = FaultInjector(
        [{"kind": "storm", "t0": 500.0, "magnitude": 0.5},
         FaultEvent("pool-outage", 100.0, 60.0, (1,))], n_pools=2)
    assert [e.kind for e in fi.schedule] == ["pool-outage", "storm"]
    assert fi.pending()
    started, ended = fi.begin_tick(100.0)
    assert [e.kind for _, e in started] == ["pool-outage"]
    assert ended == []
    # the outage ends inside the 160-tick; the storm starts at 500
    started, ended = fi.begin_tick(160.0)
    assert started == [] and ended == [0]
    started, _ = fi.begin_tick(500.0)
    assert [e.kind for _, e in started] == ["storm"]
    assert not fi.pending()


def test_bias_windows_sum_active_events():
    fi = FaultInjector(
        [FaultEvent("capacity-crunch", 100.0, 100.0, (0,), 0.2),
         FaultEvent("capacity-crunch", 150.0, 100.0, None, 0.1),
         FaultEvent("price-spike", 100.0, 50.0, (1,), 2.0)], n_pools=2)
    assert fi.util_bias(50.0) is None             # nothing active yet
    assert np.allclose(fi.util_bias(100.0), [0.2, 0.0])
    assert np.allclose(fi.util_bias(160.0), [0.3, 0.1])   # windows overlap
    assert fi.util_bias(300.0) is None            # all windows closed
    assert np.allclose(fi.shock_bias(120.0), [0.0, 2.0])
    assert fi.shock_bias(150.0) is None           # [t0, t1) half-open


def test_storm_victims_lowest_bids_first():
    registry = {
        "vid": np.array([10, 11, 12, 13, 20], dtype=np.int64),
        "pool": np.array([0, 0, 0, 0, 1], dtype=np.int64),
        "bid": np.array([0.9, 0.3, 0.5, 0.3, 0.7]),
    }
    # pool 0: ceil(0.5 * 4) = 2 victims, lowest bids (ties by vid)
    v = storm_victims(registry, (0,), 0.5)
    assert v.tolist() == [11, 13]
    # all pools: pool 1 contributes ceil(0.5 * 1) = 1
    v = storm_victims(registry, (0, 1), 0.5)
    assert v.tolist() == [11, 13, 20]
    assert storm_victims(registry, (0,), 0.0001).tolist() == [11]  # ceil >= 1
    empty = {k: a[:0] for k, a in registry.items()}
    assert storm_victims(empty, (0,), 0.5).size == 0


# ---------------------------------------------------------------------------
# scenario generators
# ---------------------------------------------------------------------------
def test_builtin_scenarios_compile_and_random_storms_are_seeded():
    for name in ("storm", "pool-outage", "price-spike", "capacity-crunch"):
        fi = make_fault_injector(name, 4, 14400.0, 60.0, 0)
        assert fi.schedule and all(e.kind in name or e.kind == "storm"
                                   for e in fi.schedule)
    scripted = make_fault_injector(
        "scripted", 4, 14400.0, 60.0, 0,
        events=[{"kind": "storm", "t0": 120.0, "magnitude": 0.5},
                FaultEvent("pool-outage", 60.0, 30.0, (1,))])
    assert [e.kind for e in scripted.schedule] == ["pool-outage", "storm"]
    a = make_fault_injector("random-storms", 4, 14400.0, 60.0, seed=3)
    b = make_fault_injector("random-storms", 4, 14400.0, 60.0, seed=3)
    c = make_fault_injector("random-storms", 4, 14400.0, 60.0, seed=4)
    assert a.schedule == b.schedule        # pre-drawn schedule is seeded
    assert a.schedule != c.schedule
    with pytest.raises(ValueError, match="unknown fault scenario"):
        make_fault_injector("meteor-shower", 4, 14400.0, 60.0, 0)


# ---------------------------------------------------------------------------
# price-path faults compose with the engine tick (not bypass it)
# ---------------------------------------------------------------------------
def _twin_engines(seed=0, n_pools=3):
    mk = lambda: MarketEngine(make_market(  # noqa: E731
        "volatile", n_pools=n_pools, seed=seed, tick_interval=60.0))
    return mk(), mk()


def _tick_pool(eng):
    from repro.core import HostPool
    pool = HostPool()
    pool.enable_market(eng.n_pools)
    for p in range(eng.n_pools):
        pool.add_host(BIG, pool=p)
    return pool


def test_price_spike_bias_raises_only_affected_pools():
    base, spiked = _twin_engines()
    pool_b, pool_s = _tick_pool(base), _tick_pool(spiked)
    bias = np.array([0.0, 4.0, 0.0])
    hit = False
    for k in range(20):
        pb = base.tick(pool_b, 60.0 * k)
        ps = spiked.tick(pool_s, 60.0 * k, shock_bias=bias)
        # unaffected pools share the identical shock draws → identical prices
        assert pb[0] == ps[0] and pb[2] == ps[2]
        hit = hit or ps[1] > pb[1]
    assert hit     # +4 sigma must lift the affected pool's price somewhere


def test_capacity_crunch_bias_raises_only_affected_pools():
    base, crunched = _twin_engines()
    pool_b, pool_c = _tick_pool(base), _tick_pool(crunched)
    bias = np.array([0.4, 0.0, 0.0])
    hit = False
    for k in range(20):
        pb = base.tick(pool_b, 60.0 * k)
        pc = crunched.tick(pool_c, 60.0 * k, util_bias=bias)
        assert pb[1] == pc[1] and pb[2] == pc[2]
        hit = hit or pc[0] > pb[0]
    assert hit


# ---------------------------------------------------------------------------
# simulator wiring: outage + storm lifecycles
# ---------------------------------------------------------------------------
def test_pool_outage_evicts_then_reactivates():
    eng = scripted_engine([0.1] * 60, [0.1] * 60, tick=10.0)
    fi = FaultInjector([FaultEvent("pool-outage", 20.0, 30.0, (0,))], 2)
    sim = fault_sim(eng, fi)
    h0 = sim.add_host(BIG, pool=0)
    sim.add_host(BIG, pool=1)
    vm = make_spot(0, SMALL, 100.0, bid=0.8, pool=0,
                   hibernation_timeout=1e6)
    sim.submit(vm)
    m = sim.run(until=300.0)

    # evicted at the window start through the ordinary interruption path
    ev = m.interruption_events[0]
    assert (ev.vm_id, ev.time, ev.kind) == (0, 20.0, "host-removed")
    assert ev.cause == InterruptionCause.FAULT_OUTAGE
    # pool-pinned → hibernates through the outage, resumes at the window
    # end on the reactivated host (ran 20s, so it finishes 80s later)
    assert vm.interruptions == 1
    assert [(i.host, i.start) for i in vm.history] == [(h0, 0.0), (h0, 50.0)]
    assert vm.state is VmState.FINISHED and vm.finish_time == 130.0
    assert sim.pool.active[h0]
    assert [r.kind for r in m.fault_records] == ["pool-outage"]
    assert m.fault_records[0].t1 == 50.0


def test_storm_reclaims_fraction_lowest_bids_first():
    eng = scripted_engine([0.01] * 60, [0.01] * 60, tick=10.0)
    fi = FaultInjector([FaultEvent("storm", 30.0, magnitude=0.5)], 2)
    sim = fault_sim(eng, fi)
    sim.add_host(BIG, pool=0)
    sim.add_host(BIG, pool=1)
    from repro.core import InterruptionBehavior
    vms = [make_spot(i, SMALL, 500.0, bid=0.2 + 0.1 * i, pool=i % 2,
                     behavior=InterruptionBehavior.TERMINATE)
           for i in range(4)]
    for v in vms:
        sim.submit(v)
    m = sim.run(until=100.0)

    # ceil(0.5 * 2) = 1 victim per pool, lowest bid each: vm 0 and vm 1
    storm_evs = [e for e in m.interruption_events
                 if e.cause == InterruptionCause.FAULT_STORM]
    assert [(e.vm_id, e.time, e.kind) for e in storm_evs] == \
        [(0, 30.0, "terminate"), (1, 30.0, "terminate")]
    assert vms[0].state is VmState.TERMINATED
    assert vms[1].state is VmState.TERMINATED
    assert vms[2].state is VmState.RUNNING
    assert vms[3].state is VmState.RUNNING
    # prices stayed far below every bid: the storm, not the wave, did this
    assert m.wave_events == []


# ---------------------------------------------------------------------------
# bit-identity contracts
# ---------------------------------------------------------------------------
def _seeded_market_run(faults, seed=7):
    rng = np.random.default_rng(seed)
    eng = MarketEngine(make_market("volatile", n_pools=2, seed=seed,
                                   tick_interval=20.0))
    sim = MarketSimulator(policy=FirstFit(),
                          config=SimConfig(record_timeline=True),
                          engine=eng, faults=faults)
    for h in range(6):
        sim.add_host(resources(16, 32_768, 10_000, 400_000), pool=h % 2)
    for i in range(60):
        demand = resources(float(rng.choice([1, 2, 4])), 2048, 100, 10_000)
        sim.submit(make_spot(i, demand, float(rng.uniform(50, 400)),
                             bid=float(rng.uniform(0.3, 1.0)),
                             hibernation_timeout=400.0,
                             submit_time=float(rng.uniform(0.0, 300.0))))
    m = sim.run(until=2000.0)
    return sim, m


def test_empty_injector_bit_identical_to_no_injector():
    """faults=FaultInjector(()) == faults=None: identical VM tables, events,
    prices, timeline — the fault layer is invisible until an event fires."""
    sim1, m1 = _seeded_market_run(faults=None)
    sim2, m2 = _seeded_market_run(faults=FaultInjector((), n_pools=2))
    assert to_json(dynamic_vm_table(sim1.all_vms())) == \
        to_json(dynamic_vm_table(sim2.all_vms()))
    assert m1.interruption_events == m2.interruption_events
    assert m1.price_series == m2.price_series
    assert m1.timeline == m2.timeline
    assert m2.fault_records == []


def test_chaos_two_run_bit_identity():
    """The chaos-determinism contract: two identical fleet+faults runs at a
    fixed seed are bit-identical (VM tables, interruptions, fault records,
    capacity samples)."""
    spec = RunSpec(
        scenario=ScenarioSpec(workload="market", regime="volatile",
                              n_pools=3, horizon=3600.0),
        policy=PolicySpec("first-fit"),
        fleet=FleetSpec(params={"target_capacity": 16.0}),
        faults=FaultSpec(scenario="storm",
                         params={"first": 600.0, "every": 600.0,
                                 "count": 3, "fraction": 0.5}))

    def one():
        sim = build(spec, seed=0)
        m = sim.run(until=3600.0)
        return sim, m

    sim1, m1 = one()
    sim2, m2 = one()
    assert m1.fault_records and m1.fleet_launches > 0   # chaos actually ran
    assert any(e.cause == InterruptionCause.FAULT_STORM
               for e in m1.interruption_events)
    assert to_json(dynamic_vm_table(sim1.all_vms())) == \
        to_json(dynamic_vm_table(sim2.all_vms()))
    assert m1.interruption_events == m2.interruption_events
    assert m1.fault_records == m2.fault_records
    assert m1.fleet_samples == m2.fleet_samples
    assert m1.fallback_counts == m2.fallback_counts
    assert json.dumps(m1.resilience_stats(sim1.vms, sim1.engine, sim1.pool),
                      sort_keys=True) == \
        json.dumps(m2.resilience_stats(sim2.vms, sim2.engine, sim2.pool),
                   sort_keys=True)
