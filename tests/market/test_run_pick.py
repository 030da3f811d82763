"""Runs of consecutive arrivals placed from one device call: the event loop
hands ``HlemVmp`` (jax backend) the ``VM_SUBMIT`` events due next, one
program scores them one after the other on the device
(``hlem_scores_tol_jax_run``), and each placement takes its step only while
everything the step assumed holds.  Decisions are the single pick's: event
logs equal runs of one and the numpy backend's, dispatch stays in key
order, and every cause that drops a run ends it and has the rows its call
wrote sent again."""
import numpy as np
import pytest

from repro.api import (AutoscaleSpec, BidSpec, FaultSpec, FleetSpec,
                       MigrationSpec, ObsSpec, PolicySpec, RunSpec,
                       ScenarioSpec, ServeSpec, build)
from repro.api.build import collect_row
from repro.core import hlem
from repro.core.allocation import HlemVmpAdjusted
from repro.core.hlem import (ResidentScorer, hlem_scores_tol_jax_resident,
                             pack_pick, pack_run, run_out_width)
from repro.core.hosts import HostPool
from repro.core.events import EventKind
from repro.core.simulator import MarketSimulator
from repro.core.types import make_on_demand, make_spot, resources
from repro.obs import EventLog, Tracer, first_divergence

SMALL = resources(16, 24_576, 10_000, 400_000)
BIG = resources(64, 98_304, 20_000, 800_000)


def _trace(backend):
    return RunSpec(
        scenario=ScenarioSpec(workload="trace", horizon=3600.0,
                              workload_params={"n_machines": 300,
                                               "sim_days": 0.05,
                                               "n_spot": 300}),
        policy=PolicySpec("hlem-vmp-adjusted", {"backend": backend}),
        obs=ObsSpec(events=True, trace=True)), 3600.0


def _market_hour(backend):
    return RunSpec(
        scenario=ScenarioSpec(workload="market", regime="volatile",
                              bid=BidSpec("randomized", {"lo": 0.45})),
        policy=PolicySpec("hlem-vmp-adjusted",
                          {"alpha": -0.5, "backend": backend}),
        migration=MigrationSpec("gradient-aware"),
        obs=ObsSpec(events=True, trace=True)), 3600.0


def _serve_storms(backend):
    return RunSpec(
        scenario=ScenarioSpec(workload="serve-diurnal", regime="volatile",
                              n_pools=4, horizon=7200.0),
        policy=PolicySpec("hlem-vmp-adjusted",
                          {"alpha": -0.5, "backend": backend}),
        migration=MigrationSpec("gradient-aware"),
        fleet=FleetSpec("diversified", {"target_capacity": 24.0}),
        faults=FaultSpec("storm"),
        serve=ServeSpec(),
        autoscale=AutoscaleSpec("target-tracking"),
        obs=ObsSpec(events=True, trace=True)), 7200.0


@pytest.mark.parametrize("make", [_trace, _market_hour, _serve_storms],
                         ids=["trace-300", "market-hour", "serve-storms"])
def test_runs_decide_as_single_picks_and_numpy(make, monkeypatch):
    out = {}
    for label, backend, k in (("numpy", "numpy", hlem.PICK_RUN_K),
                              ("single", "jax", 1),
                              ("run", "jax", hlem.PICK_RUN_K)):
        monkeypatch.setattr(hlem, "PICK_RUN_K", k)
        spec, until = make(backend)
        sim = build(spec, 3)
        out[label] = (sim, collect_row(sim, sim.run(until=until), spec, 3))
    (sim_np, row_np), (sim_1, row_1), (sim_k, row_k) = (
        out["numpy"], out["single"], out["run"])
    assert row_np == row_1 == row_k
    assert first_divergence(sim_np.events, sim_1.events) is None
    assert first_divergence(sim_1.events, sim_k.events) is None
    c1, ck = sim_1.obs.counters.values, sim_k.obs.counters.values
    # the same picks and fallbacks, from fewer device calls
    assert (sim_k.policy.device_picks, sim_k.policy.device_fallbacks) == (
        sim_1.policy.device_picks, sim_1.policy.device_fallbacks)
    assert "pick/run_calls" not in c1
    assert c1["pick/device_calls"] == sim_1.policy.device_picks
    assert 0 < ck["pick/run_calls"] < ck["pick/device_calls"]
    assert ck["pick/device_calls"] < c1["pick/device_calls"]
    # every step computed is either used or dropped
    assert ck["pick/run_steps"] == (ck["pick/run_taken"]
                                    + ck.get("pick/run_dropped", 0))
    assert ck.get("pick/run_dropped", 0) == sum(
        v for key, v in ck.items() if key.startswith("pick/run_dropped_"))


def _sim(backend, hosts, vms):
    sim = MarketSimulator(policy=HlemVmpAdjusted(alpha=-0.5,
                                                 backend=backend),
                          obs=Tracer(keep_records=False),
                          events=EventLog())
    for cap in hosts:
        sim.add_host(cap)
    for vm in vms:
        sim.submit(vm)
    return sim


def _arrivals():
    """Five arrivals a second apart; the first runs 1.5 s, so its finish
    falls between the second and the third."""
    vms = [make_on_demand(0, resources(2, 3_000.5, 10, 1_000), 1.5)]
    vms += [make_spot(i, resources(1, 1_536 + 7.3 * i, 10, 1_000), 600.0)
            for i in range(1, 5)]
    for i, vm in enumerate(vms):
        vm.submit_time = float(i)
    return vms


def test_a_vm_finishing_inside_a_run_is_dispatched_in_key_order(
        monkeypatch):
    hosts = [SMALL, BIG, SMALL, BIG]
    logs = {}
    for label, backend, k in (("numpy", "numpy", 8), ("single", "jax", 1),
                              ("run", "jax", 8)):
        monkeypatch.setattr(hlem, "PICK_RUN_K", k)
        sim = _sim(backend, hosts, _arrivals())
        sim.run(until=10.0)
        logs[label] = sim
    run = logs["run"]
    recs = list(run.events.records())
    assert [r[0] for r in recs] == sorted(r[0] for r in recs)
    kinds = [(r[1], r[2]) for r in recs]
    assert kinds.index(("finish", 0)) < kinds.index(("submit", 2))
    assert kinds.index(("submit", 1)) < kinds.index(("finish", 0))
    c = run.obs.counters.values
    # one run of five planned; the finish ended it after two steps, and
    # the last three arrivals made a run of their own
    assert c["pick/run_calls"] == 2
    assert c["pick/run_steps"] == 5 + 3
    assert c["pick/run_dropped_event"] == 3
    assert c["events/vm-finish"] == 1
    for other in ("numpy", "single"):
        assert first_divergence(logs[other].events, run.events) is None


def test_a_handler_that_raises_leaves_the_runs_arrivals_queued(monkeypatch):
    monkeypatch.setattr(hlem, "PICK_RUN_K", 8)
    sim = _sim("jax", [SMALL, BIG, SMALL, BIG], _arrivals()[1:])
    placed = []

    def fail_second(sim, time, vm, **kw):
        placed.append(vm.id)
        if len(placed) == 2:
            raise RuntimeError("listener")

    sim.on("vm_allocated", fail_second)
    with pytest.raises(RuntimeError):
        sim.run(until=10.0)
    # the run ended with the handler; its later arrivals wait in the queue
    assert sim.policy._run is None
    assert sorted(e[3].payload for e in sim.queue._heap
                  if e[3].kind is EventKind.VM_SUBMIT) == [3, 4]
    c = sim.obs.counters.values
    assert c["pick/run_steps"] == 4 and c["pick/run_dropped_event"] == 2
    sim.run(until=10.0)
    assert placed == [1, 2, 3, 4]


def _pool():
    pool = HostPool(capacity_hint=8)
    for cap in (SMALL, BIG, SMALL, BIG, SMALL):
        pool.add_host(cap)
    return pool


def _policy():
    pol = HlemVmpAdjusted(alpha=-0.5, backend="jax")
    pol.tracer = Tracer(keep_records=False)
    return pol


def _place(pol, pool, vm):
    """What the simulator does with a submission: find, then place."""
    with pol.tracer.span("allocation", "place", 0.0):
        hid, clearing = pol.find_host(vm, pool, 0.0, True)
    if hid >= 0 and not clearing:
        pool.place(vm, hid)
    return hid, clearing


def _spots(n, vid0=100):
    return [make_spot(vid0 + i, resources(1 + i % 2, 1_536 + 3.7 * i, 10,
                                          1_000), 600.0)
            for i in range(n)]


def _mirror_is_storage(pol, pool):
    free, spot_frac = pool.storage_views()
    return (np.array_equal(np.asarray(pol._mirror.free),
                           free.astype(np.float32))
            and np.array_equal(np.asarray(pol._mirror.spot_frac),
                               spot_frac.astype(np.float32)))


def _alter_step(monkeypatch, k, change):
    """Have run calls return their lines with step ``k``'s changed by
    ``change(line, rows)`` in place."""
    scores_tol_run = ResidentScorer.scores_tol_run

    def altered(self, packed):
        out = np.array(scores_tol_run(self, packed))
        change(out[k], self.free.shape[0])
        return out

    monkeypatch.setattr(ResidentScorer, "scores_tol_run", altered)


def _event(pol, pool, vms):
    # another pick between two of the run's arrivals
    _place(pol, pool, vms[0])
    with pol.tracer.span("allocation", "place", 0.0):
        pol._score_pick(pool.direct_mask_into(vms[1].demand).copy(),
                        vms[1], pool)


def _bit(monkeypatch):
    def flip(line, rows):
        line[rows + 4] = 3.0 - line[rows + 4]
    _alter_step(monkeypatch, 1, flip)


def _row(monkeypatch):
    # step 0's row as written lies 64 MB off, in a RAM column step 1 (for
    # which that row stays a primary candidate) finds degenerate: no tol
    # holds there
    scores_tol_run = ResidentScorer.scores_tol_run

    def off(self, packed):
        out = np.array(scores_tol_run(self, packed))
        rows = self.free.shape[0]
        out[0, rows + 4 + hlem.PICK_RUN_K + 1] += 64.0
        out[1, -3 * 4 + 4 + 1] = 0.0
        return out

    monkeypatch.setattr(ResidentScorer, "scores_tol_run", off)


def _pick(monkeypatch):
    # step 1 claims a host the certified scores do not pick
    def other(line, rows):
        line[rows + 1] = float((int(line[rows + 1]) + 1) % 5)
    _alter_step(monkeypatch, 1, other)


@pytest.mark.parametrize("cause", ["event", "bit", "row", "pick"])
def test_each_drop_cause_ends_the_run_and_rewrites_its_rows(cause,
                                                            monkeypatch):
    pool, pol = _pool(), _policy()
    c = pol.tracer.counters.values
    vms = _spots(4)
    if cause == "bit":
        _bit(monkeypatch)
    elif cause == "pick":
        _pick(monkeypatch)
    pol.plan_run(vms)
    if cause == "row":
        _row(monkeypatch)
    if cause == "event":
        _event(pol, pool, vms)
        taken = 1
    else:
        _place(pol, pool, vms[0])
        _place(pol, pool, vms[1])
        taken = 1 if cause in ("bit", "row") else 2
    assert c["pick/run_calls"] == 1 and c["pick/run_steps"] == 4
    assert c["pick/run_taken"] == taken
    assert c["pick/run_dropped"] == c[f"pick/run_dropped_{cause}"] == \
        4 - taken
    assert pol._run is None
    # the device wrote its picks for all four steps; the next call sends
    # those rows again (after "pick" no call came yet: they wait), so the
    # mirror is the storage once more
    assert (len(pol._stale) > 0) == (cause == "pick")
    with pol.tracer.span("allocation", "place", 0.0):
        assert pol.find_host(vms[2], pool, 0.0, True)[0] >= 0
    assert not pol._stale
    assert _mirror_is_storage(pol, pool)


def test_a_clearing_drops_the_rest_of_the_run():
    pool, pol = _pool(), _policy()
    c = pol.tracer.counters.values
    # every host full of spot VMs: an on-demand arrival fits nowhere
    # directly and goes to the spot-clearing list
    for hid in range(pool.n):
        cap = pool.totals()[hid]
        pool.place(make_spot(900 + hid, cap.copy(), 600.0), hid)
    od = make_on_demand(1, resources(2, 2_048, 10, 1_000), 600.0)
    pol.plan_run([od] + _spots(3))
    hid, clearing = _place(pol, pool, od)
    assert clearing and hid >= 0
    assert pol._run is None
    assert c["pick/run_dropped_clearing"] == c["pick/run_dropped"] == 4
    assert c.get("pick/run_taken", 0) == 0


def test_a_spot_arrival_no_host_fits_keeps_the_run():
    pool, pol = _pool(), _policy()
    c = pol.tracer.counters.values
    huge = make_spot(1, resources(128, 2_048, 10, 1_000), 600.0)
    vms = [huge] + _spots(3)
    pol.plan_run(vms)
    assert _place(pol, pool, huge) == (-1, False)
    for vm in vms[1:]:
        assert _place(pol, pool, vm)[0] >= 0
    assert c["pick/run_taken"] == 4 and "pick/run_dropped" not in c
    assert pol.device_picks == 3


def test_first_step_picks_as_the_resident_program():
    """Step 0 of a run scores the mirror the resident program would, with
    the same mask: the same pick, and scores within each other's tol."""
    pool = _pool()
    for i, hid in enumerate((0, 1, 1, 3)):
        pool.place(make_on_demand(50 + i, resources(1 + i, 1_000.25 * (i + 1),
                                                    10, 1_000), 60.0), hid)
    free, spot_frac = pool.storage_views()
    rows = free.shape[0]
    pol = _policy()
    vm = _spots(1)[0]
    masks, params = pol._run_inputs([vm], pool)
    mask = pool.direct_mask_into(vm.demand).copy()
    assert np.array_equal(masks[0] & 1, mask)
    masks[0] &= 1          # feasibility alone, as the single pick sends it
    mirror = ResidentScorer()
    mirror.upload(free, spot_frac)
    mirror.upload_tot(pool.scoring_totals())
    out = np.asarray(mirror.scores_tol_run(pack_run(
        masks, params, pol.rc, pol.threshold, hlem.PICK_RUN_K, [], free,
        spot_frac)))
    assert out.shape == (hlem.PICK_RUN_K, run_out_width(rows, 4,
                                                        hlem.PICK_RUN_K))
    again = ResidentScorer()
    again.upload(free, spot_frac)
    single = np.asarray(hlem_scores_tol_jax_resident(
        again.free, again.spot_frac,
        pack_pick(mask, pol._alpha_for(vm), [], free, spot_frac))[2])
    scores, tol = single[:-1], float(single[-1])
    assert int(out[0, rows + 1]) == int(np.argmax(scores))
    assert abs(float(out[0, rows]) - tol) <= 1e-3 * tol
    padded = np.zeros(rows, dtype=bool)
    padded[: mask.size] = mask
    assert np.abs(out[0, :rows] - scores)[padded].max() <= tol
    # the step wrote the VM's demand into the row it picked
    h = int(out[0, rows + 1])
    written = out[0, rows + 4 + hlem.PICK_RUN_K:][:8]
    assert np.allclose(written[:4], free[h] - vm.demand)
    assert np.allclose(written[4:], spot_frac[h] + vm.demand
                       / pool.scoring_totals()[h])
    # steps past the run are left empty
    assert (out[1:, rows + 1] == 0).all()
