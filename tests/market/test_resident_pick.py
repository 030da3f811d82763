"""The device pick's resident mirror: a float32 copy of the pool's
(free, spot_frac) storage kept on the device between picks, updated by the
rows the pool's row log names.  After every pick it equals the storage in
float32 bit for bit, the packed scores and tolerance equal the reference
scorer's, overflow and new storage take a whole upload, one program is
compiled per storage size, and runs decide exactly as the numpy backend."""
import numpy as np
import pytest

from repro.api import (MigrationSpec, ObsSpec, PolicySpec, RunSpec,
                       ScenarioSpec, build)
from repro.api.build import collect_row
from repro.core.allocation import HlemVmp, HlemVmpAdjusted
from repro.core.hlem import (ResidentScorer, dirty_capacity,
                             hlem_scores_tol_jax,
                             hlem_scores_tol_jax_resident)
from repro.core.hosts import HostPool
from repro.core.types import make_on_demand, make_spot, resources
from repro.obs import Tracer, first_divergence

BIG = resources(64, 98_304, 20_000, 800_000)
SMALL = resources(16, 24_576, 10_000, 400_000)


def _bits(a):
    return np.asarray(a, dtype=np.float32).view(np.uint32)


@pytest.fixture
def checked(monkeypatch):
    """Check each device pick right after it: the mirror against the
    storage and the packed output against :func:`hlem_scores_tol_jax` on
    the same state, bit for bit.  Returns the storage sizes picked on."""
    sizes = []
    last = {}
    score_pick = HlemVmp._score_pick
    scores_tol = ResidentScorer.scores_tol

    def spy_scores_tol(self, packed):
        last["out"] = scores_tol(self, packed)
        return last["out"]

    def spy_score_pick(self, mask, vm, pool):
        last.clear()
        hid = score_pick(self, mask, vm, pool)
        if "out" not in last:
            return hid
        free, spot_frac = pool.storage_views()
        assert np.array_equal(_bits(self._mirror.free),
                              free.astype(np.float32).view(np.uint32))
        assert np.array_equal(_bits(self._mirror.spot_frac),
                              spot_frac.astype(np.float32).view(np.uint32))
        padded = np.zeros(free.shape[0], dtype=bool)
        padded[: mask.size] = mask
        scores, tol = hlem_scores_tol_jax(free, padded, spot_frac,
                                          np.float32(self._alpha_for(vm)))
        want = np.append(np.asarray(scores), np.float32(tol))
        assert np.array_equal(_bits(last["out"]), want.view(np.uint32))
        sizes.append(free.shape[0])
        return hid

    monkeypatch.setattr(ResidentScorer, "scores_tol", spy_scores_tol)
    monkeypatch.setattr(HlemVmp, "_score_pick", spy_score_pick)
    return sizes


def _policy():
    pol = HlemVmpAdjusted(alpha=-0.5, backend="jax")
    pol.tracer = Tracer(keep_records=False)
    return pol


def _pick(pol, pool, vm):
    pol.tracer.begin("allocation", "place")
    hid = pol._score_pick(pool.direct_mask_into(vm.demand).copy(), vm, pool)
    pol.tracer.end(0.0)
    return hid


def test_mirror_follows_every_writer_of_the_storage(checked):
    pool = HostPool(capacity_hint=4)
    for k in range(3):
        pool.add_host(BIG if k % 2 else SMALL)
    pol = _policy()
    c = pol.tracer.counters.values
    spot = make_spot(1, resources(4, 6_144, 10, 1_000), 3_600.0,
                     min_running_time=60.0)
    od = make_on_demand(2, resources(2, 3_000.5, 10, 1_000), 3_600.0)
    probe = make_spot(3, resources(1, 1_024, 10, 1_000), 60.0)
    steps = [
        lambda: pool.place(spot, 1),
        lambda: pool.place(od, 0),
        lambda: [pool.add_host(SMALL) for _ in range(6)],   # 4 -> 16 rows
        lambda: pool.remove_host(2),
        lambda: pool.reactivate_host(2),
        lambda: pool.update_host(0, BIG),                   # trace UPDATE
        lambda: pool.reserve(probe, 4),                     # migration
        lambda: pool.release_reservation(probe.id),
        lambda: pool.release(spot),
        lambda: pool.release(od),
    ]
    assert _pick(pol, pool, probe) >= 0
    for step in steps:
        step()
        assert _pick(pol, pool, probe) >= 0
    assert checked == [4] + [4, 4] + [16] * 8
    # a whole upload at the first pick and when storage grew; every other
    # change rode along as rewritten rows
    assert c["pick/mirror_uploads"] == 2
    assert c["pick/dirty_rows"] == 9


def test_more_changed_rows_than_k_upload_in_full(checked):
    pool = HostPool(capacity_hint=1024)
    for _ in range(1024):
        pool.add_host(SMALL)
    k_cap = dirty_capacity(1024)
    pol = _policy()
    c = pol.tracer.counters.values
    probe = make_spot(0, resources(1, 1_024, 10, 1_000), 60.0)
    _pick(pol, pool, probe)
    assert c["pick/mirror_uploads"] == 1

    def rewrite(rows, vid0):
        for i in range(rows):
            pool.place(make_on_demand(vid0 + i,
                                      resources(1, 512, 1, 10), 60.0), i)

    rewrite(k_cap, 1)
    _pick(pol, pool, probe)
    assert (c["pick/mirror_uploads"], c["pick/dirty_rows"]) == (1, k_cap)
    rewrite(k_cap + 1, 10_000)
    _pick(pol, pool, probe)
    assert (c["pick/mirror_uploads"], c["pick/dirty_rows"]) == (2, k_cap)
    # the same row rewritten many times is one row
    for vid in range(20_000, 20_000 + 3 * k_cap):
        pool.place(make_on_demand(vid, resources(0.01, 1, 0, 0), 60.0), 7)
    _pick(pol, pool, probe)
    assert (c["pick/mirror_uploads"], c["pick/dirty_rows"]) == (2, k_cap + 1)
    assert len(checked) == 4


def test_a_new_pool_uploads_and_a_stale_position_reads_everything():
    pol = _policy()
    c = pol.tracer.counters.values
    probe = make_spot(0, resources(1, 1_024, 10, 1_000), 60.0)
    pools = []
    for _ in range(2):
        pool = HostPool(capacity_hint=8)
        for _ in range(8):
            pool.add_host(SMALL)
        pools.append(pool)
    _pick(pol, pools[0], probe)
    _pick(pol, pools[1], probe)
    assert c["pick/mirror_uploads"] == 2
    # left unread for more than the storage's rows, the log starts over
    pool = pools[1]
    for vid in range(1, 20):
        pool.place(make_on_demand(vid, resources(0.1, 1, 0, 0), 60.0), 3)
    assert pool.rows_since(pol._mirror_pos) is None
    _pick(pol, pool, probe)
    assert c["pick/mirror_uploads"] == 3
    assert pool.rows_since(pol._mirror_pos) == []


def test_row_log_positions_and_compaction():
    pool = HostPool(capacity_hint=8)
    for _ in range(4):
        pool.add_host(SMALL)
    assert pool.rows_since(0) is None
    pos = pool.track_rows()
    assert pool.track_rows() == pos
    pool.remove_host(2)
    pool.update_host(1, BIG)
    assert pool.rows_since(pos) == [2, 1]
    pool.compact_row_log(pos + 1)
    assert pool.rows_since(pos) is None
    assert pool.rows_since(pos + 1) == [1]
    assert pool.track_rows() == pos + 2


def _market(backend):
    return RunSpec(
        scenario=ScenarioSpec(workload="market", regime="volatile",
                              bid={"strategy": "randomized",
                                   "params": {"lo": 0.45}}),
        policy=PolicySpec("hlem-vmp-adjusted",
                          {"alpha": -0.5, "backend": backend}),
        migration=MigrationSpec("gradient-aware"),
        obs=ObsSpec(events=True)), 2700.0


def _trace(backend):
    # churn removes hosts and re-adds them as new rows (64 -> 128 storage)
    return RunSpec(
        scenario=ScenarioSpec(workload="trace", horizon=4320.0,
                              workload_params={"n_machines": 60,
                                               "sim_days": 0.05,
                                               "n_spot": 300,
                                               "machine_churn_per_day": 40.0}),
        policy=PolicySpec("hlem-vmp-adjusted", {"backend": backend}),
        obs=ObsSpec(events=True)), 4320.0


@pytest.mark.parametrize("make", [_market, _trace], ids=["market", "trace"])
def test_runs_decide_as_the_numpy_backend(make, checked):
    hlem_scores_tol_jax_resident.clear_cache()
    runs = {}
    for backend in ("numpy", "jax"):
        spec, until = make(backend)
        sim = build(spec, 5)
        if make is _trace:
            for k in range(5):     # trace UPDATE events
                sim.schedule_host_update(600.0 + 500.0 * k, k, BIG)
        runs[backend] = (sim, collect_row(sim, sim.run(until=until),
                                          spec, 5))
    (sim_np, row_np), (sim_jx, row_jx) = runs["numpy"], runs["jax"]
    assert row_np == row_jx
    assert first_divergence(sim_np.events, sim_jx.events) is None
    assert sim_jx.policy.device_picks == len(checked) > 0
    # the numpy backend keeps no row log; one program per storage size
    assert sim_np.pool._row_log is None
    assert sim_jx.pool._row_log is not None
    assert hlem_scores_tol_jax_resident._cache_size() == len(set(checked))
    if make is _market:
        assert row_jx["migrations"] > 0 and row_jx["waves"] > 0
    else:
        assert set(checked) == {64, 128}
