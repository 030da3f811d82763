"""HLEM scoring math: numpy oracle vs jitted JAX vs properties."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    hlem_scores_batch_jax,
    hlem_scores_batch_np,
    hlem_scores_jax,
    hlem_scores_np,
    hlem_select_batch_jax,
    hlem_select_jax,
    hlem_select_np,
)
from repro.core import hlem
from repro.core.hlem import (PICK_NP_M_CUTOVER, hlem_pick_candidates_np,
                             hlem_pick_np, hlem_weights_np)

BIG = 3.4e38


@pytest.mark.parametrize("n", [2, 5, 33, 200])
@pytest.mark.parametrize("alpha", [0.0, -0.5, 0.7])
def test_np_vs_jax_scores(n, alpha):
    rng = np.random.default_rng(n)
    free = rng.uniform(0, 100, (n, 4))
    mask = rng.random(n) < 0.7
    spot = rng.uniform(0, 1, (n, 4))
    s_np = hlem_scores_np(free, mask, spot, alpha)
    s_jx = np.asarray(hlem_scores_jax(
        jnp.asarray(free, jnp.float32), jnp.asarray(mask),
        jnp.asarray(spot, jnp.float32), jnp.float32(alpha)))
    if mask.any():
        np.testing.assert_allclose(s_np[mask], s_jx[mask], rtol=2e-3,
                                   atol=2e-4)
        assert np.argmax(s_np) == np.argmax(s_jx)
    assert np.all(s_jx[~mask] <= -BIG / 2)


def test_select_consistency():
    rng = np.random.default_rng(7)
    for _ in range(20):
        n = int(rng.integers(2, 50))
        free = rng.uniform(0, 10, (n, 4))
        mask = rng.random(n) < 0.5
        spot = rng.uniform(0, 1, (n, 4))
        i_np = hlem_select_np(free, mask, spot, -0.5)
        i_jx = int(hlem_select_jax(
            jnp.asarray(free, jnp.float32), jnp.asarray(mask),
            jnp.asarray(spot, jnp.float32), jnp.float32(-0.5)))
        assert i_np == i_jx


def test_batched_select_matches_loop():
    rng = np.random.default_rng(3)
    n, b = 40, 8
    free = jnp.asarray(rng.uniform(0, 10, (n, 4)), jnp.float32)
    masks = jnp.asarray(rng.random((b, n)) < 0.6)
    spot = jnp.asarray(rng.uniform(0, 1, (n, 4)), jnp.float32)
    batched = np.asarray(hlem_select_batch_jax(free, masks, spot,
                                               jnp.float32(-0.5)))
    for i in range(b):
        single = int(hlem_select_jax(free, masks[i], spot,
                                     jnp.float32(-0.5)))
        assert batched[i] == single


def test_score_scale_invariance_of_selection():
    """Min-max standardization makes selection invariant to per-dimension
    affine rescaling of free capacities."""
    rng = np.random.default_rng(11)
    free = rng.uniform(1, 9, (12, 4))
    mask = np.ones(12, bool)
    base = hlem_select_np(free, mask)
    scaled = free * np.array([10.0, 0.5, 3.0, 100.0])
    assert hlem_select_np(scaled, mask) == base


def test_alpha_zero_equals_unadjusted():
    rng = np.random.default_rng(5)
    free = rng.uniform(0, 10, (9, 4))
    mask = rng.random(9) < 0.8
    spot = rng.uniform(0, 1, (9, 4))
    np.testing.assert_allclose(
        hlem_scores_np(free, mask, spot, 0.0),
        hlem_scores_np(free, mask, None, 0.0))


# ---------------------------------------------------------------------------
# batched oracle (B VMs x n hosts in one pass)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,n", [(1, 5), (4, 33), (16, 200), (8, 64)])
def test_batch_np_rows_match_single_oracle(b, n):
    rng = np.random.default_rng(b * 100 + n)
    free = rng.uniform(0, 50, (n, 4))
    free[:, 3] = 7.0  # degenerate (zero-span) column among candidates
    masks = rng.random((b, n)) < 0.6
    masks[0] = False  # fully-masked row
    spot = rng.uniform(0, 1, (n, 4))
    alphas = np.where(rng.random(b) < 0.5, -0.5, 0.0)
    out = hlem_scores_batch_np(free, masks, spot, alphas)
    assert out.shape == (b, n)
    for i in range(b):
        want = hlem_scores_np(free, masks[i], spot, alphas[i])
        if masks[i].any():
            np.testing.assert_allclose(out[i][masks[i]], want[masks[i]],
                                       rtol=1e-12, atol=1e-12)
            assert np.argmax(out[i]) == np.argmax(want)
        assert np.all(np.isneginf(out[i][~masks[i]]))


def test_batch_jax_matches_batch_np():
    rng = np.random.default_rng(17)
    b, n = 6, 80
    free = rng.uniform(0, 20, (n, 4)).astype(np.float32)
    free[:, 2] = 3.0  # degenerate column
    masks = rng.random((b, n)) < 0.5
    spot = rng.uniform(0, 1, (n, 4)).astype(np.float32)
    alphas = np.linspace(-0.9, 0.9, b).astype(np.float32)
    want = hlem_scores_batch_np(free, masks, spot, alphas)
    got = np.asarray(hlem_scores_batch_jax(
        jnp.asarray(free), jnp.asarray(masks), jnp.asarray(spot),
        jnp.asarray(alphas)))
    for i in range(b):
        if masks[i].any():
            np.testing.assert_allclose(got[i][masks[i]], want[i][masks[i]],
                                       rtol=1e-4, atol=1e-5)
            assert np.argmax(got[i]) == np.argmax(want[i])


@pytest.mark.parametrize("b", [1, 4, 9])
def test_batch_np_large_n_crossover(b):
    """Above BATCH_NP_N_CUTOVER the batched scorer routes rows through the
    compressed per-row oracle; both paths must agree on finiteness, values
    (to summation-order tolerance), and — for downstream allocation — the
    per-row argmax decision."""
    from repro.core.hlem import BATCH_NP_N_CUTOVER
    n = BATCH_NP_N_CUTOVER + 64
    rng = np.random.default_rng(b)
    free = rng.uniform(0, 50, (n, 4))
    free[:, 3] = 7.0  # degenerate column survives both paths
    masks = rng.random((b, n)) < 0.6
    masks[-1] = False  # fully-masked row
    spot = rng.uniform(0, 1, (n, 4))
    alphas = np.where(rng.random(b) < 0.5, -0.5, 0.0)
    routed = hlem_scores_batch_np(free, masks, spot, alphas)
    # routed rows are exactly the per-row oracle
    for i in range(b):
        want = hlem_scores_np(free, masks[i], spot, alphas[i])
        np.testing.assert_array_equal(routed[i], want)
    # and agree with the broadcast core across the crossover
    forced = hlem_scores_batch_np(free, masks, spot, alphas,
                                  n_cutover=10 ** 9)
    finite = np.isfinite(forced)
    assert np.array_equal(np.isfinite(routed), finite)
    np.testing.assert_allclose(routed[finite], forced[finite],
                               rtol=1e-9, atol=1e-12)
    for i in range(b):
        if masks[i].any():
            assert np.argmax(routed[i]) == np.argmax(forced[i])


def test_batch_np_just_below_cutover_uses_broadcast_core():
    """At n <= cutover the broadcast core is untouched (bit-for-bit) — the
    trace-scale flush depends on its exact numerics."""
    from repro.core.hlem import BATCH_NP_N_CUTOVER
    rng = np.random.default_rng(99)
    n, b = 64, 5
    assert n <= BATCH_NP_N_CUTOVER
    free = rng.uniform(0, 50, (n, 4))
    masks = rng.random((b, n)) < 0.6
    spot = rng.uniform(0, 1, (n, 4))
    auto = hlem_scores_batch_np(free, masks, spot, -0.5)
    forced = hlem_scores_batch_np(free, masks, spot, -0.5,
                                  n_cutover=10 ** 9)
    np.testing.assert_array_equal(auto, forced)


def test_fused_pick_matches_scores_argmax():
    rng = np.random.default_rng(23)
    for trial in range(50):
        n = int(rng.integers(2, 80))
        free = rng.uniform(0, 10, (n, 4))
        if trial % 3 == 0:
            free[:, 1] = 5.0                  # degenerate dim
        if trial % 7 == 0:
            free[:] = free[0]                 # all dims degenerate
        if trial % 5 == 0 and n >= 4:
            free[2] = free[1]                 # exact duplicate hosts (ties)
        mask = rng.random(n) < 0.6
        spot = rng.uniform(0, 1, (n, 4))
        alpha = float(rng.choice([0.0, -0.5, 0.7]))
        got = hlem_pick_np(free, mask, spot, alpha)
        if not mask.any():
            assert got == -1
        else:
            assert got == int(np.argmax(hlem_scores_np(free, mask, spot,
                                                       alpha)))


def _pick_cases(rng, m):
    """(name, free, mask, spot) candidate sets of m hosts among m + m // 3
    rows with trace-like capacities, and a set of two."""
    n = m + m // 3
    cap = rng.choice([16.0, 32.0, 64.0], size=n)[:, None] * np.array(
        [1.0, 1_536.0, 625.0, 25_000.0])
    free = cap * rng.uniform(0.0, 1.0, (n, 4))
    spot = rng.uniform(0.0, 0.5, (n, 4))
    mask = np.zeros(n, dtype=bool)
    mask[rng.permutation(n)[:m]] = True
    partly = free.copy()
    partly[:, 1] = 3_072.0                    # one dimension degenerate
    flat = np.broadcast_to(free[0], free.shape).copy()    # every dimension
    # seven distinct (free, spot) rows, each held by two candidates or none:
    # the best score is held by several hosts, and the pick breaks the tie
    # to the first
    pairs = rng.integers(0, 7, m)
    pairs[1::2] = pairs[0::2][: m // 2]
    pairs[-1] = pairs[0] if m % 2 else pairs[-1]
    kind = rng.integers(0, 7, n)
    kind[mask] = rng.permutation(pairs)
    two = np.zeros(n, dtype=bool)
    two[np.flatnonzero(mask)[:2]] = True
    return [("random", free, mask, spot), ("partly", partly, mask, spot),
            ("flat", flat, mask, spot),
            ("duplicates", free[kind], mask, spot[kind]),
            ("two", free, two, spot)]


@pytest.mark.parametrize("m,m_cutover", [
    (PICK_NP_M_CUTOVER // 4, None),           # rows, below the cutover
    (PICK_NP_M_CUTOVER + 1_000, None),        # columns, above it
    (300, 0),                                 # columns forced
])
def test_pick_layouts_match_the_oracle(m, m_cutover):
    """Both layouts of the fused pick take the oracle's host, ties to the
    first, and derive its standardized capacities and weights bit for
    bit."""
    rng = np.random.default_rng(m)
    for name, free, mask, spot in _pick_cases(rng, m):
        idx = np.flatnonzero(mask)
        c_want, w_want = hlem_weights_np(free, mask)
        c_got, w_got = hlem._candidate_weights_np(free, idx, False,
                                                  m_cutover)
        assert np.array_equal(w_got, w_want), name
        assert np.array_equal(c_got, c_want[mask]), name
        for alpha in (0.0, -0.5, 0.7):
            scores = hlem_scores_np(free, mask, spot, alpha)
            want = int(np.argmax(scores))
            if name == "duplicates":
                assert np.count_nonzero(scores == scores[want]) > 1
            assert hlem_pick_candidates_np(free, idx, spot, alpha,
                                           m_cutover) == want, (name, alpha)


def _decision_spec(workload: str, backend: str):
    from repro.api import (MigrationSpec, ObsSpec, PolicySpec, RunSpec,
                           ScenarioSpec)
    policy = PolicySpec("hlem-vmp-adjusted",
                        {"alpha": -0.5, "backend": backend})
    if workload == "trace":
        return RunSpec(
            scenario=ScenarioSpec(workload="trace", horizon=3600.0,
                                  workload_params={"n_machines": 300,
                                                   "load_per_machine": 3.6,
                                                   "sim_days": 0.05,
                                                   "n_spot": 300}),
            policy=policy, obs=ObsSpec(events=True))
    return RunSpec(
        scenario=ScenarioSpec(workload="market", regime="volatile",
                              n_pools=4, from_advisor=True,
                              workload_params={"fleet_scale": 1.7},
                              bid={"strategy": "randomized",
                                   "params": {"lo": 0.45}},
                              horizon=3600.0),
        policy=policy, migration=MigrationSpec("gradient-aware"),
        obs=ObsSpec(events=True))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("workload", ["trace", "market"])
def test_column_pick_changes_no_decision_in_a_run(workload, backend,
                                                  monkeypatch):
    """A trace run at 300 machines and an hour of a volatile market day
    make every decision alike with the pick's cutover at its default,
    forced to 0 (the column statistics for every set) and above every set
    (numpy's axis-0 reductions for every set): zero-divergence event
    logs."""
    from repro.api import build
    from repro.obs.diff import first_divergence
    spec = _decision_spec(workload, backend)
    default = build(spec, 0)
    default.run(until=3600.0)
    assert len(default.events) > 0
    weights = hlem._candidate_weights_np
    for cutover in (0, 2 ** 62):
        weighed = []

        def spy(free, idx, flat_exit, m_cutover=None):
            weighed.append(idx.size)
            return weights(free, idx, flat_exit, m_cutover)

        monkeypatch.setattr(hlem, "PICK_NP_M_CUTOVER", cutover)
        monkeypatch.setattr(hlem, "_candidate_weights_np", spy)
        forced = build(spec, 0)
        forced.run(until=3600.0)
        assert len(weighed) > 0
        assert first_divergence(default.events, forced.events) is None
        if backend == "jax":
            # the float64 picks after fallbacks ran
            assert forced.policy.device_fallbacks == len(weighed)


def test_host_exact_rows_counts_the_fallbacks_candidates():
    from repro.core.allocation import HlemVmp
    from repro.core.hosts import HostPool
    from repro.core.types import make_on_demand, resources
    from repro.obs import Tracer
    pool = HostPool()
    for _ in range(4):
        pool.add_host(resources(16, 24_576, 10_000, 400_000))
    # hosts 0 and 1 float32 cannot order (as in the test below); host 3
    # is full
    pool.place(make_on_demand(98, resources(1, 1_024.0000001, 10, 1_000),
                              60.0), 0)
    pool.place(make_on_demand(99, resources(1, 1_024, 10, 1_000), 60.0), 1)
    pool.place(make_on_demand(97, resources(1, 2_048, 10, 1_000), 60.0), 2)
    pool.place(make_on_demand(96, resources(16, 1_024, 10, 1_000), 60.0), 3)
    vm = make_on_demand(0, resources(1, 1_024, 10, 1_000), 60.0)
    mask = pool.direct_mask_into(vm.demand).copy()
    assert np.count_nonzero(mask) == 3
    pol = HlemVmp(backend="jax")
    pol.tracer = Tracer(keep_records=False, profile=True)
    pol.tracer.begin("allocation", "place")   # the pick's spans nest in it
    assert pol._score_pick(mask, vm, pool) == 1
    pol.tracer.end(0.0)
    assert pol.device_fallbacks == 1
    assert pol.tracer.counters.values["pick/host_exact_rows"] == 3
    assert pol.tracer.profile()[("allocation", "pick/host-exact")][0] == 1


# ---------------------------------------------------------------------------
# device picks certified against the float64 oracle
# ---------------------------------------------------------------------------
def test_certified_pick_identical_rows_break_to_first():
    from repro.core.hlem import certified_pick
    free = np.array([[1.0, 2, 3, 4], [5, 6, 7, 8], [1, 2, 3, 4], [5, 6, 7, 8]])
    spot = np.zeros((4, 4))
    scores = np.array([0.2, 0.9, 0.2, 0.9], np.float32)
    assert certified_pick(scores, 1e-3, free, spot) == 1


def test_certified_pick_defers_a_near_tie_of_distinct_rows():
    from repro.core.hlem import certified_pick
    free = np.array([[1.0, 2, 3, 4], [5, 6, 7, 8], [5, 6, 7, 9]])
    spot = np.zeros((3, 4))
    scores = np.array([0.2, 0.9, 0.8995], np.float32)
    assert certified_pick(scores, 1e-3, free, spot) is None
    assert certified_pick(scores, 1e-4, free, spot) == 1


@pytest.mark.parametrize("case", ["random", "near_uniform", "narrow_span"])
def test_score_tolerance_bounds_float32_error(case):
    """The bound the device returns covers the float32 error of every
    score difference near the top, also where the entropies sit near 1
    (weights amplify their rounding) and where large capacities differ by
    little (float32 rounds the inputs on the scale of the span)."""
    from repro.core.hlem import hlem_scores_tol_jax
    rng = np.random.default_rng(hash(case) % 2 ** 32)
    n = 3000
    free = rng.uniform(0, 100, (n, 4))
    if case == "near_uniform":
        free[:] = [64.0, 98_304.0, 20_000.0, 800_000.0]
        free[: n // 100] -= rng.uniform(0, 4, (n // 100, 4)) * [1, 2048, 10,
                                                                1000]
    elif case == "narrow_span":
        free = 98_304.0 + rng.uniform(0, 0.05, (n, 4))
    mask = rng.random(n) < 0.8
    spot = rng.uniform(0, 1, (n, 4))
    for alpha in (0.0, -0.5):
        s64 = hlem_scores_np(free, mask, spot, alpha)
        s32, tol = hlem_scores_tol_jax(free, mask, spot, np.float32(alpha))
        s32 = np.asarray(s32, np.float64)
        b = int(np.argmax(s64))
        near = mask & (s64 >= s64[b] - 0.05)
        err = np.abs((s32 - s32[b]) - (s64 - s64[b]))[near].max()
        assert err <= float(tol)


def test_jax_backend_breaks_float32_near_ties_like_the_oracle():
    """Two hosts whose float64 scores differ by less than float32 can
    resolve: the device pick defers to the exact pick and agrees."""
    from repro.core.allocation import HlemVmp
    from repro.core.hosts import HostPool
    from repro.core.types import make_on_demand, resources
    pool = HostPool()
    pool.add_host(resources(16, 24_576, 10_000, 400_000))
    pool.add_host(resources(16, 24_576, 10_000, 400_000))
    pool.add_host(resources(16, 24_576, 10_000, 400_000))
    # host 1 ends up 1e-7 RAM freer than host 0: float32 rounds the two
    # rows (and scores) together, float64 prefers host 1
    pool.place(make_on_demand(98, resources(1, 1_024.0000001, 10, 1_000),
                              60.0), 0)
    pool.place(make_on_demand(99, resources(1, 1_024, 10, 1_000), 60.0), 1)
    pool.place(make_on_demand(97, resources(1, 2_048, 10, 1_000), 60.0), 2)
    vm = make_on_demand(0, resources(1, 1_024, 10, 1_000), 60.0)
    mask = pool.direct_mask_into(vm.demand).copy()
    want = hlem_pick_np(pool.free(), mask, pool.spot_frac_view(), 0.0)
    assert want == 1
    pol = HlemVmp(backend="jax")
    assert pol._score_pick(mask, vm, pool) == want
    assert pol.device_picks == 1 and pol.device_fallbacks == 1


@pytest.mark.parametrize("policy", ["hlem-vmp", "hlem-vmp-adjusted"])
def test_jax_backend_decisions_equal_numpy_on_trace(policy):
    """Every placement of a seeded trace run is the same with device
    scoring as with the float64 host oracle: equal metrics rows and a
    zero-divergence event log."""
    from repro.api import ObsSpec, PolicySpec, RunSpec, ScenarioSpec, build
    from repro.api.build import collect_row
    from repro.obs.diff import first_divergence
    runs = {}
    for backend in ("numpy", "jax"):
        spec = RunSpec(
            scenario=ScenarioSpec(workload="trace", horizon=1800.0,
                                  workload_params={"n_machines": 40,
                                                   "sim_days": 0.05,
                                                   "n_spot": 300}),
            policy=PolicySpec(policy, {"backend": backend}),
            obs=ObsSpec(events=True))
        sim = build(spec, 0)
        runs[backend] = (sim, collect_row(sim, sim.run(until=1800.0),
                                          spec, 0))
    (sim_np, row_np), (sim_jx, row_jx) = runs["numpy"], runs["jax"]
    assert row_np == row_jx
    assert row_np["allocations"] > 0
    assert first_divergence(sim_np.events, sim_jx.events) is None
    assert sim_jx.policy.device_picks > 0
