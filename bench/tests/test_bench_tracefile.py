"""The trace reduction and the scorer's roofline arithmetic, on a small
synthetic trace laid out as a TPU trace is: a host plane with the
benchmark's annotations, a device plane with ``XLA Modules`` and ``XLA
Ops`` lines, times in nanoseconds."""
import pytest

from bench import roofline, run, tracefile

SCORER = "jit_hlem_scores_tol_jax(123)"


def synthetic():
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ("bench/window", 1_000, 10_000),
        ("bench/build", 1_000, 2_000),
        ("bench/chunk", 3_000, 8_000)]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [(SCORER, 4_000, 1_000),
                                           (SCORER, 7_000, 1_000),
                                           ("jit_other", 500, 400)]},
        {"name": "XLA Ops", "events": [
            ("%fusion.1 = f32[4] fusion(x)", 4_000, 600),
            ("%fusion.2 = f32[] fusion(y)", 4_400, 400),   # overlaps
            ("%fusion.1 = f32[4] fusion(x)", 7_000, 500),
            ("%copy = f32[4] copy(z)", 10_800, 400)]},      # past the end
    ]}
    return [host, dev, {"name": "/host:metadata", "lines": []}]


def test_busy_window_and_programs():
    r = tracefile.reduce(synthetic())
    assert r["window_s"] == pytest.approx(10e-6)
    # union of [4000,4800], [7000,7500], [10800,11000] clipped to the window
    assert r["busy_s"] == pytest.approx((800 + 500 + 200) * 1e-9)
    assert list(r["modules"]) == [SCORER]
    assert r["modules"][SCORER]["calls"] == 2
    assert r["modules"][SCORER]["seconds"] == pytest.approx(2e-6)
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "%fusion.1" and "%copy" in names


def test_idle_gaps_are_named_by_the_innermost_annotation():
    r = tracefile.reduce(synthetic())
    gaps = r["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench/chunk", "bench/build",
                                    "bench/chunk"]
    assert [g[1] for g in gaps] == pytest.approx([3.3e-6, 3.0e-6, 2.2e-6])
    total_idle = sum(g[1] for g in gaps)
    assert total_idle + r["busy_s"] == pytest.approx(r["window_s"])


def test_no_device_or_no_window_reads_nothing():
    host, dev, meta = synthetic()
    assert tracefile.reduce([host, meta]) is None
    assert tracefile.reduce([dev]) is None


def test_scorer_bytes_and_least_time():
    rows = 16_384
    assert roofline.scorer_bytes(rows) == 615_432
    least = roofline.scorer_least_s(rows, "TPU v5 lite")
    assert least == pytest.approx(615_432 / 819e9)
    assert least > roofline.scorer_flops(rows) / 197e12   # bound by bytes
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_device_readers():
    r = tracefile.reduce(synthetic())
    ctx = {"trace": r, "scorer_rows": 256, "device_kind": "TPU v5 lite"}
    share = run.reader("scorer_roofline")(ctx)
    assert share == pytest.approx(
        100 * 2 * roofline.scorer_least_s(256, "TPU v5 lite") / 2e-6)
    assert 0 < share <= 100
    idle = run.reader("device_idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - 1.5e-6 / 10e-6))
    assert run.reader("device_idle_share")({"trace": None}) is None
    assert run.reader("scorer_roofline")(
        {"trace": None, "scorer_rows": 256}) is None


def test_span_and_counter_readers():
    prof = {("event-loop", "dispatch/vm-submit"): [10, 3.0, 2.0],
            ("event-loop", "dispatch/price-tick"): [2, 2.0, 0.5],
            ("allocation", "flush/batched"): [5, 1.0, 1.0],
            ("market-tick", "tick/wave"): [2, 0.2, 0.2],
            ("migration", "plan/gradient-aware"): [2, 0.1, 0.1]}
    ctx = {"profile": prof, "window_s": 5.0, "device_picks": 40,
           "device_fallbacks": 10}
    assert run.reader("dispatch_self_share")(ctx) == pytest.approx(50.0)
    assert run.reader("flush_self_share")(ctx) == pytest.approx(20.0)
    assert run.reader("tick_self_share")(ctx) == pytest.approx(6.0)
    assert run.reader("device_fallback_share")(ctx) == pytest.approx(25.0)
    none = {"profile": {}, "window_s": 5.0, "device_picks": 0}
    assert run.reader("tick_self_share")(none) is None
    assert run.reader("device_fallback_share")(none) is None
