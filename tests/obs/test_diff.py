"""First-divergence diffing: the bit-identity debugging tool.

Acceptance pair (ISSUE 8): diffing PR 5's fused vectorized engine against
the scalar oracle at the same seed reports **zero divergence**, while a
deliberately perturbed run (one flipped bid) yields a correctly located
first-divergence event."""
import pytest

from repro.api import (
    BidSpec,
    MigrationSpec,
    ObsSpec,
    PolicySpec,
    RunSpec,
    ScenarioSpec,
    build,
)
from repro.obs import (
    EventLog,
    bisect_divergence,
    first_divergence,
    format_divergence,
)

EVENTS_ON = ObsSpec(events=True)


def _market_spec(**overrides):
    kw = dict(
        scenario=ScenarioSpec(workload="market", regime="volatile",
                              bid=BidSpec("randomized", {"lo": 0.45})),
        policy=PolicySpec("hlem-vmp-adjusted", {"alpha": -0.5}),
        migration=MigrationSpec("gradient-aware"),
        obs=EVENTS_ON)
    kw.update(overrides)
    return RunSpec(**kw)


def _attach(sim, log):
    """Swap a custom (e.g. windowed) recorder into every emit site."""
    sim.instrument(events=log)


def _flip_one_bid(sim, after=300.0):
    """Perturb one spot VM's bid (the deliberate divergence); returns the
    VM and its submit time."""
    vm = min((v for v in sim.vms.values()
              if v.bid != float("inf") and v.submit_time >= after),
             key=lambda v: v.submit_time)
    vm.bid *= 1.01
    return vm


# ---------------------------------------------------------------------------
# streaming diff basics
# ---------------------------------------------------------------------------
def test_identical_and_diverging_iterables():
    a = [(0.0, "start", 1, 0, 0, 0.0, 0.0, None),
         (1.0, "finish", 1, 0, 0, 0.0, 0.0, None)]
    assert first_divergence(a, list(a)) is None
    b = [a[0], (1.0, "interrupt", 1, 0, 0, 0.0, 0.0, "price")]
    div = first_divergence(a, b, context=3)
    assert div.index == 1
    assert div.record_a[1] == "finish" and div.record_b[1] == "interrupt"
    assert div.time == 1.0
    assert div.context == [a[0]]


def test_one_stream_ends_early():
    a = [(0.0, "start", 1, 0, 0, 0.0, 0.0, None),
         (1.0, "finish", 1, 0, 0, 0.0, 0.0, None)]
    div = first_divergence(a, a[:1])
    assert div.index == 1
    assert div.record_a is not None and div.record_b is None
    assert "<stream ended>" in format_divergence(div)


def test_format_divergence_strings():
    assert "zero divergence" in format_divergence(None)
    a = [(0.0, "start", 1, 2, 3, 0.5, 0.0, "x")]
    div = first_divergence(a, [])
    text = format_divergence(div, label_a="A", label_b="B")
    assert "record #0" in text and "vm=1" in text and "pool=2" in text


# ---------------------------------------------------------------------------
# acceptance: vectorized engine vs scalar oracle — zero divergence
# ---------------------------------------------------------------------------
def test_vectorized_vs_scalar_oracle_zero_divergence():
    logs = []
    for vectorized in (True, False):
        sim = build(_market_spec(), 0)
        sim.engine.use_vectorized = vectorized
        sim.run(until=3600.0)
        logs.append(sim.events)
    assert len(logs[0]) > 100
    div = first_divergence(logs[0], logs[1])
    assert div is None, format_divergence(div, "vectorized", "oracle")


# ---------------------------------------------------------------------------
# acceptance: one flipped bid — divergence correctly located
# ---------------------------------------------------------------------------
def test_flipped_bid_divergence_located():
    sim_a = build(_market_spec(), 0)
    sim_b = build(_market_spec(), 0)
    flipped = _flip_one_bid(sim_b, after=300.0)
    sim_a.run(until=3600.0)
    sim_b.run(until=3600.0)
    div = first_divergence(sim_a.events, sim_b.events)
    assert div is not None
    # the first divergent record is exactly the perturbed VM's submit
    # event (it carries the bid in payload a) — nothing before it differs
    assert div.time == pytest.approx(flipped.submit_time)
    assert div.record_a[1] == "submit" and div.record_b[1] == "submit"
    assert div.record_a[2] == flipped.id and div.record_b[2] == flipped.id
    assert div.record_a[5] != div.record_b[5]      # the flipped bid
    assert len(div.context) == 5                    # shared prefix window


# ---------------------------------------------------------------------------
# windowed-rerun bisection
# ---------------------------------------------------------------------------
def test_bisect_divergence_narrows_to_flip():
    t_end = 2400.0

    def make_logs(t0, t1):
        out = []
        for perturb in (False, True):
            sim = build(_market_spec(obs=None), 0)
            if perturb:
                _flip_one_bid(sim, after=300.0)
            _attach(sim, EventLog(t_min=t0, t_max=t1))
            sim.run(until=t_end)
            out.append(sim.events)
        return out[0], out[1]

    # recover the true divergence time from one un-windowed reference pair
    a, b = make_logs(0.0, t_end)
    t_true = first_divergence(a, b).time

    div, (lo, hi) = bisect_divergence(make_logs, t_end, min_window=600.0)
    assert hi - lo <= 600.0 + 1e-9
    assert lo <= t_true < hi
    assert div is not None and div.time == pytest.approx(t_true)


def test_bisect_divergence_identical_runs():
    def make_logs(t0, t1):
        out = []
        for _ in range(2):
            sim = build(_market_spec(obs=None), 3)
            _attach(sim, EventLog(t_min=t0, t_max=t1))
            sim.run(until=1200.0)
            out.append(sim.events)
        return out[0], out[1]

    div, window = bisect_divergence(make_logs, 1200.0, min_window=600.0)
    assert div is None
