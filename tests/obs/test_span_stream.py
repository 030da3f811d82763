"""Golden record of the tracer's output on three fixed-seed runs.

Each case runs a traced simulation and compares it with
``tests/obs/data/span_stream.json``: the number of spans per
``(cat, name)``, and the sha256 of the canonical JSON of
:meth:`~repro.obs.tracer.Tracer.deterministic_view` (spans with their
simulated times and args, instants, the counter series and the final
counters).  A refactor of the instrumentation must leave both unchanged.

Adding, removing or renaming a span, an instant or a counter changes the
record on purpose: re-record the file with

    PYTHONPATH=src python tests/obs/test_span_stream.py

and commit it with the change that moved it.

The cases together also cover every span a per-layer reader under
``bench/metrics/`` reads, so a reader cannot lose its span unnoticed.
"""
import ast
import hashlib
import json
import pathlib

import pytest

from repro.api import (
    AutoscaleSpec,
    BidSpec,
    FaultSpec,
    FleetSpec,
    MigrationSpec,
    ObsSpec,
    PolicySpec,
    RunSpec,
    ScenarioSpec,
    ServeSpec,
    build,
)
from repro.core.allocation import make_policy
from repro.market.engine import MarketEngine
from repro.market.migration import make_migration_planner
from repro.market.pools import make_market
from repro.market.pricing import realized_cost_stats
from repro.market.trace import TraceConfig, generate_trace, simulate_trace
from repro.obs import Tracer

SEED = 3
DATA = pathlib.Path(__file__).parent / "data" / "span_stream.json"
METRICS = pathlib.Path(__file__).parents[2] / "bench" / "metrics"
POLICY = PolicySpec("hlem-vmp-adjusted", {"alpha": -0.5, "backend": "jax"})
OBS = ObsSpec(trace=True, counters_every=600.0)


def _serve_storms():
    """Serving on a diversified fleet under interruption storms: the tick's
    faults, storms and fleet phases, serve ticks, autoscaling, billing."""
    sim = build(RunSpec(
        scenario=ScenarioSpec(workload="serve-diurnal", regime="volatile",
                              n_pools=4, horizon=7200.0),
        policy=POLICY,
        migration=MigrationSpec("gradient-aware"),
        fleet=FleetSpec("diversified", {"target_capacity": 24.0}),
        faults=FaultSpec("storm"),
        serve=ServeSpec(),
        autoscale=AutoscaleSpec("target-tracking"),
        obs=OBS), SEED)
    sim.run(until=7200.0)
    realized_cost_stats(sim.all_vms(), sim.engine, sim.pool)
    return sim.obs


def _market_hour():
    """A volatile market hour with randomized bids and migration: the
    batched flush's memo and feasibility spans, device and host-exact
    picks, and placements inside ``dispatch/migrate-start``."""
    sim = build(RunSpec(
        scenario=ScenarioSpec(workload="market", regime="volatile",
                              bid=BidSpec("randomized", {"lo": 0.45})),
        policy=POLICY,
        migration=MigrationSpec("gradient-aware"),
        obs=OBS), SEED)
    sim.run(until=3600.0)
    return sim.obs


def _trace_market():
    """``simulate_trace`` with an engine, a planner and a tracer: the
    instrumentation wiring of the trace entry point."""
    cfg = TraceConfig(seed=SEED, n_machines=20, sim_days=0.05, n_spot=60)
    tracer = Tracer(keep_records=True, counters_every=600.0)
    simulate_trace(
        generate_trace(cfg),
        policy=make_policy("hlem-vmp-adjusted", alpha=-0.5, backend="jax"),
        cfg=cfg,
        engine=MarketEngine(make_market("volatile", n_pools=1, seed=SEED)),
        migration=make_migration_planner("gradient-aware"),
        obs=tracer)
    return tracer


CASES = {
    "serve-storms": _serve_storms,
    "market-hour": _market_hour,
    "trace-market": _trace_market,
}


def _record(tracer):
    counts = {}
    for cat, name, *_ in tracer.spans:
        key = f"{cat}:{name}"
        counts[key] = counts.get(key, 0) + 1
    view = json.dumps(tracer.deterministic_view(), sort_keys=True,
                      separators=(",", ":"))
    return {"span_counts": dict(sorted(counts.items())),
            "sha256": hashlib.sha256(view.encode()).hexdigest()}


def _read_spans():
    """``(cats, name, exact)`` for every span a reader under
    ``bench/metrics/`` reads through ``self_share`` or ``mean_us``."""
    wanted = []
    for path in sorted(METRICS.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)):
                continue
            args = [ast.literal_eval(a) for a in node.args[1:]]
            if node.func.id == "self_share":
                wanted.append((tuple(args[0]),
                               args[1] if len(args) > 1 else "", False))
            elif node.func.id == "mean_us":
                wanted.append(((args[0],), args[1], True))
    return wanted


@pytest.mark.parametrize("case", sorted(CASES))
def test_span_stream_matches_record(case):
    want = json.loads(DATA.read_text())[case]
    got = _record(CASES[case]())
    assert got["span_counts"] == want["span_counts"]
    assert got["sha256"] == want["sha256"]
    # the record as a whole holds every span the per-layer readers read
    seen = [key.split(":", 1) for rec in json.loads(DATA.read_text()).values()
            for key in rec["span_counts"]]
    wanted = _read_spans()
    assert wanted, "no span readers found under bench/metrics/"
    for cats, name, exact in wanted:
        assert any(cat in cats and (n == name if exact
                                    else n.startswith(name))
                   for cat, n in seen), (cats, name)


if __name__ == "__main__":
    DATA.parent.mkdir(exist_ok=True)
    DATA.write_text(json.dumps({case: _record(run())
                                for case, run in sorted(CASES.items())},
                               indent=1, sort_keys=True) + "\n")
    print(f"recorded {DATA}")
