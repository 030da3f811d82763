"""Spans inside placement, the device pick and the resubmission flush, on a
small market day with randomized bids and migration, scored by the jax
backend: their counts match the policy's own pick counters over a window,
they nest where they are placed, tracing changes neither the metrics nor
the event log, and every span is mirrored onto the ``jax.profiler``
trace's host plane."""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.api import (MigrationSpec, ObsSpec, PolicySpec, RunSpec,
                       ScenarioSpec, build)
from repro.api.build import collect_row
from repro.core.hlem import (PICK_RUN_K, device_arg_bytes, dirty_capacity,
                             pack_pick, pack_run)
from repro.obs import Tracer, first_divergence

SEED = 5
#: the window the counts are compared over, in simulated seconds
WINDOW = (900.0, 2700.0)


def _spec(traced: bool) -> RunSpec:
    obs = (ObsSpec(trace=True, profile=True, events=True) if traced
           else ObsSpec(events=True))
    return RunSpec(
        scenario=ScenarioSpec(workload="market", regime="volatile",
                              bid={"strategy": "randomized",
                                   "params": {"lo": 0.45}}),
        policy=PolicySpec("hlem-vmp-adjusted",
                          {"alpha": -0.5, "backend": "jax"}),
        migration=MigrationSpec("gradient-aware"),
        obs=obs)


def _picks(sim):
    return sim.policy.device_picks, sim.policy.device_fallbacks


@pytest.fixture(scope="module")
def runs():
    """(traced, untraced) simulators run to the window's end, and the
    traced run's pick counters and profile at the window's start."""
    out = {}
    for traced in (True, False):
        sim = build(_spec(traced), SEED)
        sim.run(until=WINDOW[0])
        start = None
        if traced:
            start = (_picks(sim), {k: list(v) for k, v in
                                   sim.obs.profile().items()},
                     dict(sim.obs.counters.values))
        metrics = sim.run(until=WINDOW[1])
        out[traced] = (sim, metrics, start)
    return out


def _count(prof, name, before=None):
    n = prof.get(("allocation", name), [0])[0]
    return n - (before or {}).get(("allocation", name), [0])[0]


def test_pick_spans_count_the_policys_picks_over_a_window(runs):
    sim, _, ((picks0, falls0), prof0, counters0) = runs[True]
    picks, falls = _picks(sim)
    prof = sim.obs.profile()
    c = sim.obs.counters.values

    def sent(name):
        return c.get(name, 0) - counters0.get(name, 0)

    calls, runs = sent("pick/device_calls"), sent("pick/run_calls")
    assert picks - picks0 > 0 and falls - falls0 > 0 and runs > 0
    assert _count(prof, "pick/call", prof0) == calls
    assert _count(prof, "pick/readback", prof0) == calls
    assert _count(prof, "pick/host-exact", prof0) == falls - falls0
    # a single call is one pick; a run call one for each step the host
    # used that placed
    assert calls - runs <= picks - picks0 <= (calls - runs
                                               + sent("pick/run_taken"))
    # every call sends one packed array, of a length fixed by the storage
    # (a single pick's: a mask byte a row, then alpha, K row ids and their
    # 2 x D float32 values in words; a run's: PICK_RUN_K mask bytes a row,
    # three words and the steps' parameters, the same rows), and an upload
    # sends the whole storage in float32 (a run's totals: half of that)
    free, spot_frac = sim.pool.storage_views()
    rows, d = free.shape
    k_cap = dirty_capacity(rows)
    per_call = pack_pick(np.zeros(rows, dtype=bool), 0.0, [], free,
                         spot_frac).nbytes
    assert per_call == -(-rows // 4) * 4 + 4 * (1 + k_cap * (1 + 2 * d))
    per_run = pack_run(np.zeros((1, rows), dtype=np.uint8),
                       np.zeros((1, 2 + 2 * d)), 0.95, 0.0, PICK_RUN_K, [],
                       free, spot_frac).nbytes
    assert per_run == PICK_RUN_K * (-(-rows // 4) * 4 + 4 * (2 + 2 * d)) \
        + 4 * (3 + k_cap * (1 + 2 * d))
    per_tot = rows * d * 4
    assert sent("pick/h2d_bytes") == ((calls - runs) * per_call
                                      + runs * per_run
                                      + sent("pick/mirror_uploads")
                                      * 2 * per_tot
                                      + sent("pick/tot_uploads") * per_tot)
    # a float64 pick after a fallback scores the candidates, at least the
    # two hosts float32 could not order
    hosts = sim.pool.free().shape[0]
    assert (2 * (falls - falls0) <= sent("pick/host_exact_rows")
            <= (falls - falls0) * hosts)
    # the run's first pick uploads; after it, rewritten rows ride along
    assert c["pick/mirror_uploads"] >= 1
    assert 0 < sent("pick/dirty_rows") <= calls * k_cap


def test_h2d_bytes_leave_out_arguments_already_on_the_device():
    import jax.numpy as jnp

    host = np.zeros((16, 4))
    assert device_arg_bytes(host, np.float32(0.5)) == 16 * 4 * 4 + 4
    assert device_arg_bytes(jnp.asarray(host), np.float32(0.5)) == 4


def test_flush_counters_and_spans(runs):
    sim, _, _ = runs[True]
    c = sim.obs.counters.values
    prof = sim.obs.profile()
    assert c["flush/batch_calls"] == _count(prof, "flush/feasibility") > 0
    assert c["flush/batch_rows"] >= 2 * c["flush/batch_calls"]
    assert _count(prof, "flush/memo") > 0
    assert not any(k.startswith("alloc/") for k in c)


def _inside(child, parents):
    _c, _n, t0, dur = child[:4]
    return any(p[2] <= t0 and t0 + dur <= p[2] + p[3] for p in parents)


def test_spans_nest_where_they_are_placed(runs):
    spans = runs[True][0].obs.spans
    by = {}
    for rec in spans:
        by.setdefault(rec[1], []).append(rec)
    places = by["place"]
    flushes = [r for r in spans if r[1] in ("flush/batched", "flush/per_vm")]
    picks = [r for r in spans if r[1].startswith("pick/")]
    assert places and flushes and picks
    # a pick is made either placing a VM (on submission, or a migration's
    # destination) or inside a flush
    in_place = [r for r in picks if _inside(r, places)]
    assert len(in_place) > 0
    assert all(_inside(r, places) or _inside(r, flushes) for r in picks)
    migrations = by["dispatch/migrate-start"]
    assert migrations and any(_inside(r, migrations) for r in places)
    # the policy's spans take the simulated time of the span around them
    for r in picks:
        around = [p for p in places + flushes if _inside(r, [p])]
        assert r[4] is not None and r[4] == around[0][4]
    for name in ("flush/memo", "flush/feasibility"):
        assert by[name] and all(_inside(r, flushes) for r in by[name])
    # the tracer's own nesting: a placement's self time leaves out its picks
    prof = runs[True][0].obs.profile()
    _n, total, self_s = prof[("allocation", "place")]
    assert self_s < total


def test_tracing_changes_neither_metrics_nor_the_event_log(runs):
    (on, m_on, _), (off, m_off, _) = runs[True], runs[False]
    spec = _spec(False)
    row_on = collect_row(on, m_on, spec, SEED)
    row_off = collect_row(off, m_off, spec, SEED)
    assert json.dumps(row_on, sort_keys=True) == json.dumps(row_off,
                                                            sort_keys=True)
    assert _picks(on) == _picks(off)
    assert len(on.events) > 0
    assert first_divergence(on.events, off.events) is None


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    assert files
    data = ProfileData.from_file(str(files[-1]))
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for p in data.planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events]


def test_spans_reach_the_profilers_host_plane(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tr = Tracer(keep_records=False, profile=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tr.begin("allocation", "place")
        tr.begin("allocation", "pick/call")
        jnp.arange(8).sum().block_until_ready()
        tr.end(0.0)
        tr.end(0.0)
    finally:
        jax.profiler.stop_trace()
    notes = {name: (s, d) for name, s, d in _host_events(tmp_path)
             if name.startswith("repro/")}
    assert set(notes) == {"repro/allocation/place",
                          "repro/allocation/pick/call"}
    (ps, pd), (cs, cd) = (notes["repro/allocation/place"],
                          notes["repro/allocation/pick/call"])
    assert ps <= cs and cs + cd <= ps + pd
    assert tr.profile()[("allocation", "place")][0] == 1


def test_tracer_works_without_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro.obs import Tracer\n"
            "tr = Tracer(profile=True)\n"
            "tr.begin('a', 'b'); tr.end(1.0)\n"
            "assert tr._annotate is None\n"
            "assert tr.profile()[('a', 'b')][0] == 1\n"
            "assert 'jax' not in [m for m in sys.modules if sys.modules[m]]\n"
            "print('ok')\n")
    root = Path(__file__).resolve().parents[2]
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         env={"PYTHONPATH": str(root / "src"),
                              "PATH": "/usr/bin:/bin"},
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
