#!/usr/bin/env python3
"""Time the device pick's round trip, on one TPU chip.

Run from the repository root, on a machine with a TPU:

    PYTHONPATH=src python3 tools/pick_roundtrip.py [--rows R ...] [--iters N]
        [--way NAME ...] [--steps K ...]

For each storage size (rows of 4 float64 columns, as ``HostPool`` keeps
them; a quarter of the rows past the hosts, as at 12,600 hosts in 16,384
rows), one pick is made ``--iters`` times each way:

* ``args4``: ``hlem_scores_tol_jax`` on the float64 storage, the mask and
  alpha, four host arguments JAX converts and copies; the scores and the
  tolerance fetched one after the other.
* ``packed1``: the whole storage converted to float32 on the host and
  packed with the mask and alpha into one host array; one array fetched.
* ``resident``: ``ResidentScorer``, the mirror kept on the device, the
  packed input carrying the mask, alpha and two rewritten rows a pick; one
  array fetched.
* ``run``: ``ResidentScorer.scores_tol_run``, a run of K placements in one
  call (``hlem_scores_tol_jax_run``), for each K of ``--steps``: the
  packed input carrying K mask bytes a row, K steps' parameters and two
  rewritten rows; one (K, rows + ...) array fetched.  Its lines carry
  ``steps`` and the call's microseconds a step.

Per pick it reads the host clock around the call (conversion, transfer,
enqueue, and for ``resident`` the request of the copy back: ``call``), the
wait for the device (``block_until_ready``: ``wait``) and the copy back
(``fetch``); ``readback`` is wait plus fetch, as the program's
``pick/readback`` span counts it.  One JSON line per (rows, way, steps)
gives the median and quartiles in microseconds and the bytes a call sends;
lines per size check the resident scores and tolerance against
``hlem_scores_tol_jax`` bit for bit, the mirror against the storage in
float32, and a run's first step's pick against the resident pick.
Without a TPU the script exits 3; ``--rehearse`` runs it on whatever JAX
finds, a few iterations, to check the script itself (no measurement).
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import numpy as np


def _storage(rows: int, seed: int):
    rng = np.random.default_rng(seed)
    hosts = rows - rows // 4 if rows >= 64 else rows
    free = np.zeros((rows, 4))
    spot = np.zeros((rows, 4))
    cap = rng.choice([16.0, 32.0, 64.0], size=hosts)[:, None] * np.array(
        [1.0, 1_536.0, 625.0, 25_000.0])
    free[:hosts] = cap * rng.uniform(0.0, 1.0, (hosts, 4))
    spot[:hosts] = rng.uniform(0.0, 0.5, (hosts, 4))
    mask = np.zeros(rows, dtype=bool)
    mask[:hosts] = rng.uniform(size=hosts) < 0.5
    return free, spot, mask


def _quartiles(xs):
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    return {"median": q2, "q1": q1, "q3": q3}


def _run_way(steps: int):
    """The ``run`` way at ``steps`` placements a call: every step has the
    mask, odd steps also the primary list of its even rows, demands of one
    CPU and 1,536 MB.  The rows a call wrote go back with the next call,
    as ``HlemVmp`` sends them."""
    from repro.core.hlem import ResidentScorer, dirty_capacity, pack_run

    def run(free, spot, mask, alpha, state):
        if "mirror" not in state:
            rows, d = free.shape
            state["mirror"] = ResidentScorer()
            state["mirror"].upload(free, spot)
            state["mirror"].upload_tot(np.maximum(free, 1.0) * 2.0)
            state["k"] = 0
            prim = mask & (np.arange(rows) % 2 == 0)
            state["masks"] = np.where(np.arange(steps)[:, None] % 2,
                                      mask.view(np.uint8)
                                      | (prim.view(np.uint8) << 1),
                                      mask.view(np.uint8))
            state["wrote"] = []
            demand = np.array([1.0, 1_536.0, 10.0, 1_000.0][:d])
            par = np.concatenate([[alpha, 1.0], demand - 1e-9, demand])
            state["params"] = np.tile(par, (steps, 1))
        k = state["k"] = (state["k"] + 2) % (free.shape[0] - 2)
        free[[k, k + 1]] *= 0.999
        ids = sorted({k, k + 1, *state["wrote"]})
        t0 = time.perf_counter()
        sent = 0
        if len(ids) > dirty_capacity(free.shape[0]):
            sent, ids = state["mirror"].upload(free, spot), []
        packed = pack_run(state["masks"], state["params"], 0.95, 0.0,
                          steps, ids, free, spot)
        out = state["mirror"].scores_tol_run(packed)
        t1 = time.perf_counter()
        out.block_until_ready()
        t2 = time.perf_counter()
        state["out"] = np.asarray(out)
        t3 = time.perf_counter()
        picks = state["out"][:, free.shape[0] + 1]
        state["wrote"] = [int(p) for p in picks[picks >= 0]]
        return t1 - t0, t2 - t1, t3 - t2, sent + packed.nbytes

    return run


def _ways():
    import jax
    import jax.numpy as jnp

    from repro.core.hlem import (ResidentScorer, _scores_and_tol,
                                 hlem_scores_tol_jax, pack_pick)

    @jax.jit
    def packed_full(packed):
        rows = (packed.shape[0] - 4) // 33
        words = jax.lax.bitcast_convert_type(
            packed[: 32 * rows + 4].reshape(-1, 4), jnp.float32)
        free = words[: 4 * rows].reshape(rows, 4)
        spot = words[4 * rows: 8 * rows].reshape(rows, 4)
        alpha = words[8 * rows]
        mask = packed[32 * rows + 4:] != 0
        scores, tol = _scores_and_tol(free, mask, spot, alpha)
        return jax.lax.dynamic_update_slice(jnp.pad(scores, (0, 1)),
                                            tol[None], (rows,))

    def args4(free, spot, mask, alpha, _state):
        t0 = time.perf_counter()
        scores, tol = hlem_scores_tol_jax(free, mask, spot, np.float32(alpha))
        t1 = time.perf_counter()
        jax.block_until_ready((scores, tol))
        t2 = time.perf_counter()
        np.asarray(scores), float(tol)
        t3 = time.perf_counter()
        sent = 2 * free.size * 4 + mask.size + 4
        return t1 - t0, t2 - t1, t3 - t2, sent

    def packed1(free, spot, mask, alpha, _state):
        t0 = time.perf_counter()
        head = np.concatenate([free.astype("<f4").ravel(),
                               spot.astype("<f4").ravel(),
                               np.array([alpha], dtype="<f4")])
        packed = np.concatenate([head.view(np.uint8),
                                 mask.view(np.uint8)])
        out = packed_full(packed)
        t1 = time.perf_counter()
        out.block_until_ready()
        t2 = time.perf_counter()
        np.asarray(out)
        t3 = time.perf_counter()
        return t1 - t0, t2 - t1, t3 - t2, packed.nbytes

    def resident(free, spot, mask, alpha, state):
        if "mirror" not in state:
            state["mirror"] = ResidentScorer()
            state["mirror"].upload(free, spot)
            state["k"] = 0
        # two rows rewritten since the last pick, as placements do
        k = state["k"] = (state["k"] + 2) % (free.shape[0] - 2)
        ids = [k, k + 1]
        free[ids] *= 0.999
        t0 = time.perf_counter()
        packed = pack_pick(mask, alpha, ids, free, spot)
        out = state["mirror"].scores_tol(packed)
        t1 = time.perf_counter()
        out.block_until_ready()
        t2 = time.perf_counter()
        state["out"] = np.asarray(out)
        t3 = time.perf_counter()
        return t1 - t0, t2 - t1, t3 - t2, packed.nbytes

    return {"args4": args4, "packed1": packed1, "resident": resident}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, nargs="+", default=[256, 16_384])
    ap.add_argument("--iters", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--way", nargs="+",
                    default=["args4", "packed1", "resident", "run"])
    ap.add_argument("--steps", type=int, nargs="+",
                    default=[1, 2, 4, 8, 16, 32])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"pick_roundtrip: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 3
    iters = 5 if args.rehearse else args.iters
    device = {"platform": dev.platform, "kind": dev.device_kind}
    from repro.core.hlem import hlem_scores_tol_jax

    for rows in args.rows:
        ways = [(name, None, way) for name, way in _ways().items()
                if name in args.way]
        if "run" in args.way:
            ways += [("run", k, _run_way(k)) for k in args.steps]
        picks = {}
        for name, steps, way in ways:
            free, spot, mask = _storage(rows, args.seed)
            state = {}
            for _ in range(20):
                way(free, spot, mask, -0.5, state)
            calls, waits, fetches, sent = [], [], [], 0
            for _ in range(iters):
                c, w, f, sent = way(free, spot, mask, -0.5, state)
                calls.append(1e6 * c)
                waits.append(1e6 * w)
                fetches.append(1e6 * f)
            line = {"rows": rows, "way": name, "iters": iters,
                    "h2d_bytes": sent, "device": device,
                    "rehearsal": args.rehearse,
                    "call_us": _quartiles(calls),
                    "wait_us": _quartiles(waits),
                    "fetch_us": _quartiles(fetches),
                    "readback_us": _quartiles(
                        [w + f for w, f in zip(waits, fetches)])}
            if steps is not None:
                total = statistics.median(
                    [c + w + f for c, w, f in zip(calls, waits, fetches)])
                line.update(steps=steps, round_trip_us_per_step=total / steps)
                picks[steps] = int(state["out"][0, rows + 1])
            print(json.dumps(line), flush=True)
            if name == "resident":
                picks["resident"] = int(np.argmax(state["out"][:-1]))
                scores, tol = hlem_scores_tol_jax(free, mask, spot,
                                                  np.float32(-0.5))
                want = np.append(np.asarray(scores), np.float32(tol))
                same = np.array_equal(state["out"].view(np.uint32),
                                      want.view(np.uint32))
                mirror = np.array_equal(
                    np.asarray(state["mirror"].free),
                    free.astype(np.float32)) and np.array_equal(
                    np.asarray(state["mirror"].spot_frac),
                    spot.astype(np.float32))
                print(json.dumps({"rows": rows, "resident_equals_reference":
                                  bool(same), "mirror_equals_storage":
                                  bool(mirror), "device": device}),
                      flush=True)
        if picks:
            # each way's last call saw the same storage: a run's first
            # step (the mask alone) picks as the resident program
            print(json.dumps({"rows": rows, "first_step_picks": {
                str(k): v for k, v in picks.items()}, "device": device}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
