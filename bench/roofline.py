"""Least time of the device scorer's work, and the table of chip peaks.

``hlem_scores_tol_jax`` scores every row of the pool's storage for one VM:
it reads the free capacity and the spot fraction, ``rows x 4`` float32
each, and the ``rows`` bool mask, and writes ``rows`` float32 scores and
one float32 tolerance.  The bytes below are that least traffic; the
operations count the elementwise arithmetic of Eqs. 3-11 and of the
tolerance, per (row, dimension): mask selects and min/max (4), standardize
(3), proportions (2), p log p (3 and 1 logarithm), weighted sums for the
score and spot load (4), the adjustment (2 per row), and the rounding
term's max (1).  Its least time is bound by the bytes.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
D = 4
FLOPS_PER_ELEMENT = 18


def scorer_bytes(rows: int) -> int:
    return rows * D * 4 * 2 + rows * 1 + rows * 4 + 4


def scorer_flops(rows: int) -> int:
    return rows * D * FLOPS_PER_ELEMENT + rows * 2


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them "
                       f"to {PEAKS.name} with their source")
    return table[device_kind]


def scorer_least_s(rows: int, device_kind: str) -> float:
    pk = peaks(device_kind)
    return max(scorer_bytes(rows) / pk["hbm_bytes_per_s"],
               scorer_flops(rows) / pk["flops_per_s"])
