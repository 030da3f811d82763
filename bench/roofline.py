"""Least time of the device scorer's work, and the table of chip peaks.

The program a device pick runs, ``hlem_scores_tol_jax_resident``, scores
every row of the pool's storage for one VM.  Its least traffic, counted
below: it reads the float32 mirror of the free capacity and the spot
fraction on the device (``rows x 4`` each) and the packed input sent from
the host (a mask byte a row padded to whole words, then alpha, the ids of
up to K = max(16, rows / 64) rewritten rows and their two float32 rows of
4: 25,604 B at 16,384 rows, 836 B at 256), and writes ``rows`` float32
scores and one float32 tolerance.  Writing the rewritten rows into the
mirror (about one row a pick) is not counted, and neither is the time of
the loop over the rows sent and of the unpacking: that is device time the
count does not cover, so it lowers the share.  The operations count the
elementwise arithmetic of Eqs. 3-11 and of the tolerance, per (row,
dimension): mask selects and min/max (4), standardize (3), proportions
(2), p log p (3 and 1 logarithm), weighted sums for the score and spot
load (4), the adjustment (2 per row), and the rounding term's max (1).
Its least time is bound by the bytes.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
D = 4
FLOPS_PER_ELEMENT = 18


def packed_input_bytes(rows: int) -> int:
    """The host's packed input to one pick at a storage of ``rows``."""
    k = max(16, rows // 64)
    return -(-rows // 4) * 4 + 4 * (1 + k * (1 + 2 * D))


def scorer_bytes(rows: int) -> int:
    return rows * D * 4 * 2 + packed_input_bytes(rows) + rows * 4 + 4


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
