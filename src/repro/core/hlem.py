"""HLEM-VMP host scoring (paper §VI, Eqs. 1–11).

Three implementations of the same math:

* ``hlem_scores_np``  — pure-numpy oracle (readable, used as test reference),
* ``hlem_scores_jax`` — vectorized/jitted JAX in float32 (the device path,
  run by ``hlem_scores_tol_jax_resident`` on a mirror of the pool kept on
  the device; :func:`certified_pick` keeps its decisions those of the
  float64 oracle),
* ``repro.kernels.hlem_score`` — Pallas TPU kernel (tiled over hosts), checked
  against the numpy oracle in interpret mode and, on a TPU, by
  ``chip_smoke.py``.

All take a *masked* formulation: every host is scored, infeasible hosts carry
``mask=False`` and receive ``-inf`` so downstream argmax ignores them.  This is
the jit-friendly equivalent of the paper's explicit candidate-list construction.

Phases (paper §VI-A):
  1. host filtering   — feasibility + RsDiff threshold (Eqs. 1–2), done by the
                        policy layer (see allocation.py), expressed as ``mask``;
  2. load evaluation  — min-max standardize free capacity per dimension (Eq. 3),
                        proportions (Eq. 4), entropy e_d (Eqs. 5–6), variation
                        g_d = 1 - e_d (Eq. 7), weights w_d (Eq. 8);
  3. selection        — host score HS_i = sum_d w_d * C~_i^d (Eq. 9), argmax.

Adjusted variant (§VI-C): spot load SL_i = sum_d w_d * spot_used/total (Eq. 10)
scales the score AHS_i = HS_i * (1 + alpha * SL_i) (Eq. 11).  A *negative*
``alpha`` penalizes spot-heavy hosts, which is the behavior the paper's text
describes ("distribute spot instances more evenly"); the magnitude is tunable.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-12


# ---------------------------------------------------------------------------
# numpy oracle
# ---------------------------------------------------------------------------
def hlem_weights_np(free: np.ndarray, mask: np.ndarray):
    """Entropy-derived resource weights over the masked candidate set.

    Returns (standardized capacity C~ (n,D), weights w (D,)).
    """
    free = np.asarray(free, dtype=np.float64)
    mask = np.asarray(mask, dtype=bool)
    n_cand = int(mask.sum())
    d = free.shape[1]
    if n_cand == 0:
        return np.zeros_like(free), np.full(d, 1.0 / d)

    sel = free[mask]  # (m, D)
    lo, hi = sel.min(axis=0), sel.max(axis=0)
    span = hi - lo
    # Eq. 3 — min-max standardization; degenerate dimension -> all equal (1.0)
    c_std = np.where(span > _EPS, (sel - lo) / np.where(span > _EPS, span, 1.0), 1.0)
    # Eq. 4 — proportions over candidates
    col = c_std.sum(axis=0)
    p = np.where(col > _EPS, c_std / np.where(col > _EPS, col, 1.0), 1.0 / n_cand)
    # Eqs. 5–6 — entropy with k = 1/ln(n); n == 1 degenerates to zero entropy
    if n_cand > 1:
        k = 1.0 / np.log(n_cand)
        plogp = np.where(p > _EPS, p * np.log(np.maximum(p, _EPS)), 0.0)
        e = -k * plogp.sum(axis=0)
    else:
        e = np.zeros(d)
    # Eqs. 7–8 — variation factors and weights
    g = 1.0 - e
    gsum = g.sum()
    w = g / gsum if gsum > _EPS else np.full(d, 1.0 / d)

    c_full = np.zeros_like(free)
    c_full[mask] = c_std
    return c_full, w


def hlem_scores_np(
    free: np.ndarray,
    mask: np.ndarray,
    spot_frac: np.ndarray | None = None,
    alpha: float = 0.0,
) -> np.ndarray:
    """Full HLEM-VMP host scores; -inf where mask is False.

    ``spot_frac`` is spot_used/total per (host, dim); with ``alpha != 0`` this
    computes the adjusted score AHS (Eq. 11).
    """
    mask = np.asarray(mask, dtype=bool)
    c_std, w = hlem_weights_np(free, mask)
    hs = c_std @ w  # Eq. 9
    if spot_frac is not None and alpha != 0.0:
        sl = np.asarray(spot_frac, dtype=np.float64) @ w  # Eq. 10
        hs = hs * (1.0 + alpha * sl)  # Eq. 11
    return np.where(mask, hs, -np.inf)


def hlem_select_np(free, mask, spot_frac=None, alpha=0.0) -> int:
    """argmax host id, or -1 if no candidate."""
    if not np.any(mask):
        return -1
    return int(np.argmax(hlem_scores_np(free, mask, spot_frac, alpha)))


def hlem_pick_np(
    free: np.ndarray,
    mask: np.ndarray,
    spot_frac: np.ndarray,
    alpha: float = 0.0,
) -> int:
    """Fused single-VM selection: ``argmax(hlem_scores_np(...))`` without
    materializing full-fleet score arrays.

    Decision-identical to scoring + argmax: the standardization/entropy math
    (Eqs. 3-9) runs on the *compressed* candidate rows — exactly the arrays
    ``hlem_scores_np`` reduces over — and the compressed argmax maps back
    through ``flatnonzero`` (order-preserving, so ties break to the same
    host).  This is the allocation hot path's scorer; ``hlem_scores_np``
    remains the readable oracle."""
    idx = np.flatnonzero(mask)
    return hlem_pick_candidates_np(free, idx, spot_frac, alpha)


class _PickWorkspace:
    """Preallocated scratch for the fused pick — the hot path allocates
    nothing per call (arrays grow monotonically with the fleet)."""

    def __init__(self):
        self.cap = 0

    def ensure(self, m: int, d: int) -> None:
        if m <= self.cap:
            return
        cap = max(m, max(self.cap * 2, 64))
        self.sel = np.empty((cap, d))
        self.tmp = np.empty((cap, d))
        self.tmp2 = np.empty((cap, d))
        self.boolbuf = np.empty((cap, d), dtype=bool)
        self.hs = np.empty(cap)
        self.cap = cap


_WS = _PickWorkspace()


def hlem_pick_candidates_np(
    free: np.ndarray,
    idx: np.ndarray,
    spot_frac: np.ndarray,
    alpha: float = 0.0,
    m_cutover: int | None = None,   # override PICK_NP_M_CUTOVER (tests)
) -> int:
    """:func:`hlem_pick_np` over an explicit candidate-id array (the policy
    layer already holds ``flatnonzero`` of its masks).

    Runs the oracle's exact operation sequence on compressed candidate rows
    with preallocated workspace buffers — values (and therefore the argmax
    decision, ties included) match scoring + argmax bit for bit."""
    m = idx.size
    if m == 0:
        return -1
    if m == 1:
        return int(idx[0])  # degenerate candidate set: any weighting agrees
    weights = _candidate_weights_np(free, idx, alpha == 0.0, m_cutover)
    if weights is None:
        # all dims degenerate: HS identical for every candidate and the
        # adjustment is off, so the argmax tie-breaks to the first
        return int(idx[0])
    c_std, w = weights
    hs = np.dot(c_std, w, out=_WS.hs[:m])
    if alpha != 0.0:
        sl = np.take(np.asarray(spot_frac, dtype=np.float64), idx, axis=0) @ w
        hs = hs * (1.0 + alpha * sl)
    return int(idx[np.argmax(hs)])


def _candidate_weights_np(free, idx, flat_exit: bool,
                          m_cutover: int | None = None):
    """:func:`hlem_weights_np` over the m >= 2 candidates ``idx``, bit for
    bit: (standardized candidate rows C~ (m, D), a workspace view valid
    until the next pick; weights w (D,)).  None where ``flat_exit`` is set
    and every dimension is degenerate.

    numpy reduces an (m, D) array over axis 0 as m calls of a D-wide loop.
    Above ``PICK_NP_M_CUTOVER`` candidates the column statistics avoid
    that: min and max (exact in any order) run along a copy laid out a row
    per dimension, and each column sum is an ``einsum`` over the rows,
    which adds candidate after candidate into the D sums, as the oracle's
    axis-0 sum does (a sum along the copy's rows would add pairwise), in
    one pass."""
    m = idx.size
    free = np.asarray(free, dtype=np.float64)
    d = free.shape[1]
    _WS.ensure(m, d)
    by_dim = m > (PICK_NP_M_CUTOVER if m_cutover is None else m_cutover)
    sel = np.take(free, idx, axis=0, out=_WS.sel[:m])
    if by_dim:
        # the leading m * D elements of a buffer c_std overwrites below
        dims = _WS.tmp.reshape(-1)[: d * m].reshape(d, m)
        np.copyto(dims, sel.T)
        lo, hi = dims.min(axis=1), dims.max(axis=1)
    else:
        lo, hi = sel.min(axis=0), sel.max(axis=0)
    span = hi - lo
    nondegen = span > _EPS
    c_std = _WS.tmp[:m]
    np.subtract(sel, lo, out=c_std)
    if nondegen.all():
        np.divide(c_std, span, out=c_std)
    else:
        if flat_exit and not nondegen.any():
            return None
        np.divide(c_std, np.where(nondegen, span, 1.0), out=c_std)
        np.copyto(c_std, 1.0, where=~nondegen)
    # each column sums to >= 1 (its max candidate standardizes to 1.0, or the
    # degenerate all-ones case sums to m), so the col > eps guard of the
    # oracle never fires and plain division is value-identical
    col = np.einsum("ij->j", c_std) if by_dim else c_std.sum(axis=0)
    # p reuses the gather buffer (sel is not read past this point); the
    # entropy chain below computes where(p > eps, p*log(max(p, eps)), 0)
    # elementwise-identically with zero allocation
    p = np.divide(c_std, col, out=_WS.sel[:m])
    small = np.less_equal(p, _EPS, out=_WS.boolbuf[:m])
    plogp = np.maximum(p, _EPS, out=_WS.tmp2[:m])
    np.log(plogp, out=plogp)
    np.multiply(p, plogp, out=plogp)
    np.copyto(plogp, 0.0, where=small)
    k = 1.0 / math.log(m)
    e = -k * (np.einsum("ij->j", plogp) if by_dim else plogp.sum(axis=0))
    g = 1.0 - e
    gsum = g.sum()
    w = g / gsum if gsum > _EPS else np.full(d, 1.0 / d)
    return c_std, w


#: candidate count above which :func:`hlem_pick_candidates_np` takes its
#: column statistics without numpy's axis-0 reductions (the same values bit
#: for bit).  The copy and the einsum calls cost more than they save on
#: small sets: on a TPU v5e host the two ways cost the same between 64 and
#: 128 candidates, and the new one 0.44 of the old at 12,600
#: (``tools/host_pick_bench.py`` times both on the host it runs on).
PICK_NP_M_CUTOVER = 96

#: fleet-size crossover for the batched numpy scorer: above this many hosts
#: the (B, n, D) broadcast core loses to a compressed per-row pass (its
#: masked intermediates thrash cache, while the per-row path reduces over the
#: compressed candidate set) — measured ~1.4-1.9x per-row advantage at
#: n >= 1000 for B in 4..32, batch advantage up to 2.2x at n <= 300.
BATCH_NP_N_CUTOVER = 512


def hlem_scores_batch_np(
    free: np.ndarray,          # (n, D) shared host state
    masks: np.ndarray,         # (B, n) per-VM candidate masks
    spot_frac: np.ndarray,     # (n, D)
    alphas: np.ndarray | float = 0.0,   # (B,) or scalar per-VM adjustment
    n_cutover: int | None = None,       # override BATCH_NP_N_CUTOVER (tests)
) -> np.ndarray:               # (B, n) scores, -inf outside each row's mask
    """Score B pending VMs against the same host state in one pass.

    Row b equals ``hlem_scores_np(free, masks[b], spot_frac, alphas[b])`` up
    to summation order (each row's entropy weights are derived from its own
    candidate set, Eqs. 3-9; Eq. 11 applied with the row's alpha).  This is
    the oracle for the batched Pallas kernel and the engine of the batched
    resubmission path.

    Large fleets (``n > BATCH_NP_N_CUTOVER``) route through the compressed
    per-row oracle instead of the broadcast core (same masked semantics, ulp-
    level summation-order differences — exactly the tolerance the broadcast
    core already carries vs the oracle).
    """
    free = np.asarray(free, dtype=np.float64)
    masks = np.asarray(masks, dtype=bool)
    spot_frac = np.asarray(spot_frac, dtype=np.float64)
    b, n = masks.shape
    d = free.shape[1]
    alphas = np.broadcast_to(np.asarray(alphas, dtype=np.float64), (b,))
    cut = BATCH_NP_N_CUTOVER if n_cutover is None else n_cutover
    if n > cut:
        out = np.empty((b, n))
        for i in range(b):
            out[i] = hlem_scores_np(free, masks[i], spot_frac,
                                    float(alphas[i]))
        return out
    maskf = masks[..., None].astype(np.float64)        # (B, n, 1)
    m = masks.sum(axis=1).astype(np.float64)           # (B,) candidate counts

    # Eq. 3 — per-row min-max standardization over each candidate set
    lo = np.where(masks[..., None], free[None], np.inf).min(axis=1)   # (B, D)
    hi = np.where(masks[..., None], free[None], -np.inf).max(axis=1)
    span = hi - lo
    degen = span <= _EPS
    c = np.where(degen[:, None, :], 1.0,
                 (free[None] - lo[:, None]) / np.where(degen, 1.0, span)[:, None])
    c = c * maskf
    # Eq. 4 — proportions over each row's candidates
    col = c.sum(axis=1)                                # (B, D)
    p = np.where(col[:, None] > _EPS,
                 c / np.where(col > _EPS, col, 1.0)[:, None],
                 maskf / np.maximum(m, 1.0)[:, None, None])
    p = p * maskf
    # Eqs. 5-6 — entropy with k = 1/ln(m); m <= 1 degenerates to zero entropy
    k = np.where(m > 1.0, 1.0 / np.log(np.maximum(m, 2.0)), 0.0)
    plogp = np.where(p > _EPS, p * np.log(np.maximum(p, _EPS)), 0.0)
    e = -k[:, None] * plogp.sum(axis=1)                # (B, D)
    # Eqs. 7-8 — variation factors and weights
    g = 1.0 - e
    gsum = g.sum(axis=1)
    w = np.where(gsum[:, None] > _EPS,
                 g / np.where(gsum > _EPS, gsum, 1.0)[:, None], 1.0 / d)
    # Eqs. 9-11
    hs = np.einsum("bnd,bd->bn", c, w)
    sl = np.einsum("nd,bd->bn", spot_frac, w)
    hs = hs * (1.0 + alphas[:, None] * sl)
    return np.where(masks, hs, -np.inf)


# ---------------------------------------------------------------------------
# JAX (jitted, mask-based — fixed shapes, no data-dependent control flow)
# ---------------------------------------------------------------------------
#: scale of the float32 error of a score *difference* (scores are O(1)).
#: :func:`hlem_scores_tol_jax` multiplies it by ``1 + 1/sum(g)``, the
#: amplification of entropies near 1 (Eq. 7), and adds the rounding of the
#: float32 inputs over each dimension's span.  Measured against it, with a
#: wide margin: errors up to 1.4e-5 (at sum(g) ~ 0.06) and error x sum(g)
#: up to 8.5e-7 over sampled trace picks on a CPU.
DEVICE_TIE_TOL = 1e-5


def _scores_and_tol(free, mask, spot_frac, alpha):
    free = free.astype(jnp.float32)
    maskf = mask.astype(jnp.float32)[:, None]          # (n,1)
    m = jnp.sum(maskf)                                 # candidate count
    big = jnp.float32(3.4e38)

    masked = jnp.where(mask[:, None], free, jnp.inf)
    lo = jnp.min(masked, axis=0)
    masked_hi = jnp.where(mask[:, None], free, -jnp.inf)
    hi = jnp.max(masked_hi, axis=0)
    span = hi - lo
    degen = span <= _EPS
    c_std = jnp.where(degen[None, :], 1.0, (free - lo[None, :]) / jnp.where(degen, 1.0, span)[None, :])
    c_std = c_std * maskf

    col = jnp.sum(c_std, axis=0)
    p = jnp.where(col[None, :] > _EPS, c_std / jnp.where(col > _EPS, col, 1.0)[None, :],
                  maskf / jnp.maximum(m, 1.0))
    p = p * maskf
    k = jnp.where(m > 1.0, 1.0 / jnp.log(jnp.maximum(m, 2.0)), 0.0)
    plogp = jnp.where(p > _EPS, p * jnp.log(jnp.maximum(p, _EPS)), 0.0)
    e = -k * jnp.sum(plogp, axis=0)
    g = 1.0 - e
    gsum = jnp.sum(g)
    d = free.shape[1]
    w = jnp.where(gsum > _EPS, g / jnp.where(gsum > _EPS, gsum, 1.0), 1.0 / d)

    # elementwise multiply + reduce, not ``@``: a TPU matmul of float32
    # operands rounds them to bfloat16 at default precision
    hs = jnp.sum(c_std * w, axis=1)
    sl = jnp.sum(spot_frac.astype(jnp.float32) * w, axis=1)
    hs = hs * (1.0 + alpha * sl)

    # float32 rounds an input by up to 2^-24 of its magnitude, and the
    # standardization divides that by the dimension's span (Eq. 3)
    mag = jnp.max(jnp.where(mask[:, None], jnp.abs(free), 0.0), axis=0)
    rounding = jnp.sum(jnp.where(degen, 0.0,
                                 w * mag / jnp.where(degen, 1.0, span)))
    tol = (DEVICE_TIE_TOL * (1.0 + 1.0 / jnp.maximum(gsum, 1e-30))
           + 2.0 ** -20 * rounding)
    return jnp.where(mask, hs, -big), tol


@jax.jit
def hlem_scores_jax(
    free: jax.Array,           # (n, D) float32/float64
    mask: jax.Array,           # (n,) bool
    spot_frac: jax.Array,      # (n, D)
    alpha: jax.Array,          # scalar
) -> jax.Array:
    """Identical math to ``hlem_scores_np``, jit-compiled."""
    return _scores_and_tol(free, mask, spot_frac, alpha)[0]


@jax.jit
def hlem_scores_tol_jax(free, mask, spot_frac, alpha):
    """(scores, tol): :func:`hlem_scores_jax` and a bound on the float32
    error of any difference of two of its scores."""
    return _scores_and_tol(free, mask, spot_frac, alpha)


def dirty_capacity(rows: int) -> int:
    """Changed rows one packed pick input carries for a storage of ``rows``:
    a sixty-fourth of the rows (at least 16), whose 36 bytes each come to
    under a fiftieth of a whole upload; more changed rows upload whole."""
    return max(16, rows // 64)


def _pick_layout(rows: int, d: int):
    """(mask bytes padded to whole words, dirty-row capacity, total bytes)
    of the packed pick input for a storage of ``rows`` x ``d``."""
    head = -(-rows // 4) * 4
    k_cap = dirty_capacity(rows)
    return head, k_cap, head + 4 * (1 + k_cap * (1 + 2 * d))


def pack_pick(mask: np.ndarray, alpha: float, ids, free: np.ndarray,
              spot_frac: np.ndarray) -> np.ndarray:
    """The one host array a resident pick sends, bytes of a length fixed by
    the storage's shape: the candidate ``mask`` (a byte a row, padded to
    the storage and to whole words), then little-endian words: ``alpha``
    (float32), the ids of the rows rewritten since the last pick (unused
    slots hold the row count) and their (free, spot_frac) rows in float32,
    rounded by numpy as a host argument of the jitted scorer is."""
    rows, d = free.shape
    head, k_cap, size = _pick_layout(rows, d)
    buf = np.zeros(size, dtype=np.uint8)
    buf[: mask.size] = mask
    words = buf[head:].view("<u4")
    words[0] = np.float32(alpha).view(np.uint32)
    k = len(ids)
    words[1: 1 + k] = ids
    words[1 + k: 1 + k_cap] = rows
    vals = buf[head + 4 * (1 + k_cap):].view("<f4").reshape(k_cap, 2 * d)
    vals[:k, :d] = free[ids]
    vals[:k, d:] = spot_frac[ids]
    return buf


@functools.partial(jax.jit, donate_argnums=(0, 1))
def hlem_scores_tol_jax_resident(free, spot_frac, packed):
    """:func:`hlem_scores_tol_jax` on a float32 mirror of the storage kept
    on the device: writes the changed rows that ``packed``
    (:func:`pack_pick`) carries into ``free`` and ``spot_frac`` in place,
    scores, and returns the new mirror and ``[scores..., tol]``."""
    rows, d = free.shape
    head, k_cap, _ = _pick_layout(rows, d)
    # a byte a row and no arithmetic on it: unpacking bits here changes
    # how XLA fuses the scorer, and its float32 scores then differ by ulps
    # from :func:`hlem_scores_tol_jax`'s
    mask = packed[:rows] != 0
    words = jax.lax.bitcast_convert_type(packed[head:].reshape(-1, 4),
                                         jnp.uint32)
    alpha = jax.lax.bitcast_convert_type(words[0], jnp.float32)
    ids = words[1: 1 + k_cap].astype(jnp.int32)
    vals = jax.lax.bitcast_convert_type(words[1 + k_cap:], jnp.float32)
    vals = vals.reshape(k_cap, 2 * d)

    # one row at a time, as many as were sent: a scatter would have the
    # TPU lay the mirror out row-major (4 -> 128 lanes) and copy it twice
    def write_row(i, mirror):
        f, s = mirror
        f = jax.lax.dynamic_update_slice(f, vals[i, None, :d], (ids[i], 0))
        s = jax.lax.dynamic_update_slice(s, vals[i, None, d:], (ids[i], 0))
        return f, s

    free, spot_frac = jax.lax.fori_loop(0, jnp.sum(ids < rows), write_row,
                                        (free, spot_frac))
    scores, tol = _scores_and_tol(free, mask, spot_frac, alpha)
    # tol written into the slot after the scores: a concatenate lets XLA
    # fuse tol's arithmetic otherwise, and round it by an ulp
    out = jax.lax.dynamic_update_slice(jnp.pad(scores, (0, 1)), tol[None],
                                       (rows,))
    return free, spot_frac, out


class ResidentScorer:
    """A float32 mirror of a pool's (free, spot_frac) storage on the device,
    for :func:`hlem_scores_tol_jax_resident`: each pick sends one packed
    array and the rows changed since the previous pick, and reads one
    array back.  The caller tracks which rows changed; a new pool, grown
    storage or more changed rows than :func:`dirty_capacity` take
    :meth:`upload` instead."""

    def __init__(self):
        self.free = self.spot_frac = None

    @property
    def rows(self) -> int:
        return -1 if self.free is None else self.free.shape[0]

    def upload(self, free: np.ndarray, spot_frac: np.ndarray) -> int:
        """Replace the mirror by the whole storage; returns the bytes sent."""
        host = (free.astype(np.float32), spot_frac.astype(np.float32))
        self.free, self.spot_frac = jax.device_put(host)
        return host[0].nbytes + host[1].nbytes

    def scores_tol(self, packed: np.ndarray) -> jax.Array:
        """Apply ``packed``'s rows and score: ``[scores..., tol]``, on the
        device, its copy to the host already requested (it starts when the
        program ends, not when the host asks)."""
        self.free, self.spot_frac, out = hlem_scores_tol_jax_resident(
            self.free, self.spot_frac, packed)
        out.copy_to_host_async()
        return out


def device_arg_bytes(*args) -> int:
    """Bytes a jitted call copies to the device for ``args``: each host
    argument (array or scalar) in the dtype JAX gives it under the current
    x64 setting (float64 host arrays arrive as float32 while x64 is off).
    An argument already on the device (a ``jax.Array``) crosses nothing
    and counts 0."""
    return sum(int(np.size(a))
               * jax.dtypes.canonicalize_dtype(np.result_type(a)).itemsize
               for a in args if not isinstance(a, jax.Array))


def certified_pick(scores: np.ndarray, tol: float, free: np.ndarray,
                   spot_frac: np.ndarray) -> int | None:
    """The float64 oracle's argmax read off float32 ``scores``, or None
    when float32 cannot decide it.

    Every host within ``tol`` (:func:`hlem_scores_tol_jax`) of the float32
    maximum could be the oracle's pick.  When all of them carry the same
    (free, spot_frac) row in float64, they score identically in both
    precisions, and the oracle picks the first of them; otherwise the
    caller must score exactly."""
    near = np.flatnonzero(scores >= scores.max() - tol)
    first = near[0]
    if near.size == 1 or ((free[near] == free[first]).all()
                          and (spot_frac[near] == spot_frac[first]).all()):
        return int(first)
    return None


@jax.jit
def hlem_select_jax(free, mask, spot_frac, alpha) -> jax.Array:
    scores = hlem_scores_jax(free, mask, spot_frac, alpha)
    idx = jnp.argmax(scores)
    return jnp.where(jnp.any(mask), idx, -1)


# Batched variants: score B pending VM demands against the same host state in
# one call (used when flushing the resubmission queue) — a beyond-CloudSim
# vectorization enabled by the masked formulation.
@jax.jit
def hlem_scores_batch_jax(
    free: jax.Array,        # (n, D) shared host state
    masks: jax.Array,       # (B, n) per-VM feasibility masks
    spot_frac: jax.Array,   # (n, D)
    alphas: jax.Array,      # (B,) per-VM adjustment
) -> jax.Array:             # (B, n) scores, -big outside each row's mask
    fn = jax.vmap(lambda m, a: hlem_scores_jax(free, m, spot_frac, a))
    return fn(masks, alphas)


@jax.jit
def hlem_select_batch_jax(
    free: jax.Array,        # (n, D)
    masks: jax.Array,       # (B, n) per-VM feasibility masks
    spot_frac: jax.Array,   # (n, D)
    alpha: jax.Array,
) -> jax.Array:             # (B,) selected host per VM (ignoring cross-VM capacity)
    fn = jax.vmap(lambda m: hlem_select_jax(free, m, spot_frac, alpha))
    return fn(masks)


# ---------------------------------------------------------------------------
# Filtering math shared by the policy layer
# ---------------------------------------------------------------------------
def rsdiff_np(
    demand_cpu: float,
    used_cpu: np.ndarray,
    total_cpu: np.ndarray,
    rc: float = 0.95,
) -> np.ndarray:
    """Eq. 1 — RsDiff = R_j(t) - U_i(t) * Rc, in CPU-fraction units.

    R_j is the VM's CPU request relative to the host's CPU capacity; U_i is the
    host's current CPU utilization. Hosts already loaded with similar workloads
    (high utilization relative to the request) are filtered out (Eq. 2).
    """
    tot = np.maximum(total_cpu, _EPS)
    r_j = demand_cpu / tot
    u_i = used_cpu / tot
    return r_j - u_i * rc
