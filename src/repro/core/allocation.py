"""VM allocation policies (paper §II-D, §VI).

Each policy implements ``find_host(vm, pool, now, allow_spot_clearing)`` and
returns ``(host_id, needs_clearing)``; ``host_id == -1`` means no placement.
``needs_clearing`` signals that the chosen host only becomes feasible after
interrupting (some of) its spot VMs — the simulator performs the actual victim
selection and interruption (DynamicAllocation.spotAllocation in the paper).

Spot-clearing feasibility counts only *interruptible* spot VMs: those past
their minimum running time (§IV-B "minimum runtime must be enforced") — the
pool maintains that sum incrementally (see ``hosts.HostPool``), so both masks
are single vectorized comparisons against cached arrays.

Batched paths (clearing is never considered: queued VMs do not trigger new
preemption cascades, see simulator._flush_pending):

* ``find_first_direct(vms, pool)`` is the engine of the simulator's batched
  flush — one feasibility matrix decides which VM places, then a single-row
  scoring pass (bit-identical to the per-VM path) picks its host;
* ``find_hosts_batch(vms, pool, now)`` decides ALL rows in one shot (one
  feasibility matrix + one batched HLEM scoring pass) for offline/accelerator
  use; rows match per-VM ``find_host`` up to float summation order (a
  near-tie argmax can differ at the ulp level).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

from .hlem import (
    ResidentScorer,
    certified_pick,
    device_arg_bytes,
    dirty_capacity,
    hlem_pick_candidates_np,
    hlem_pick_np,
    hlem_scores_batch_np,
    pack_pick,
)
from .hosts import HostPool
from ..obs.tracer import NULL_TRACER
from .registry import Registry
from .types import Vm

_EPS = 1e-9

#: string-keyed plugin registry for allocation policies — the scenario API's
#: extension point.  Register custom policies with
#: ``@register_policy("my-policy")``; ``make_policy`` and ``PolicySpec``
#: resolve against it.
POLICY_REGISTRY = Registry("allocation policy")
register_policy = POLICY_REGISTRY.register


def direct_mask(vm: Vm, pool: HostPool) -> np.ndarray:
    """Hosts that fit the demand right now (fresh array; hot paths use
    ``pool.direct_mask_into`` which is scratch-backed)."""
    return pool.direct_mask_into(vm.demand, vm.bid, vm.pool).copy()


def clearing_mask(vm: Vm, pool: HostPool, now: float) -> np.ndarray:
    """Hosts that would fit the demand after deallocating their interruptible
    spot VMs (§VI-A: "checks the potential capacity of hosts if active spot
    instances were to be deallocated").

    One vectorized comparison against the pool's incrementally maintained
    reclaimable-capacity cache; min-running-time expiries up to ``now`` are
    folded in first.
    """
    pool.refresh_reclaim(now)
    return pool.clearing_mask_into(vm.demand, vm.bid, vm.pool).copy()


def feasibility_masks(vm: Vm, pool: HostPool, now: float):
    """(direct_mask, clearing_mask) — kept for tests; prefer the lazy pair."""
    return direct_mask(vm, pool), clearing_mask(vm, pool, now)


class AllocationPolicy:
    name = "abstract"

    #: telemetry hook (``repro.obs``); the simulator hands in its live
    #: tracer.  In the batched flush (:meth:`find_first_direct`) the
    #: ``allocation:flush/feasibility`` span times the feasibility matrix,
    #: and the counters ``flush/batch_calls`` and ``flush/batch_rows``
    #: count its calls and the queued VMs B it holds (their ratio is the
    #: mean B; a pass with one candidate goes through :meth:`find_direct`
    #: and is not counted).  :meth:`HlemVmp._score_pick` adds the device
    #: pick's spans and the counters ``pick/h2d_bytes``,
    #: ``pick/dirty_rows``, ``pick/mirror_uploads`` and
    #: ``pick/host_exact_rows`` (the candidates of the float64 picks after
    #: fallbacks: over ``device_fallbacks``, their mean count).
    tracer = NULL_TRACER

    def _pick(self, mask: np.ndarray, vm: Vm, pool: HostPool) -> int:
        raise NotImplementedError

    def find_host(
        self, vm: Vm, pool: HostPool, now: float, allow_spot_clearing: bool
    ) -> Tuple[int, bool]:
        hid = self._pick(pool.direct_mask_into(vm.demand, vm.bid, vm.pool),
                         vm, pool)
        if hid >= 0:
            return hid, False
        if allow_spot_clearing and not vm.is_spot:
            pool.refresh_reclaim(now)
            hid = self._pick(
                pool.clearing_mask_into(vm.demand, vm.bid, vm.pool), vm, pool)
            if hid >= 0:
                return hid, True
        return -1, False

    def _pick_direct(self, mask: np.ndarray, vm: Vm, pool: HostPool) -> int:
        """Select from a direct-feasibility mask; >= 0 whenever mask is
        non-empty.  Shared by ``find_host`` and the batched flush."""
        return self._pick(mask, vm, pool)

    def find_direct(self, vm: Vm, pool: HostPool) -> int:
        """Direct placement only (no spot clearing): chosen host or -1."""
        mask = pool.direct_mask_into(vm.demand, vm.bid, vm.pool)
        if not mask.any():
            return -1
        return self._pick_direct(mask, vm, pool)

    # -- batched path --------------------------------------------------------
    def find_hosts_batch(
        self, vms: Sequence[Vm], pool: HostPool, now: float
    ) -> np.ndarray:
        """(B,) chosen host per VM (-1 = none), direct placements only.

        Row b matches ``find_host(vms[b], ...)`` against the same pool state
        with spot clearing ignored (for HLEM, up to float summation order in
        the batched scorer).  The result is only valid until the pool mutates
        (committing one row invalidates the rest)."""
        demands = np.stack([vm.demand for vm in vms])
        bids = np.array([vm.bid for vm in vms])
        pids = np.array([vm.pool for vm in vms], dtype=np.int64)
        feas = pool.direct_mask_batch(demands, bids, pids)
        return self._pick_batch(feas, vms, pool)

    def find_first_direct(
        self, vms: Sequence[Vm], pool: HostPool
    ) -> Tuple[int, int]:
        """(index, host) of the first VM in ``vms`` that fits somewhere right
        now, or (B, -1) if none does.

        One vectorized feasibility matrix decides *which* VM places (a VM
        places iff its feasibility row is non-empty); scoring then runs for
        that single row only.  This is the engine of the batched flush: the
        greedy commit loop re-decides only the suffix after each placement,
        so scoring work is one pass per placement instead of per queued VM."""
        nvm = len(vms)
        tr = self.tracer
        tr.counters.inc("flush/batch_calls")
        tr.counters.inc("flush/batch_rows", nvm)
        demands = np.empty((nvm, vms[0].demand.shape[0]))
        bids = np.empty(nvm)
        pids = np.empty(nvm, dtype=np.int64)
        for b, vm in enumerate(vms):
            demands[b] = vm.demand
            bids[b] = vm.bid
            pids[b] = vm.pool
        with tr.span("allocation", "flush/feasibility"):
            feas = pool.direct_mask_batch(demands, bids, pids)
        any_row = feas.any(axis=1)
        for b in np.flatnonzero(any_row):
            return int(b), self._pick_direct(feas[b], vms[b], pool)
        return nvm, -1

    def _pick_batch(self, feas: np.ndarray, vms: Sequence[Vm],
                    pool: HostPool) -> np.ndarray:
        # generic fallback: per-row _pick on the shared feasibility matrix
        return np.array([self._pick(feas[b], vms[b], pool)
                         for b in range(feas.shape[0])], dtype=np.int64)


@register_policy("first-fit")
class FirstFit(AllocationPolicy):
    """CloudSim Plus baseline: first host (insertion order) that fits."""

    name = "first-fit"

    def _pick(self, mask, vm, pool):
        idx = np.flatnonzero(mask)
        return int(idx[0]) if idx.size else -1

    def _pick_batch(self, feas, vms, pool):
        any_row = feas.any(axis=1)
        return np.where(any_row, feas.argmax(axis=1), -1)


@register_policy("best-fit")
class BestFit(AllocationPolicy):
    """Host with the least free CPU that still fits (tightest packing)."""

    name = "best-fit"

    def _pick(self, mask, vm, pool):
        if not mask.any():
            return -1
        free_cpu = np.where(mask, pool.free()[:, 0], np.inf)
        return int(np.argmin(free_cpu))

    def _pick_batch(self, feas, vms, pool):
        any_row = feas.any(axis=1)
        free_cpu = np.where(feas, pool.free()[None, :, 0], np.inf)
        return np.where(any_row, free_cpu.argmin(axis=1), -1)


@register_policy("worst-fit")
class WorstFit(AllocationPolicy):
    """Host with the most free CPU (max headroom)."""

    name = "worst-fit"

    def _pick(self, mask, vm, pool):
        if not mask.any():
            return -1
        free_cpu = np.where(mask, pool.free()[:, 0], -np.inf)
        return int(np.argmax(free_cpu))

    def _pick_batch(self, feas, vms, pool):
        any_row = feas.any(axis=1)
        free_cpu = np.where(feas, pool.free()[None, :, 0], -np.inf)
        return np.where(any_row, free_cpu.argmax(axis=1), -1)


@register_policy("hlem-vmp")
class HlemVmp(AllocationPolicy):
    """HLEM-VMP (paper §VI-A/B).

    Phase 1 filters feasible hosts and applies the RsDiff threshold (Eqs. 1–2);
    if that leaves no candidate, the threshold filter is relaxed (and, for
    on-demand VMs, the spot-clearing candidate list is used — Algorithm 1).
    Phases 2–3 score candidates with entropy weights and pick the max.
    """

    name = "hlem-vmp"
    #: adjusted-variant knobs (unused in the base class)
    alpha = 0.0
    adjust_spot_only = True

    def __init__(self, rc: float = 0.95, threshold: float = 0.0,
                 backend: str = "numpy"):
        self.rc = rc
        self.threshold = threshold
        assert backend in ("numpy", "jax")
        self.backend = backend
        #: device-scored picks, and those a float32 near-tie sent back to
        #: the exact host pick (``backend="jax"`` only)
        self.device_picks = 0
        self.device_fallbacks = 0
        #: the device mirror of ``_mirror_pool``'s scoring storage, current
        #: up to position ``_mirror_pos`` of that pool's row log
        self._mirror = ResidentScorer()
        self._mirror_pool = None
        self._mirror_pos = 0

    # -- phase 1 ------------------------------------------------------------
    def _rsdiff_ok(self, vm: Vm, pool: HostPool) -> np.ndarray:
        tot, util = pool.rsdiff_inputs()
        rs = vm.demand[0] / tot - util * self.rc
        return rs > self.threshold

    # -- phases 2-3 ---------------------------------------------------------
    def _alpha_for(self, vm: Vm) -> float:
        if self.alpha != 0.0 and (vm.is_spot or not self.adjust_spot_only):
            return self.alpha
        return 0.0

    def _score_pick(self, mask: np.ndarray, vm: Vm, pool: HostPool) -> int:
        """The HLEM pick over ``mask``: on the device (``backend="jax"``)
        unless float32 cannot decide it, else on the host in float64.

        The device scores a float32 mirror of the pool's whole storage
        (rows past n masked off; its row count only changes when storage
        doubles, so the scorer compiles a few times per run) that stays
        there between picks: a pick sends one packed array, the mask and
        the rows rewritten since the last pick (:func:`pack_pick`), and
        reads one back.  A new pool, grown storage or more rewritten rows
        than the packed array holds upload the whole mirror first.

        With the tracer enabled, a device pick records three spans in the
        ``allocation`` category: ``pick/call``, the scorer's call (packing,
        any upload, the transfer, the enqueue); ``pick/readback``, the host
        blocked on the device and copying ``[scores..., tol]`` back; and
        ``pick/host-exact``, the float64 pick after a fallback.  Counters:
        ``pick/h2d_bytes``, the bytes the pick copies to the device (the
        packed array and any upload); ``pick/dirty_rows``, rewritten rows
        the packed arrays carried; ``pick/mirror_uploads``, whole
        uploads; ``pick/host_exact_rows``, the candidates of the float64
        picks."""
        if not mask.any():
            return -1
        alpha = self._alpha_for(vm)
        if self.backend == "jax":
            free, spot_frac = pool.storage_views()
            tr = self.tracer
            with tr.span("allocation", "pick/call"):
                mirror = self._mirror
                changed = (pool.rows_since(self._mirror_pos)
                           if pool is self._mirror_pool else None)
                self._mirror_pool, self._mirror_pos = pool, pool.track_rows()
                pool.compact_row_log(self._mirror_pos)
                ids = [] if changed is None else sorted(set(changed))
                sent = uploads = 0
                if (changed is None or mirror.rows != free.shape[0]
                        or len(ids) > dirty_capacity(free.shape[0])):
                    sent = mirror.upload(free, spot_frac)
                    uploads, ids = 1, []
                packed = pack_pick(mask, alpha, ids, free, spot_frac)
                out = mirror.scores_tol(packed)
            if tr.enabled:
                tr.counters.inc("pick/h2d_bytes", sent + device_arg_bytes(
                    mirror.free, mirror.spot_frac, packed))
                tr.counters.inc("pick/dirty_rows", len(ids))
                tr.counters.inc("pick/mirror_uploads", uploads)
            self.device_picks += 1
            with tr.span("allocation", "pick/readback"):
                out = np.asarray(out)
                scores, tol = out[:-1], float(out[-1])
            hid = certified_pick(scores, tol, free, spot_frac)
            if hid is not None:
                return hid
            # a near-tie float32 cannot order: the exact pick decides
            self.device_fallbacks += 1
            with tr.span("allocation", "pick/host-exact"):
                if tr.enabled:
                    tr.counters.inc("pick/host_exact_rows",
                                    int(np.count_nonzero(mask)))
                return hlem_pick_np(pool.free(), mask, pool.spot_frac_view(),
                                    alpha)
        return hlem_pick_np(pool.free(), mask, pool.spot_frac_view(), alpha)

    def _pick_direct(self, mask, vm, pool):
        # primary candidate list: feasible AND RsDiff above threshold;
        # relaxed to plain feasibility if that leaves no candidate
        if self.backend == "jax":
            rs_ok = self._rsdiff_ok(vm, pool)
            hid = self._score_pick(mask & rs_ok, vm, pool)
            if hid >= 0:
                return hid
            return self._score_pick(mask, vm, pool)
        # numpy hot path: compress once, apply Eqs. 1-2 on the candidates only
        return self._pick_direct_idx(np.flatnonzero(mask), vm, pool)

    def _pick_direct_idx(self, idx: np.ndarray, vm, pool) -> int:
        if idx.size == 0:
            return -1
        if idx.size == 1:
            return int(idx[0])  # RsDiff filtering cannot change a 1-set pick
        tot, util = pool.rsdiff_inputs()
        rs_ok = (vm.demand[0] / tot[idx] - util[idx] * self.rc
                 ) > self.threshold
        cand = idx[rs_ok] if rs_ok.any() else idx
        return hlem_pick_candidates_np(
            pool.free(), cand, pool.spot_frac_view(), self._alpha_for(vm))

    def find_host(self, vm, pool, now, allow_spot_clearing):
        if self.backend == "jax":
            direct = pool.direct_mask_into(vm.demand, vm.bid, vm.pool)
            if direct.any():
                return self._pick_direct(direct, vm, pool), False
        else:
            idx = pool.direct_idx_into(vm.demand, vm.bid, vm.pool)
            if idx.size:
                return self._pick_direct_idx(idx, vm, pool), False
        # spot-clearing list (Algorithm 1, lines 8-10) — on-demand only
        if allow_spot_clearing and not vm.is_spot:
            pool.refresh_reclaim(now)
            clearing = pool.clearing_mask_into(vm.demand, vm.bid, vm.pool)
            if clearing.any():
                return self._pick_direct(clearing, vm, pool), True
        return -1, False

    def find_direct(self, vm, pool):
        if self.backend == "jax":
            return super().find_direct(vm, pool)
        return self._pick_direct_idx(
            pool.direct_idx_into(vm.demand, vm.bid, vm.pool), vm, pool)

    def _pick_batch(self, feas, vms, pool):
        B = feas.shape[0]
        out = np.full(B, -1, dtype=np.int64)
        rows = np.flatnonzero(feas.any(axis=1))
        if rows.size == 0:
            return out
        # Eqs. 1-2 vectorized over the batch: rs[b, i] for every (VM, host)
        tot, util = pool.rsdiff_inputs()
        demands_cpu = np.array([vms[b].demand[0] for b in rows])
        rs_ok = (demands_cpu[:, None] / tot[None] - util[None] * self.rc
                 ) > self.threshold
        primary = feas[rows] & rs_ok
        use_primary = primary.any(axis=1)
        masks = np.where(use_primary[:, None], primary, feas[rows])
        alphas = np.array([self._alpha_for(vms[b]) for b in rows])
        scores = hlem_scores_batch_np(
            pool.free(), masks, pool.spot_frac_view(), alphas)
        out[rows] = np.argmax(scores, axis=1)
        return out


@register_policy("hlem-vmp-adjusted")
class HlemVmpAdjusted(HlemVmp):
    """Adjusted HLEM-VMP (§VI-C): spot-load-aware score AHS = HS*(1+α·SL).

    With α < 0 (default -0.5) spot-heavy hosts are penalized when placing spot
    VMs, spreading spot load across hosts to reduce interruption counts.
    ``adjust_spot_only=False`` applies the adjustment to on-demand placement
    too (then on-demand avoids spot-heavy hosts as well — fewer preemptions,
    beyond-paper variant benchmarked in EXPERIMENTS.md).
    """

    name = "hlem-vmp-adjusted"

    def __init__(self, rc: float = 0.95, threshold: float = 0.0,
                 alpha: float = -0.5, adjust_spot_only: bool = True,
                 backend: str = "numpy"):
        super().__init__(rc=rc, threshold=threshold, backend=backend)
        self.alpha = alpha
        self.adjust_spot_only = adjust_spot_only


#: live name → class view of the registry (kept for backward compatibility;
#: register new policies via ``register_policy``, not by mutating this)
POLICIES = POLICY_REGISTRY.entries


def make_policy(name: str, **kwargs) -> AllocationPolicy:
    return POLICY_REGISTRY.build(name, **kwargs)
