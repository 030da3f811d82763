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

from . import hlem
from .hlem import (
    ResidentScorer,
    certified_pick,
    device_arg_bytes,
    dirty_capacity,
    hlem_pick_candidates_np,
    hlem_pick_np,
    hlem_scores_batch_np,
    pack_pick,
    pack_run,
)
from .hosts import HostPool
from ..obs.tracer import NULL_TRACER
from .registry import Registry
from .types import N_DIMS, Vm

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
    #: ``pick/dirty_rows``, ``pick/mirror_uploads``, ``pick/device_calls``
    #: (every device program call, a single pick's or a run's) and
    #: ``pick/host_exact_rows`` (the candidates of the float64 picks after
    #: fallbacks: over ``device_fallbacks``, their mean count); a run
    #: (:meth:`HlemVmp.plan_run`) counts ``pick/run_calls``,
    #: ``pick/tot_uploads`` (uploads of the run program's totals),
    #: ``pick/run_steps`` (steps computed), ``pick/run_taken`` (steps
    #: used) and ``pick/run_dropped`` (steps thrown away), the last also
    #: by cause: ``pick/run_dropped_event`` (another event or pick came
    #: between two of the run's arrivals), ``_bit`` (a rechecked row's
    #: mask bit differs in float64), ``_row`` (a row the device wrote lies
    #: beyond float32's rounding of the host's), ``_pick`` (the host
    #: committed another host than the device's) and ``_clearing`` (an
    #: on-demand VM no host fits goes to the spot-clearing list).
    tracer = NULL_TRACER

    #: whether the policy can place a run of consecutive arrivals from one
    #: device call (:meth:`plan_run`)
    places_runs = False

    @property
    def run_k(self) -> int:
        """The most arrivals the event loop hands :meth:`plan_run` at once:
        ``hlem.PICK_RUN_K`` where the policy places runs, else 1."""
        return hlem.PICK_RUN_K if self.places_runs else 1

    def plan_run(self, vms: Sequence[Vm]) -> None:
        """The event loop's next ``find_host`` calls will place ``vms``, in
        order, unless another event comes between (then :meth:`end_run`).
        A policy that does not place runs ignores it."""

    def end_run(self) -> None:
        """Something other than the planned arrivals happens next: forget
        the run (its remaining steps are dropped)."""

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


class _Run:
    """One run of arrivals (:meth:`HlemVmp.plan_run`) and, once its call is
    made, the device's lines for its steps and what the host committed."""

    __slots__ = ("vms", "next", "out", "masks", "rows", "pool", "pos",
                 "epoch", "steps", "written")

    def __init__(self, vms):
        self.vms = vms
        #: index of the step the next ``find_host`` uses
        self.next = 0
        #: (steps, width) lines; None until the call
        self.out = None
        #: (L, n) host masks at the call (bit 0 feasible, bit 1 primary)
        self.masks = None
        self.rows = 0
        self.pool = None
        #: row-log position and pool epoch the committed steps lead to
        self.pos = 0
        self.epoch = 0
        #: (step, host) of the committed steps that placed, in order (the
        #: row log since the call must read exactly these hosts), and each
        #: chosen row as the device wrote it
        self.steps = []
        self.written = {}


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
        #: up to position ``_mirror_pos`` of that pool's row log, except the
        #: rows a run call wrote (``_stale``), which the next call rewrites
        self._mirror = ResidentScorer()
        self._mirror_pool = None
        self._mirror_pos = 0
        self._stale = set()
        #: (pool, its static_epoch, rows) the mirror's ``tot`` was read at
        self._tot_key = None
        #: the live run of arrivals, if any (:meth:`plan_run`)
        self._run = None
        self.places_runs = backend == "jax"

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
        uploads; ``pick/tot_uploads``, uploads of a run's ``tot``;
        ``pick/device_calls``, device calls;
        ``pick/host_exact_rows``, the candidates of the float64 picks.
        A pick outside a live run ends it (:meth:`plan_run`)."""
        if not mask.any():
            return -1
        alpha = self._alpha_for(vm)
        if self.backend == "jax":
            self.end_run()
            free, spot_frac = pool.storage_views()
            tr = self.tracer
            with tr.span("allocation", "pick/call"):
                ids, sent, uploads = self._sync_mirror(pool, free, spot_frac)
                packed = pack_pick(mask, alpha, ids, free, spot_frac)
                out = self._mirror.scores_tol(packed)
            if tr.enabled:
                self._count_call(sent, packed, ids, uploads)
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

    def _sync_mirror(self, pool: HostPool, free: np.ndarray,
                     spot_frac: np.ndarray):
        """Bring the device mirror up to ``pool``'s storage for the next
        call: (ids of the rows the call must carry, bytes uploaded, whole
        uploads).  A new pool, grown storage or more rewritten rows than a
        packed input holds upload the whole mirror instead."""
        mirror = self._mirror
        changed = (pool.rows_since(self._mirror_pos)
                   if pool is self._mirror_pool else None)
        self._mirror_pool, self._mirror_pos = pool, pool.track_rows()
        pool.compact_row_log(self._mirror_pos)
        ids = ([] if changed is None
               else sorted(self._stale.union(changed)))
        self._stale = set()
        rows = free.shape[0]
        if mirror.rows != rows:
            return [], (mirror.upload(free, spot_frac)
                        + self._compile_for(pool, free, spot_frac)), 1
        if changed is None or len(ids) > dirty_capacity(rows):
            return [], mirror.upload(free, spot_frac), 1
        return ids, 0, 0

    def _compile_for(self, pool: HostPool, free: np.ndarray,
                     spot_frac: np.ndarray) -> int:
        """Compile the single pick's and the run's programs for a new
        storage size at once, by a call of each that changes nothing (no
        candidate, no step, no row), so that whichever a run needs first,
        the other does not compile later.  Returns the bytes sent."""
        rows, d = free.shape
        sent = self._mirror.upload_tot(pool.scoring_totals())
        self._tot_key = (pool, pool.static_epoch, rows)
        single = pack_pick(np.zeros(rows, dtype=bool), 0.0, [], free,
                           spot_frac)
        run = pack_run(np.zeros((0, rows), dtype=np.uint8),
                       np.zeros((0, 2 + 2 * d)), self.rc, self.threshold,
                       self.run_k, [], free, spot_frac)
        self._mirror.scores_tol(single)
        self._mirror.scores_tol_run(run)
        return sent + single.nbytes + run.nbytes

    def _count_call(self, sent: int, packed: np.ndarray, ids,
                    uploads: int) -> None:
        counters = self.tracer.counters
        counters.inc("pick/h2d_bytes", sent + device_arg_bytes(packed))
        counters.inc("pick/dirty_rows", len(ids))
        counters.inc("pick/mirror_uploads", uploads)
        counters.inc("pick/device_calls")

    # -- runs of arrivals (jax backend) --------------------------------------
    def plan_run(self, vms):
        """Score the next arrivals ``vms`` (at most :attr:`run_k`) with one
        device call, made at the first one's placement
        (:func:`~repro.core.hlem.hlem_scores_tol_jax_run`); each later
        ``find_host`` for them takes its step only while everything the
        step assumed holds (:meth:`_take_step`), and otherwise decides as
        a single pick would."""
        self.end_run()
        self._run = _Run(list(vms))

    def end_run(self):
        if self._run is not None:
            self._end_run("event")

    def _end_run(self, cause: str) -> None:
        """Drop the live run's remaining steps, counted under ``cause``.
        The rows its call wrote on the device are in ``_stale`` already."""
        run, self._run = self._run, None
        left = len(run.vms) - run.next
        if run.out is not None and left and self.tracer.enabled:
            self.tracer.counters.inc("pick/run_dropped", left)
            self.tracer.counters.inc("pick/run_dropped_" + cause, left)

    def _run_inputs(self, vms, pool: HostPool):
        """Each VM's mask byte a host (bit 0 direct feasibility with market
        admission and pins, as ``direct_mask_into`` gives it; bit 1 the
        RsDiff primary list, as :meth:`_rsdiff_ok` gives it) at the pool's
        state, in one vectorized pass, and its step parameters."""
        demands = np.array([vm.demand for vm in vms])
        feas = pool.direct_mask_batch(
            demands, np.array([vm.bid for vm in vms]),
            np.array([vm.pool for vm in vms], dtype=np.int64))
        # Eq. 1 once for each distinct CPU demand (arrivals share a few)
        cpu, of = np.unique(demands[:, 0], return_inverse=True)
        tot, util = pool.rsdiff_inputs()
        rs_ok = cpu[:, None] / tot - util * self.rc > self.threshold
        masks = (feas & rs_ok[of]).view(np.uint8) << 1
        masks |= feas.view(np.uint8)
        d = demands.shape[1]
        params = np.empty((len(vms), 2 + 2 * d))
        params[:, 0] = [self._alpha_for(vm) for vm in vms]
        params[:, 1] = [vm.is_spot for vm in vms]
        params[:, 2: 2 + d] = demands - _EPS
        params[:, 2 + d:] = demands
        return masks, params

    def _call_run(self, run: _Run, pool: HostPool) -> None:
        """The run's one device call, in the spans a single pick uses."""
        free, spot_frac = pool.storage_views()
        rows = free.shape[0]
        tr = self.tracer
        with tr.span("allocation", "pick/call"):
            ids, sent, uploads = self._sync_mirror(pool, free, spot_frac)
            key = (pool, pool.static_epoch, rows)
            tot_sent = 0
            if self._tot_key != key:
                tot_sent = self._mirror.upload_tot(pool.scoring_totals())
                self._tot_key = key
            run.masks, params = self._run_inputs(run.vms, pool)
            packed = pack_run(run.masks, params, self.rc, self.threshold,
                              self.run_k, ids, free, spot_frac)
            out = self._mirror.scores_tol_run(packed)
        if tr.enabled:
            self._count_call(sent + tot_sent, packed, ids, uploads)
            tr.counters.inc("pick/tot_uploads", int(tot_sent > 0))
            tr.counters.inc("pick/run_calls")
            tr.counters.inc("pick/run_steps", len(run.vms))
        with tr.span("allocation", "pick/readback"):
            run.out = np.asarray(out)
        run.rows, run.pool = rows, pool
        run.pos, run.epoch = self._mirror_pos, pool.epoch
        picks = run.out[: len(run.vms), rows + 1]
        self._stale.update(int(p) for p in picks[picks >= 0])

    def _take_step(self, vm: Vm, pool: HostPool, allow_spot_clearing: bool):
        """``find_host``'s answer for ``vm`` from the live run's step, or
        None where the step cannot be used (the run is then dropped and the
        caller decides as a single pick would).

        A step is used only while every earlier step was committed as the
        device predicted and nothing else touched the pool (the row log
        since the call holds exactly the committed hosts, and the pool's
        epoch moved by one a placement); and only if, for every row an
        earlier step chose, the float64 feasibility and RsDiff bits equal
        the bits the device used.  Then the step's scores are certified as
        a single pick's are, with ``tol`` widened where a row an earlier
        step wrote lies further from the host's row than float32's
        rounding (:meth:`_step_tol`), or the float64 pick decides; a
        decision other than the device's ends the run after this step."""
        run = self._run
        k = run.next
        if run.vms[k] is not vm:
            self._end_run("event")
            return None
        if run.out is None:
            self._call_run(run, pool)
        elif not (pool is run.pool and pool.epoch == run.epoch
                  and pool.rows_since(run.pos)
                  == [h for _, h in run.steps]):
            self._end_run("event")
            return None
        rows = run.rows
        line = run.out[k]
        pick, use_p = int(line[rows + 1]), bool(line[rows + 2])
        free, spot_frac = pool.storage_views()
        hs = f64 = p64 = None
        tol = float(line[rows])
        if run.steps:
            js = np.array([j for j, _ in run.steps])
            hs = np.array([h for _, h in run.steps])
            f64 = ((run.masks[k, hs] & 1).astype(bool)
                   & (free[hs] >= vm.demand - _EPS).all(axis=1))
            tot, util = pool.rsdiff_inputs()
            p64 = f64 & (vm.demand[0] / tot[hs] - util[hs] * self.rc
                         > self.threshold)
            if not np.array_equal(line[rows + 4 + js], f64 + 2.0 * p64):
                self._end_run("bit")
                return None
            cand = hs[p64 if use_p else f64]
            # one candidate is the pick whatever the scores
            if pick >= 0 and cand.size and line[rows + 3] > 1:
                tol = self._step_tol(run, line, tol, cand, vm, free,
                                     spot_frac)
                if tol is None:
                    self._end_run("row")
                    return None
        if pick < 0:
            if allow_spot_clearing and not vm.is_spot:
                self._end_run("clearing")
                return None
            return self._step_taken(run, k, -1, pick, line)
        self.device_picks += 1
        hid = certified_pick(line[:rows], tol, free, spot_frac)
        if hid is None:
            # a near-tie float32 cannot order: the exact pick decides
            self.device_fallbacks += 1
            mask = self._step_mask(run, k, use_p, hs, f64, p64)
            tr = self.tracer
            with tr.span("allocation", "pick/host-exact"):
                if tr.enabled:
                    tr.counters.inc("pick/host_exact_rows",
                                    int(np.count_nonzero(mask)))
                hid = hlem_pick_np(pool.free(), mask, pool.spot_frac_view(),
                                   self._alpha_for(vm))
        return self._step_taken(run, k, hid, pick, line)

    def _step_taken(self, run: _Run, k: int, hid: int, pick: int, line):
        run.next = k + 1
        if self.tracer.enabled:
            self.tracer.counters.inc("pick/run_taken")
        if hid != pick:
            self._end_run("pick")
        elif hid >= 0:
            run.epoch += 1
            run.steps.append((k, hid))
            at = run.rows + 4 + self.run_k
            run.written[hid] = line[at: at + 2 * N_DIMS]
        if run.next == len(run.vms) and self._run is run:
            self._run = None
        return hid, False

    @staticmethod
    def _step_mask(run: _Run, k: int, use_p: bool, hs, f64, p64):
        """Step k's candidate mask over the pool's hosts, in float64."""
        mask = (run.masks[k] & (2 if use_p else 1)) != 0
        if hs is not None:
            mask[hs] = p64 if use_p else f64
        return mask

    def _step_tol(self, run: _Run, line, tol: float, cand, vm: Vm, free,
                  spot_frac):
        """Step ``line``'s ``tol`` for the candidate rows ``cand`` earlier
        steps wrote: its rounding term taken at max(mag_d, 2^24 e_d) for
        each dimension, e_d the largest distance of those rows' free
        values from the host's, plus 2 |alpha| sum_d w_d e'_d for their
        spot loads' distances (``hlem_scores_tol_jax_run`` derives it).
        None where a float32-degenerate dimension's rows lie off."""
        d = free.shape[1]
        got = np.array([run.written[h] for h in cand], dtype=np.float64)
        err = np.abs(got - np.hstack([free[cand], spot_frac[cand]])).max(
            axis=0)
        w, span, mag = (line[-3 * d:].astype(np.float64)
                        .reshape(3, d))
        over = np.maximum(err[:d] * 2.0 ** 24 - mag, 0.0)
        degen = span <= 1e-12
        if (over[degen] > 0.0).any():
            return None
        live = ~degen
        return (tol + 2.0 ** -20 * float(np.sum(w[live] * over[live]
                                                / span[live]))
                + 2.0 * abs(self._alpha_for(vm)) * float(w @ err[d:]))

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
            if self._run is not None:
                got = self._take_step(vm, pool, allow_spot_clearing)
                if got is not None:
                    return got
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
