"""Plain reference of the simulator's decisions, replayed over its event log.

It imports nothing of the program.  Its inputs are the run's inputs (host
capacities and pools, each VM's request, the stated policy, market and
billing constants) and the program's ordered event records, the answers
under check.  Like a served model's tokens fed back through a reference,
each decision is judged at the state that the program's own earlier
decisions produced:

* ``placement_errors``: placements (fresh start, resumption after
  hibernation, a migration's destination, an on-demand VM's pick over the
  spot-clearing list) on a host that is not a candidate (over capacity,
  price not cleared, wrong pool) or whose HLEM-VMP-adjusted score (paper
  Eqs. 1-11) lies more than ``TIE`` below the best candidate's in float64;
  VMs not placed at submission although a host fits; queued VMs that a
  host fits after a resubmission flush; VMs due that were never submitted.
  ``placement_gap``, the widest such shortfall, is a reading beside it.
* ``wave_victim_diff``: VMs in one of the two sets of price-wave victims at
  a tick and not the other (running spot VMs past their minimum running
  time whose bid is below their pool's clearing price).
* ``price_rel_err``: the clearing price of every pool at every tick,
  recomputed from the pool's CPU utilization and the stated auction process
  and shock streams, as a relative error.
* ``runtime_err_s``: for every finished VM, the time it ran against its
  requested duration; for every VM still running, the time it has run past
  its duration.
* ``billing_rel_err``: the realized spot bill (each closed interval billed
  at the pool's clearing price, capped at the bid, per the stated price
  model), as a relative error.

``dtype`` sets the precision of the arithmetic that decides: ``float32``
is the control, the precision below the float64 the simulator states.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

#: feasibility slack and clamps as the configuration's allocation rules state
FIT_EPS = 1e-9
RS_EPS = 1e-12
SCORE_EPS = 1e-12
#: scoring a placement costs time in the number of candidate hosts: on a
#: cluster of n hosts each placement is scored with probability
#: min(1, SCORED_HOSTS / n), drawn from the run's seed; every placement is
#: checked against its candidate list
SCORED_HOSTS = 2048
#: a chosen host scoring less than this below the best is a tie: float64
#: scores are O(1) sums of four terms, so two orders of summation differ by
#: a few 1e-16, and a float32 score is off by some 1e-8
TIE = 1e-12


@dataclass
class VmSpec:
    demand: np.ndarray          # (4,) cpu, ram, bw, storage
    spot: bool
    duration: float
    bid: float                  # inf: never price-limited
    pin: int                    # capacity-pool pin, -1 for any pool
    min_running_time: float
    submit_time: float


@dataclass
class PolicyRules:
    """HLEM-VMP-adjusted as stated: RsDiff filter (Eqs. 1-2) with ``rc`` and
    ``threshold``; spot-load adjustment ``alpha`` (Eq. 11) for spot VMs
    only when ``adjust_spot_only``."""
    rc: float
    threshold: float
    alpha: float
    adjust_spot_only: bool


@dataclass
class MarketRules:
    """Per-pool auction price process: price = min(od * (0.1 + 0.9 u^3) *
    exp(s), od) with the AR(1) log-shock s' = rho s + sigma sqrt(1-rho^2) z,
    z from ``numpy.random.default_rng(seed)`` standard normals, one per
    tick."""
    od: np.ndarray
    sigma: np.ndarray
    rho: np.ndarray
    seeds: Sequence[int]


@dataclass
class BillingRules:
    """On-demand $/hour of a request (cpu, ram MB, bw Mbps, storage MB)."""
    per_cpu_hour: float
    per_gb_ram_hour: float
    per_gbps_bw_hour: float
    per_tb_storage_hour: float

    def rate(self, d: np.ndarray) -> float:
        return (float(d[0]) * self.per_cpu_hour
                + float(d[1]) / 1024.0 * self.per_gb_ram_hour
                + float(d[2]) / 1000.0 * self.per_gbps_bw_hour
                + float(d[3]) / 1_048_576.0 * self.per_tb_storage_hour)


def hlem_scores(free: np.ndarray, spot_frac: np.ndarray, alpha: float,
                dtype=np.float64) -> np.ndarray:
    """HLEM-VMP(-adjusted) scores of m candidate hosts, from their free
    capacity and spot fraction laid out (D, m)."""
    c = np.array(free, dtype=dtype)
    d, m = c.shape
    lo = c.min(axis=1)
    span = c.max(axis=1) - lo
    flat = span <= SCORE_EPS
    c -= lo[:, None]                                    # Eq. 3
    c /= np.where(flat, 1.0, span).astype(dtype)[:, None]
    c[flat] = 1.0
    col = c.sum(axis=1)                                 # Eq. 4
    thin = col <= SCORE_EPS
    p = c / np.where(thin, 1.0, col).astype(dtype)[:, None]
    p[thin] = dtype(1.0 / m)
    if m > 1:                                           # Eqs. 5-6
        plogp = np.log(np.maximum(p, dtype(SCORE_EPS)))
        plogp *= p
        plogp[p <= SCORE_EPS] = 0.0
        e = -plogp.sum(axis=1) / dtype(math.log(m))
    else:
        e = np.zeros(d, dtype=dtype)
    g = 1.0 - e                                         # Eqs. 7-8
    gs = g.sum()
    w = g / gs if gs > SCORE_EPS else np.full(d, 1.0 / d, dtype=dtype)
    hs = w @ c                                          # Eq. 9
    if alpha != 0.0:                                    # Eqs. 10-11
        hs *= 1.0 + dtype(alpha) * (w @ np.asarray(spot_frac, dtype=dtype))
    return hs


class Replay:
    """The cluster state that the program's records imply, and the checks."""

    def __init__(self, totals: np.ndarray, host_pool: np.ndarray,
                 vms: Dict[int, VmSpec], policy: PolicyRules,
                 market: Optional[MarketRules] = None,
                 billing: Optional[BillingRules] = None, dtype=np.float64,
                 seed: int = 0):
        self.dtype = dtype
        self.coin = np.random.default_rng(seed)
        # ledgers are laid out (D, hosts): a host's row is a column
        self.total = np.array(np.asarray(totals).T, dtype=np.float64)
        n = self.total.shape[1]
        self.pool = np.asarray(host_pool, dtype=np.int64)
        self.n = 0                       # hosts added so far
        self.active = np.zeros(n, dtype=bool)
        self.used = np.zeros((4, n))
        self.spot_used = np.zeros((4, n))
        # the control keeps its own accounting in its own precision
        self.control = dtype is not np.float64
        self.total_c = self.total.astype(dtype)
        self.used_c = np.zeros((4, n), dtype=dtype)
        self.spot_c = np.zeros((4, n), dtype=dtype)
        self.residents: List[Dict[int, None]] = [dict() for _ in range(n)]
        self.vms = vms
        self.policy = policy
        self.market = market
        self.billing = billing
        self.price = np.zeros(int(self.pool.max()) + 1 if n else 1)
        self.sample_p = min(1.0, SCORED_HOSTS / max(n, 1))
        # per-VM replay state
        self.state: Dict[int, str] = {}
        self.host: Dict[int, int] = {}
        self.run_start: Dict[int, float] = {}
        self.ready: Dict[int, float] = {}
        self.ran: Dict[int, float] = {}
        self.intervals: Dict[int, List[Tuple[int, float, float]]] = {}
        self.queue: Dict[int, None] = {}
        self.reserved: Dict[int, int] = {}
        self.clearing: Dict[int, tuple] = {}
        self.cleared_hosts: Dict[int, float] = {}
        # shock streams and AR(1) state of the price check
        if market is not None:
            self.rngs = [np.random.default_rng(int(s)) for s in market.seeds]
            self.log_shock = np.zeros(len(market.seeds))
        self.tick_times: List[float] = []
        self.tick_prices: List[np.ndarray] = []
        # readings
        self.gap = 0.0
        self.errors = 0
        self.victim_diff = 0
        self.price_err = 0.0
        self.runtime_err = 0.0
        self.placements = 0

    # -- state -------------------------------------------------------------
    def add_host(self, hid: int) -> None:
        self.n = max(self.n, hid + 1)
        self.active[hid] = True

    def _ledger(self, control: bool):
        if control:
            return self.total_c, self.used_c, self.spot_c, self.dtype
        return self.total, self.used, self.spot_used, np.float64

    def _book(self, hid: int, vid: int, sign: int, spot: bool) -> None:
        """Add (``sign`` 1) or take away (-1) a VM's demand on a host, in
        both ledgers, clamped at zero as the program clamps."""
        d = self.vms[vid].demand
        for used, spot_used, dt in ((self.used, self.spot_used, np.float64),
                                    (self.used_c, self.spot_c, self.dtype)):
            rows = (used, spot_used) if spot else (used,)
            for a in rows:
                a[:, hid] = np.maximum(a[:, hid] + dt(sign) * d.astype(dt),
                                       0.0)

    def free(self, control: bool = False) -> np.ndarray:
        total, used, _, _ = self._ledger(control)
        f = total[:, : self.n] - used[:, : self.n]
        f[:, ~self.active[: self.n]] = 0.0
        return f

    def candidates(self, vid: int, extra: Optional[np.ndarray] = None,
                   pool: int = -1, control: bool = False) -> np.ndarray:
        """Hosts that fit the VM now (``extra`` capacity added: the
        spot-clearing list), admitted by price and pool pin."""
        vm = self.vms[vid]
        dt = self._ledger(control)[3]
        room = self.free(control)
        if extra is not None:
            room = room + extra.astype(dt)
        ok = ((room >= (vm.demand - FIT_EPS).astype(dt)[:, None]).all(axis=0)
              & self.active[: self.n])
        if self.market is not None and math.isfinite(vm.bid):
            ok &= self.price[self.pool[: self.n]] <= vm.bid + FIT_EPS
        pin = pool if pool >= 0 else vm.pin
        if pin >= 0:
            ok &= self.pool[: self.n] == pin
        return ok

    def _score(self, vid: int, ok: np.ndarray, control: bool):
        """HLEM-VMP-adjusted over the candidates ``ok``, after the RsDiff
        filter (relaxed to all candidates when it leaves none): the
        candidates and their scores, in one ledger's precision."""
        vm = self.vms[vid]
        total, used, _, dt = self._ledger(control)
        idx = np.flatnonzero(ok)
        if idx.size > 1:
            tot = np.maximum(total[0, idx], dt(RS_EPS))
            rs = (dt(vm.demand[0]) / tot - used[0, idx] / tot
                  * dt(self.policy.rc)) > self.policy.threshold
            if rs.any():
                idx = idx[rs]
        if idx.size == 0:
            return idx, np.zeros(0, dtype=dt)
        p = self.policy
        alpha = p.alpha if (vm.spot or not p.adjust_spot_only) else 0.0
        spot = self._ledger(control)[2]
        tot = total[:, idx]
        free = tot - used[:, idx]         # candidates are active hosts
        sf = spot[:, idx] / np.maximum(tot, dt(FIT_EPS))
        return idx, hlem_scores(free, sf, alpha, dt)

    def decide(self, vid: int, extra: Optional[np.ndarray] = None,
               pool: int = -1):
        """What judging a placement needs, taken at the state it was made
        in.  Scored (a draw of the seeded coin): the candidates (ascending)
        with their float64 scores, and where this is the control, the host
        that it picks in its own precision.  Not scored: the candidate
        mask."""
        ok = self.candidates(vid, extra, pool)
        if self.coin.random() >= self.sample_p:
            return ok, None, None
        idx, s64 = self._score(vid, ok, False)
        pick = None
        if self.control:
            ic, sc = self._score(vid, self.candidates(vid, extra, pool, True),
                                 True)
            pick = int(ic[int(np.argmax(sc))]) if ic.size else -1
        return idx, s64, pick

    def _is_candidate(self, decided, h: int) -> bool:
        idx, s64, _ = decided
        if s64 is None:
            return 0 <= h < idx.size and bool(idx[h])
        return self._at(idx, h) >= 0

    @staticmethod
    def _at(idx: np.ndarray, h: int) -> int:
        """Position of host ``h`` among the candidates ``idx``, or -1."""
        i = int(np.searchsorted(idx, h))
        return i if i < idx.size and idx[i] == h else -1

    def judge(self, chosen: int, decided) -> None:
        """The gap of the chosen host (the control: of its own pick) below
        the float64 best."""
        idx, s64, pick = decided
        self.placements += 1
        if s64 is None:                  # not scored: a candidate or not
            if not self.control and not self._is_candidate(decided, chosen):
                self.errors += 1
            return
        i = self._at(idx, chosen if pick is None else pick)
        if i < 0:
            self.errors += 1
            return
        gap = float(s64.max() - s64[i])
        self.gap = max(self.gap, gap)
        if gap > TIE:
            self.errors += 1

    def place(self, vid: int, hid: int, t: float) -> None:
        vm = self.vms[vid]
        self._book(hid, vid, 1, vm.spot)
        self.residents[hid][vid] = None
        self.host[vid] = hid
        self.state[vid] = "running"
        self.run_start[vid] = t
        self.ready[vid] = t + vm.min_running_time
        self.queue.pop(vid, None)

    def leave(self, vid: int, t: float) -> None:
        """Close the VM's running interval and free its host."""
        vm, hid = self.vms[vid], self.host.pop(vid)
        start = self.run_start.pop(vid)
        self.ran[vid] = self.acc(self.ran.get(vid, 0.0), t - start)
        self.intervals.setdefault(vid, []).append((hid, start, t))
        self._book(hid, vid, -1, vm.spot)
        del self.residents[hid][vid]

    def acc(self, a: float, b: float) -> float:
        return float(self.dtype(a) + self.dtype(b))

    # -- the checks --------------------------------------------------------
    def check_submit(self, vid: int, t: float, next_rec) -> None:
        self.state[vid] = "waiting"
        ok = self.candidates(vid)
        started = (next_rec is not None and next_rec[1] == "start"
                   and next_rec[2] == vid and next_rec[0] == t)
        if ok.any():
            if not started:
                self.errors += 1
            return
        vm = self.vms[vid]
        if not vm.spot:
            reclaim = np.zeros((4, self.n))
            for h in range(self.n):
                for v in self.residents[h]:
                    s = self.vms[v]
                    if (s.spot and self.state.get(v) == "running"
                            and self.ready[v] <= t):
                        reclaim[:, h] += s.demand
            ok = self.candidates(vid, extra=reclaim)
            if ok.any():
                self.clearing[vid] = (t, self.decide(vid, extra=reclaim))
                return
        self.queue[vid] = None

    def check_start(self, vid: int, hid: int, t: float) -> None:
        pend = self.clearing.pop(vid, None)
        if (pend is not None and self._is_candidate(pend[1], hid)
                and self.cleared_hosts.get(hid, -1.0) >= pend[0]):
            self.judge(hid, pend[1])
        else:
            self.judge(hid, self.decide(vid))
        self.place(vid, hid, t)

    def check_flush(self) -> None:
        """After a resubmission flush no queued VM fits anywhere."""
        if not self.queue:
            return
        vs = [self.vms[v] for v in self.queue]
        dem = np.array([v.demand for v in vs])
        n = self.n
        ok = (self.free()[None] >= dem[:, :, None] - FIT_EPS).all(axis=1)
        ok &= self.active[None, :n]
        bids = np.array([v.bid for v in vs])
        if self.market is not None:
            price = self.price[self.pool[:n]]
            ok &= (price[None] <= bids[:, None] + FIT_EPS) \
                | ~np.isfinite(bids)[:, None]
        pins = np.array([v.pin for v in vs])
        ok &= (self.pool[None, :n] == pins[:, None]) | (pins < 0)[:, None]
        self.errors += int(ok.any(axis=1).sum())

    def tick(self, t: float, logged: np.ndarray) -> None:
        """A price tick: recompute every pool's clearing price, then apply
        the program's prices (the ones its later decisions saw)."""
        mk = self.market
        n = self.n
        act = self.active[:n]
        k = len(mk.seeds)
        total, used, _, dt = self._ledger(self.control)
        used = np.bincount(self.pool[:n][act], weights=used[0, :n][act],
                           minlength=k).astype(dt)
        tot = np.bincount(self.pool[:n][act], weights=total[0, :n][act],
                          minlength=k).astype(dt)
        u = np.clip(np.where(tot > 0, used / np.where(tot > 0, tot, 1.0),
                             0.0), 0.0, 1.0).astype(dt)
        z = np.array([g.standard_normal() for g in self.rngs])
        self.log_shock = (mk.rho * self.log_shock
                          + mk.sigma * np.sqrt(1.0 - mk.rho ** 2) * z)
        od = mk.od.astype(dt)
        want = np.minimum(od * (dt(0.1) + dt(0.9) * u ** 3)
                          * np.exp(self.log_shock.astype(dt)), od)
        err = np.abs(want.astype(np.float64) - logged) / np.abs(logged)
        self.price_err = max(self.price_err, float(err.max()))
        self.price = np.array(logged, dtype=np.float64)
        self.tick_times.append(t)
        self.tick_prices.append(self.price.copy())

    def wave_victims(self, t: float) -> set:
        out = set()
        for vid, st in self.state.items():
            if st != "running" or not self.vms[vid].spot:
                continue
            vm = self.vms[vid]
            if (vm.bid < self.price[self.pool[self.host[vid]]] - FIT_EPS
                    and self.ready[vid] <= t + FIT_EPS):
                out.add(vid)
        return out

    def finish(self, vid: int) -> None:
        want = self.vms[vid].duration
        self.runtime_err = max(self.runtime_err,
                               abs(self.ran.get(vid, 0.0) - want))

    def bill(self) -> float:
        """Realized spot bill over every closed interval."""
        ts = np.asarray(self.tick_times, dtype=np.float64)
        ph = np.asarray(self.tick_prices, dtype=np.float64)   # (ticks, pools)
        dt = self.dtype
        total = dt(0.0)
        for vid, ivs in self.intervals.items():
            vm = self.vms[vid]
            if not vm.spot:
                continue
            rate = dt(self.billing.rate(vm.demand))
            for hid, t0, t1 in ivs:
                p = int(self.pool[hid])
                cost = dt(0.0)
                for k in range(np.searchsorted(ts, t0, "right") - 1, ts.size):
                    a = max(t0, ts[k]) if k >= 0 else t0
                    b = min(t1, ts[k + 1]) if k + 1 < ts.size else t1
                    if b <= a:
                        if k + 1 < ts.size and ts[k + 1] >= t1:
                            break
                        continue
                    price = min(ph[k, p], vm.bid) if k >= 0 else 0.0
                    cost += dt(price) * dt(b - a)
                total += rate / dt(3600.0) * cost / dt(self.market.od[p])
        return float(total)


def replay(records: Iterable[tuple], t_end: float, totals: np.ndarray,
           host_pool: np.ndarray, n_initial: int, vms: Dict[int, VmSpec],
           policy: PolicyRules, market: Optional[MarketRules] = None,
           billing: Optional[BillingRules] = None,
           spot_cost: Optional[float] = None,
           destinations: Optional[Dict[int, List[int]]] = None,
           dtype=np.float64, seed: int = 0) -> dict:
    """Judge one run, which was driven to the simulated time ``t_end``.
    ``records`` are ``(t, kind, vm, pool, host, a, b, aux)`` in the order
    the program emitted them; hosts ``0..n_initial-1`` exist from the
    start.  ``destinations`` gives each VM's migration destinations in
    order (a flight still in the air at the end has no completion record).
    Returns the readings and the number of placements judged.  ``seed``
    draws the placements that are scored."""
    recs = list(records)
    r = Replay(totals, host_pool, vms, policy, market, billing, dtype, seed)
    for h in range(n_initial):
        r.add_host(h)
    dest = {v: list(h) for v, h in (destinations or {}).items()}
    program_victims: Dict[float, set] = {}
    tick_buf: List[tuple] = []

    def close_tick():
        if not tick_buf:
            return
        t = tick_buf[0][0]
        logged = np.zeros(len(market.seeds))
        for rec in tick_buf:
            logged[rec[3]] = rec[5]
        r.tick(t, logged)
        want = r.wave_victims(t)
        program_victims.setdefault(t, set())
        tick_buf.clear()
        return t, want

    ref_victims: Dict[float, set] = {}
    for i, rec in enumerate(recs):
        t, kind, vid, pool, hid, a, b, aux = rec
        if tick_buf and kind != "price-tick":
            tt, want = close_tick()
            ref_victims[tt] = want
        if kind == "price-tick":
            tick_buf.append(rec)
        elif kind == "submit":
            r.check_submit(vid, t, recs[i + 1] if i + 1 < len(recs) else None)
        elif kind in ("start", "resume"):
            r.check_start(vid, hid, t)
        elif kind == "finish":
            if r.state.get(vid) == "running":
                r.leave(vid, t)
            r.state[vid] = "done"
            r.queue.pop(vid, None)
            r.finish(vid)
        elif kind == "interrupt":
            if aux == "price-wave":
                program_victims.setdefault(t, set()).add(vid)
            if aux == "capacity":
                r.cleared_hosts[hid] = t
            if r.state.get(vid) == "running":
                r.leave(vid, t)
                r.state[vid] = "stopped"
        elif kind == "hibernate":
            r.state[vid] = "hibernated"
            r.queue[vid] = None
        elif kind in ("terminate", "fail"):
            r.state[vid] = "done"
            r.queue.pop(vid, None)
        elif kind == "migrate-start":
            to = dest[vid].pop(0)
            r.judge(to, r.decide(vid, pool=int(b)))
            r.leave(vid, t)
            r.state[vid] = "migrating"
            r._book(to, vid, 1, False)
            r.reserved[vid] = to
        elif kind == "migrate-complete":
            r._book(r.reserved.pop(vid), vid, -1, False)
            if aux == "ok":
                r.place(vid, hid, t)
            else:
                r.state[vid] = "stopped"
        elif kind == "alloc-flush":
            r.check_flush()
        elif kind == "host-add":
            r.add_host(hid)
        elif kind == "host-remove":
            r.active[hid] = False
            for v in list(r.residents[hid]):
                if not vms[v].spot:      # on-demand VMs requeue silently
                    r.leave(v, t)
                    r.state[v] = "waiting"
                    r.queue[v] = None
        if kind != "submit":
            # a clearing decision not followed at its instant is queued
            for v in [v for v, c in r.clearing.items() if c[0] < t]:
                del r.clearing[v]
                if r.state.get(v) == "waiting":
                    r.queue[v] = None
    if tick_buf:
        tt, want = close_tick()
        ref_victims[tt] = want
    for t, want in ref_victims.items():
        r.victim_diff += len(want ^ program_victims.get(t, set()))
    for t, got in program_victims.items():
        if t not in ref_victims:
            r.victim_diff += len(got)
    # every VM due by the end was submitted
    r.errors += sum(1 for vid, vm in vms.items()
                    if vm.submit_time <= t_end and vid not in r.state)
    # a VM still running has not run past its duration
    for vid, st in r.state.items():
        if st == "running":
            over = (r.ran.get(vid, 0.0) + (t_end - r.run_start[vid])
                    - vms[vid].duration)
            r.runtime_err = max(r.runtime_err, over)
    out = {"placement_gap": r.gap, "placement_errors": r.errors,
           "runtime_err_s": r.runtime_err, "placements": r.placements}
    if market is not None:
        out["wave_victim_diff"] = r.victim_diff
        out["price_rel_err"] = r.price_err
        if spot_cost is not None and billing is not None:
            want = r.bill()
            out["billing_rel_err"] = abs(want - spot_cost) / abs(want)
    return out
