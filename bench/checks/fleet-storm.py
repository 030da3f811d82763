"""A configuration's own check: a spot fleet held at its target under
correlated interruption storms.

It replays the run's event log with the base reference's inputs (hosts'
totals and pools, each VM's request, bid and pin, each migration's
destination), keeps each VM's state and each host's CPU in use, and judges
at each price tick, at the state the program's own earlier decisions
produced, what the configuration's ``stated`` block says of the fleet and
the storms:

``stated["fleet"]``::

    {"strategy": "diversified",   # the only strategy judged
     "target_capacity": 64.0,     # CPU, held as ceil(target / size[0]) slots
     "size": [2, 2048, 10, 1024], # a unit: cpu, ram MB, bw, storage MB
     "pool_weights": [1, 1, 1, 1],
     "spot_bid_of_od_rate": 0.6,
     "ladder": [["same-pool", 2], ["cheaper-pool", 2], ["on-demand", 1],
                ["queue", 2], ["scale-down", 1]],   # rungs and tries
     "backoff_s": {"base": 60.0, "mult": 2.0, "cap": 960.0},
     "od_lease_s": 1800.0}

``stated["faults"]``::

    {"scenario": "storm", "first_s": 3600.0, "every_s": 2400.0,
     "count": 3, "fraction": 0.5, "pools": "all"}

The fleet acts once a tick, after the tick's price wave, storms and
resubmission flush (the tick's first ``alloc-flush`` record).  A slot is
fresh, healthy or in an episode.  It becomes healthy when its VM is seen up
(running or migrating) at a tick; an episode opens when a VM that was seen
up is dead, and walks the ladder one attempt at a time; an on-demand VM that
ends its lease leaves the slot fresh.  A VM that dies before it is seen up
leaves the slot where it was.  A pool is admissible when its price is at
most the bid + 1e-9 and it holds one unit of free CPU at that instant (the
tick's launches before it taken off).

Numbers, each a count of wrong answers (limit 0):

* ``storm_victim_diff``: VMs in one of the two sets of storm victims at a
  tick and not the other.  At the first tick at or past each storm's time,
  per affected pool, the running spot VMs after the tick's price wave; the
  ceiling of fraction x their count, lowest bid first, then lowest id.
* ``fleet_rung_errors``: an attempt on a rung other than the next of the
  ladder (each rung its tries, in order), an attempt or a launch on a slot
  in no episode, a launch no rung asked for, a slot retired other than by
  the scale-down rung or an exhausted ladder, or not retired by them.
* ``fleet_backoff_errors``: an attempt before it is due, and a due slot not
  attempted.  The k-th attempt of an episode makes the next due
  base x mult^(k-1) s later, at most cap.
* ``fleet_pool_errors``: a launch off the rule, a launch where no pool was
  admissible, and no launch where one was.  Same-pool takes the home pool
  (that of the slot's last launch) if admissible; cheaper-pool the cheapest
  other admissible pool; on-demand the pool with the most free CPU that
  holds a unit; lowest pool id on ties.  A tick's due fresh slots, in slot
  order, take pools in pool order by the diversified apportionment: the
  weight-proportional split of the fleet's running spot units plus those
  needed, positive residuals by largest remainder (price, then pool id,
  break ties), capped by each admissible pool's units, the leftover handed
  round in that order; the cheapest pools first where no residual is left.

It claims every VM at or above the workload's stated VM count, and counts
in ``input_errors`` each one with no fleet launch record, or whose inputs
depart from ``stated["fleet"]``: the unit's size; its submission at the
launch; a spot launch's bid (the share of the on-demand rate), infinite
duration and pin to the launch pool; an on-demand launch's lease, infinite
bid and pin.  ``dtype`` sets the precision of prices, bids, free CPU and
the apportionment.
"""
import math

import numpy as np

from bench import check as base

#: admission slack, as the fleet's rules state it
EPS = 1e-9
RUNGS = ("same-pool", "cheaper-pool", "on-demand", "queue", "scale-down")
#: what a rung launches, where it launches
LAUNCHES = {"same-pool": "spot", "cheaper-pool": "spot", "on-demand": "od"}


class Rules:
    def __init__(self, config, dtype):
        st = config["stated"]
        f, z = st["fleet"], st["faults"]
        if f["strategy"] != "diversified":
            raise ValueError(f"the fleet check judges the diversified "
                             f"strategy, not {f['strategy']!r}")
        if z["scenario"] != "storm":
            raise ValueError(f"the fleet check judges storms, not "
                             f"{z['scenario']!r}")
        self.dt = dtype
        self.k = int(st["hosts"]["pools"])
        self.size = [float(x) for x in f["size"]]
        self.unit = self.size[0]
        self.slots = int(math.ceil(float(f["target_capacity"]) / self.unit))
        self.weights = np.array(f["pool_weights"], dtype=dtype)
        od = float(st["market"]["on_demand_rate"])
        self.bid = float(f["spot_bid_of_od_rate"]) * od
        self.lease = float(f["od_lease_s"])
        #: the rung of each attempt of an episode, in order
        self.seq = []
        for rung, tries in f["ladder"]:
            if rung not in RUNGS:
                raise ValueError(f"unknown rung {rung!r}")
            self.seq += [rung] * int(tries)
        b = f["backoff_s"]
        self.backoff = (float(b["base"]), float(b["mult"]), float(b["cap"]))
        pools = z["pools"]
        self.storm_pools = (list(range(self.k)) if pools == "all"
                            else [int(p) for p in pools])
        self.storms = [float(z["first_s"]) + i * float(z["every_s"])
                       for i in range(int(z["count"]))]
        self.fraction = float(z["fraction"])

    def wait(self, k):
        base_s, mult, cap = self.backoff
        return min(cap, base_s * mult ** (k - 1))


def apportion(need, cur, cap, weights, prices, dt):
    """Launch counts per pool for ``need`` fresh slots."""
    n = len(cap)
    counts = [0] * n
    if need <= 0 or not any(cap):
        return counts
    total = dt(sum(cur) + need)
    wsum = sum(weights, dt(0.0))
    desired = [weights[p] * (total / wsum) for p in range(n)]
    residual = [max(desired[p] - dt(cur[p]), dt(0.0)) if cap[p] > 0
                else dt(0.0) for p in range(n)]
    rsum = sum(residual, dt(0.0))
    if rsum <= 0.0:
        for p in sorted(range(n), key=lambda q: (prices[q], q)):
            counts[p] = min(need, cap[p])
            need -= counts[p]
            if need == 0:
                break
        return counts
    shares = [residual[p] * (dt(need) / rsum) for p in range(n)]
    floors = [math.floor(x) for x in shares]
    counts = [min(int(floors[p]), cap[p]) for p in range(n)]
    order = sorted(range(n), key=lambda q: (-(shares[q] - floors[q]),
                                            prices[q], q))
    rem = need - sum(counts)
    while rem > 0:
        progress = False
        for p in order:
            if rem == 0:
                break
            if counts[p] < cap[p]:
                counts[p] += 1
                rem -= 1
                progress = True
        if not progress:
            break
    return counts


class Tick:
    """One price tick: the prices, the free CPU the fleet plans with, and
    what is due."""

    def __init__(self, t, prices):
        self.t = t
        self.prices = prices
        self.running = None      # running spot VMs after the group
        self.wave = set()        # the program's price-wave victims
        self.storm = set()       # the program's storm victims
        self.acted = False       # the fleet's point passed
        self.free = None
        self.fresh = {}          # due fresh slot -> expected pool or None
        self.got = {}            # fresh slot -> launched pool
        self.due = set()         # due episode slots with a rung left
        self.exhausted = set()   # due episode slots past the ladder
        self.tried = set()
        self.pending = None      # the Attempt under way


class Attempt:
    """A fleet action under way until the next fleet record: a fresh slot's
    launch (``rung`` "launch") or an episode slot's ladder attempt, with the
    pool the rules give it (None: none admissible)."""

    def __init__(self, slot, rung, pool):
        self.slot, self.rung, self.pool = slot, rung, pool
        self.launched = self.retired = False


class Replay:
    def __init__(self, inp, rules):
        self.r = rules
        self.vms = inp["vms"]
        self.cpu_total = np.asarray(inp["totals"], dtype=np.float64)[:, 0]
        self.pool_of = np.asarray(inp["host_pool"], dtype=np.int64)
        n = self.cpu_total.size
        self.active = np.zeros(n, dtype=bool)
        self.active[:inp["n_initial"]] = True
        self.used = np.zeros(n)
        self.dest = {v: list(h) for v, h in inp["destinations"].items()}
        self.state, self.host, self.reserved = {}, {}, {}
        s = rules.slots
        self.vid = [-1] * s
        self.od = [False] * s
        self.ran = [False] * s
        self.episode = [False] * s
        self.k = [0] * s
        self.next = [0.0] * s
        self.retired = [False] * s
        self.home = [-1] * s
        self.launches = {}       # vm -> (t, pool, "spot" | "od")
        self.fired = [False] * len(rules.storms)
        self.counts = dict(storm_victim_diff=0, fleet_rung_errors=0,
                           fleet_backoff_errors=0, fleet_pool_errors=0)
        self.attempted = 0

    # -- the cluster the records imply ------------------------------------
    def _book(self, vid, hid, sign):
        self.used[hid] += sign * float(self.vms[vid].demand[0])

    def _place(self, vid, hid):
        self._book(vid, hid, 1)
        self.host[vid] = hid
        self.state[vid] = "running"

    def _leave(self, vid):
        """Free the host of a running VM (``host`` holds running VMs)."""
        hid = self.host.pop(vid, None)
        if hid is not None:
            self._book(vid, hid, -1)

    def apply(self, rec):
        t, kind, vid, pool, hid, a, b, aux = rec
        if kind == "submit":
            self.state[vid] = "waiting"
        elif kind in ("start", "resume"):
            self._place(vid, hid)
        elif kind in ("finish", "interrupt"):
            self._leave(vid)
            self.state[vid] = "finished" if kind == "finish" else "stopped"
        elif kind in ("hibernate", "terminate", "fail"):
            self.state[vid] = kind
        elif kind == "migrate-start":
            to = self.dest[vid].pop(0)
            self._leave(vid)
            self.state[vid] = "migrating"
            self._book(vid, to, 1)
            self.reserved[vid] = to
        elif kind == "migrate-complete":
            self._book(vid, self.reserved.pop(vid), -1)
            if aux == "ok":
                self._place(vid, hid)
            else:
                self.state[vid] = "stopped"
        elif kind == "host-add":
            self.active[hid] = True
        elif kind == "host-remove":
            self.active[hid] = False
            for v in [v for v, h in self.host.items()
                      if h == hid and not self.vms[v].spot]:
                self._leave(v)       # on-demand VMs requeue silently
                self.state[v] = "waiting"

    def free_cpu(self):
        act = self.active
        return np.bincount(self.pool_of[act],
                           weights=(self.cpu_total - self.used)[act],
                           minlength=self.r.k).astype(self.r.dt)

    def running_spot(self):
        """Running spot VMs, each with its host's pool."""
        return {v: int(self.pool_of[self.host[v]])
                for v, st in self.state.items()
                if st == "running" and self.vms[v].spot}

    # -- storms -----------------------------------------------------------
    def judge_storms(self, tk):
        r = self.r
        left = {v: p for v, p in tk.running.items() if v not in tk.wave}
        want = set()
        for i, t0 in enumerate(r.storms):
            if self.fired[i] or t0 > tk.t + EPS:
                continue
            self.fired[i] = True
            for p in r.storm_pools:
                rows = sorted((self.vms[v].bid, v) for v, q in left.items()
                              if q == p)
                for _, v in rows[:int(math.ceil(r.fraction * len(rows)))]:
                    want.add(v)
                    del left[v]
        self.counts["storm_victim_diff"] += len(want ^ tk.storm)
        self.attempted += len(want)

    # -- the fleet --------------------------------------------------------
    def admissible(self, tk, p):
        r = self.r
        return (tk.prices[p] <= r.dt(r.bid) + r.dt(EPS)
                and tk.free[p] >= r.dt(r.unit) - r.dt(EPS))

    def pool_for(self, tk, rung, home):
        r = self.r
        if rung == "same-pool":
            p = home if home >= 0 else 0
            return p if self.admissible(tk, p) else None
        best = None
        if rung == "cheaper-pool":
            for p in range(r.k):
                if p != home and self.admissible(tk, p) and (
                        best is None or tk.prices[p] < tk.prices[best] - EPS):
                    best = p
        elif rung == "on-demand":
            for p in range(r.k):
                if tk.free[p] >= r.dt(r.unit) - r.dt(EPS) and (
                        best is None or tk.free[p] > tk.free[best] + EPS):
                    best = p
        return best

    def observe(self, t):
        for s in range(self.r.slots):
            v = self.vid[s]
            if self.retired[s] or v < 0:
                continue
            st = self.state.get(v, "waiting")
            if st in ("running", "migrating"):
                if not self.ran[s] or self.episode[s]:
                    self.ran[s], self.episode[s], self.k[s] = True, False, 0
            elif st == "waiting":
                continue
            elif st == "finished" and self.od[s]:
                self.vid[s], self.od[s], self.ran[s] = -1, False, False
                self.episode[s], self.k[s], self.next[s] = False, 0, t
            elif self.ran[s]:
                if self.vms[v].pin >= 0:
                    self.home[s] = self.vms[v].pin
                self.vid[s], self.od[s], self.ran[s] = -1, False, False
                self.episode[s], self.k[s], self.next[s] = True, 0, t
            else:
                self.vid[s], self.od[s] = -1, False

    def act(self, tk):
        """The fleet's point of the tick: what it should do, and the free
        CPU it plans with."""
        r = self.r
        tk.acted = True
        self.observe(tk.t)
        tk.free = self.free_cpu()
        due = [s for s in range(r.slots) if not self.retired[s]
               and self.vid[s] < 0 and self.next[s] <= tk.t + EPS]
        fresh = [s for s in due if not self.episode[s]]
        for s in due:
            if self.episode[s]:
                (tk.due if self.k[s] < len(r.seq) else tk.exhausted).add(s)
        running = self.running_spot()
        cur = [0] * r.k
        for s in range(r.slots):
            v = self.vid[s]
            if v >= 0 and not self.od[s] and v in running:
                cur[running[v]] += 1
        cap = [int(math.floor(tk.free[p] / r.dt(r.unit)))
               if self.admissible(tk, p) and r.weights[p] > 0 else 0
               for p in range(r.k)]
        counts = apportion(len(fresh), cur, cap, r.weights, tk.prices, r.dt)
        pools = [p for p in range(r.k) for _ in range(counts[p])]
        tk.fresh = {s: (pools[i] if i < len(pools) else None)
                    for i, s in enumerate(fresh)}
        self.attempted += len(due)

    def close(self, tk):
        """The end of the action under way."""
        a, tk.pending = tk.pending, None
        if a is None:
            return
        if a.rung == "launch":
            self.counts["fleet_rung_errors"] += int(not a.launched)
        elif a.rung == "scale-down":
            self.counts["fleet_rung_errors"] += int(not a.retired)
        elif a.pool is not None and not a.launched:
            self.counts["fleet_pool_errors"] += 1

    def fleet(self, tk, rec):
        t, kind, vid, pool, hid, a, b, aux = rec
        c = self.counts
        if tk is None or not tk.acted or t != tk.t:
            c["fleet_rung_errors"] += 1    # the fleet acts at its point
            return
        r = self.r
        if kind == "fleet-launch":
            s = int(b)
            self.launches[vid] = (t, pool, aux)
            act = tk.pending
            if act is not None and act.slot == s and not act.launched:
                act.launched = True
                if act.rung == "launch":
                    tk.got[s] = pool
                    c["fleet_pool_errors"] += int(aux != "spot")
                else:
                    c["fleet_pool_errors"] += int(
                        act.pool is None or pool != act.pool
                        or aux != LAUNCHES.get(act.rung))
            else:
                c["fleet_rung_errors"] += 1
            if 0 <= s < r.slots:
                self.vid[s], self.od[s] = vid, aux == "od"
                self.ran[s], self.home[s] = False, pool
            if 0 <= pool < r.k:
                tk.free[pool] -= r.dt(r.unit)
            return
        s = int(a)
        if kind == "fleet-retire":
            act = tk.pending
            if act is not None and act.slot == s and act.rung == "scale-down":
                act.retired = True
            else:
                self.close(tk)
                c["fleet_rung_errors"] += int(s not in tk.exhausted)
            if 0 <= s < r.slots:
                self.retired[s], self.vid[s] = True, -1
            return
        self.close(tk)                     # a rung record: a new attempt
        if not 0 <= s < r.slots:
            c["fleet_rung_errors"] += 1
            return
        if aux == "launch":
            if s in tk.fresh and s not in tk.got:
                tk.pending = Attempt(s, aux, tk.fresh[s])
            elif self.episode[s] or self.retired[s]:
                c["fleet_rung_errors"] += 1
            else:
                c["fleet_backoff_errors"] += 1
            return
        tk.tried.add(s)
        if self.retired[s] or not self.episode[s]:
            c["fleet_rung_errors"] += 1
        else:
            if s not in tk.due and s not in tk.exhausted:
                c["fleet_backoff_errors"] += 1
            k = self.k[s]
            c["fleet_rung_errors"] += int(k >= len(r.seq) or r.seq[k] != aux)
        tk.pending = Attempt(s, aux, self.pool_for(tk, aux, self.home[s]))
        self.k[s] += 1
        self.next[s] = t + r.wait(self.k[s])

    def finish(self, tk):
        """The end of a tick: what was due and not done."""
        if tk is None:
            return
        if not tk.acted:                   # no flush: the storms alone
            if tk.running is None:         # no record after its prices
                tk.running = self.running_spot()
            self.judge_storms(tk)
            return
        self.close(tk)
        c = self.counts
        for s in set(tk.fresh) | set(tk.got):
            c["fleet_pool_errors"] += int(tk.fresh.get(s) != tk.got.get(s))
        c["fleet_backoff_errors"] += len(tk.due - tk.tried)
        c["fleet_rung_errors"] += sum(1 for s in tk.exhausted
                                      if not self.retired[s])

    # -- the log ----------------------------------------------------------
    def run(self, records):
        r = self.r
        tk, group = None, None
        for rec in records:
            t, kind, vid, pool, hid, a, b, aux = rec
            if kind == "price-tick":
                if group is None or group.t != t:
                    self.finish(group if group is not None else tk)
                    tk = None
                    group = Tick(t, np.zeros(r.k, dtype=r.dt))
                group.prices[pool] = r.dt(a)
                continue
            if group is not None:          # the tick's prices are all in
                tk, group = group, None
                tk.running = self.running_spot()
            if tk is not None and t == tk.t and not tk.acted:
                if kind == "interrupt" and aux == "price-wave":
                    tk.wave.add(vid)
                elif kind == "interrupt" and aux == "fault-storm":
                    tk.storm.add(vid)
                elif kind == "alloc-flush":
                    self.judge_storms(tk)
                    self.act(tk)
            elif kind == "interrupt" and aux == "fault-storm":
                self.counts["storm_victim_diff"] += 1   # outside a tick
            if kind.startswith("fleet-"):
                self.fleet(tk, rec)
            else:
                self.apply(rec)
        self.finish(group if group is not None else tk)


def check(run, config, dtype):
    rules = Rules(config, dtype)
    inp = base.inputs(run, config)
    rep = Replay(inp, rules)
    rep.run(inp["records"])
    n = base.workload_vms(config["stated"])
    if n is None:
        raise ValueError("the fleet check needs the workload's VM count")
    claims = {v for v in inp["vms"] if v >= n}
    bad = len(set(rep.launches) - claims)
    for v in claims:
        launch = rep.launches.get(v)
        vm = inp["vms"][v]
        if launch is None:
            bad += 1
            continue
        t, pool, kind = launch
        ok = ([float(x) for x in vm.demand] == rules.size
              and vm.submit_time == t and vm.pin == pool)
        if kind == "spot":
            ok &= (vm.spot and vm.bid == rules.bid
                   and math.isinf(vm.duration))
        else:
            ok &= (not vm.spot and math.isinf(vm.bid)
                   and vm.duration == rules.lease)
        bad += int(not ok)
    return {"counts": rep.counts, "attempted": rep.attempted + len(claims),
            "claims": claims, "input_errors": bad}
