"""The comparison that decides ``correct``: each simulator the window drove,
judged by the plain reference (``bench/reference.py``) over its event log.

What the reference takes from a simulator is its input (host capacities and
pools, each VM's request and bid, each pool's shock sigma) and the event
records under check; the rules it applies come from the configuration file
(policy parameters, price model, the market's stated process, on-demand
rate, persistence and seeds).  The inputs are the program's own
preparation, so ``input_errors`` holds each of them to what the
configuration's ``stated`` block says of it.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench import reference as ref

LIMITS = Path(__file__).resolve().parent / "limits.json"

#: counts add up over the simulators of a window; other numbers take the
#: widest
_COUNTS = ("input_errors", "placement_errors", "wave_victim_diff")
#: reported beside the numbers, not compared
_READINGS = ("placement_gap",)


def limits() -> Dict[str, float]:
    return {k: float(v["limit"]) for k, v in
            json.loads(LIMITS.read_text())["limits"].items()}


def _within(x: float, span, eps: float = 1e-9) -> bool:
    return span[0] - eps <= x <= span[1] + eps


def input_errors(run, stated: dict) -> int:
    """The inputs of one simulator that depart from what the configuration
    states: hosts (count, pools, capacities, the mix of types), VMs (the
    profile table, or per kind: count, sizes, durations, submission times,
    minimum running time, bids, pool pins) and each pool's shock sigma."""
    sim, bad = run.sim, 0
    pool = sim.pool
    h = stated.get("hosts")
    if h:
        totals = [tuple(float(x) for x in row) for row in pool.total[:pool.n]]
        types = [tuple(float(x) for x in t) for t in h["types"]]
        bad += abs(run.n_hosts0 - int(h["count"]))
        bad += sum(1 for t in totals if t not in types)
        k = int(h["pools"])
        bad += int((pool.pool_of[:run.n_hosts0]
                    != np.arange(run.n_hosts0) % k).sum())
        if "type_counts" in h:
            got = [totals[:run.n_hosts0].count(t) for t in types]
            bad += sum(abs(a - int(b)) for a, b in zip(got, h["type_counts"]))
    v = stated.get("vms")
    k = int(h["pools"]) if h else 1
    od = float(stated.get("market", {}).get("on_demand_rate", 1.0))
    if v:
        if "table" in v:
            want = {}
            for cpu, ram, bw, st, n_spot, n_od in v["table"]:
                d = (float(cpu), float(ram), float(bw), float(st))
                want[(d, True)] = int(n_spot)
                want[(d, False)] = int(n_od)
            got = {}
            for vm in sim.vms.values():
                key = (tuple(float(x) for x in vm.demand), bool(vm.is_spot))
                got[key] = got.get(key, 0) + 1
            bad += sum(abs(got.get(x, 0) - want.get(x, 0))
                       for x in set(got) | set(want))
        for kind, spot in (("spot", True), ("on_demand", False)):
            rule = v.get(kind)
            if not rule:
                continue
            vms = [x for x in sim.vms.values() if bool(x.is_spot) == spot]
            if "count" in rule:
                bad += abs(len(vms) - int(rule["count"]))
            for x in vms:
                d = [float(y) for y in x.demand]
                ok = True
                if "cpu" in rule:
                    ok &= d[0] in [float(c) for c in rule["cpu"]]
                if "ram_per_cpu" in rule:
                    ok &= d[0] > 0 and _within(d[1] / d[0], rule["ram_per_cpu"])
                if "bw" in rule:
                    ok &= d[2] in [float(c) for c in rule["bw"]]
                if "storage" in rule:
                    ok &= d[3] in [float(c) for c in rule["storage"]]
                if rule.get("duration_choices"):
                    ok &= float(x.duration) in [float(c) for c in
                                                rule["duration_s"]]
                elif "duration_s" in rule:
                    ok &= _within(float(x.duration), rule["duration_s"])
                if "submit_s" in rule:
                    ok &= _within(float(x.submit_time), rule["submit_s"])
                if "min_running_time_s" in rule:
                    ok &= _within(float(x.min_running_time),
                                  rule["min_running_time_s"])
                b = rule.get("bid_of_od_rate")
                if b == "inf":
                    ok &= float(x.bid) == float("inf")
                elif b is not None:
                    ok &= _within(float(x.bid) / od, b)
                pin = rule.get("pin")
                if pin == "any":
                    ok &= int(x.pool) == -1
                elif pin == "id mod pools":
                    ok &= int(x.pool) == int(x.id) % k
                bad += int(not ok)
    m = stated.get("market")
    if m and sim.engine is not None:
        sig = np.array([pc.process_kwargs["shock_sigma"]
                        for pc in sim.engine.config.pools], dtype=np.float64)
        lo, hi = m["shock_sigma_range"]
        bad += int(((sig < lo) | (sig > hi)).sum())
        bad += int((np.diff(sig) < 0).sum())
    return bad


def inputs(run, config: dict) -> dict:
    """The reference's inputs for one simulator of the window."""
    sim = run.sim
    pool = sim.pool
    n = pool.n
    vms = {vid: ref.VmSpec(demand=np.array(v.demand, dtype=np.float64),
                           spot=v.is_spot, duration=float(v.duration),
                           bid=float(v.bid), pin=int(v.pool),
                           min_running_time=float(v.min_running_time),
                           submit_time=float(v.submit_time))
           for vid, v in sim.vms.items()}
    p = config["spec"]["policy"]["params"]
    policy = ref.PolicyRules(rc=float(p["rc"]), threshold=float(p["threshold"]),
                             alpha=float(p["alpha"]),
                             adjust_spot_only=bool(p["adjust_spot_only"]))
    if sim.config.warning_time != 0.0:
        raise ValueError("the reference judges runs without a warning time")
    market = billing = None
    if sim.engine is not None:
        m = config["stated"]["market"]
        if m["process"] != "auction" or m["correlation"] != 0.0:
            raise ValueError("the reference prices independent auction "
                             "pools only")
        k = int(config["stated"]["hosts"]["pools"])
        market = ref.MarketRules(
            od=np.full(k, float(m["on_demand_rate"])),
            sigma=np.array([pc.process_kwargs["shock_sigma"]
                            for pc in sim.engine.config.pools]),
            rho=np.full(k, float(m["shock_rho"])),
            seeds=[run.seed + i for i in range(k)])
        billing = ref.BillingRules(**config["billing"])
    dest: Dict[int, List[int]] = {}
    for mev in sim.metrics.migration_events:
        dest.setdefault(mev.vm_id, []).append(mev.dst_host)
    return dict(records=list(sim.events.records()), t_end=run.t_end,
                totals=pool.total[:n].copy(), host_pool=pool.pool_of[:n].copy(),
                n_initial=run.n_hosts0, vms=vms, policy=policy, market=market,
                billing=billing, spot_cost=run.spot_cost, destinations=dest)


def judge(runs: List, config: dict, dtype=np.float64) -> dict:
    """Numbers over every simulator of the window, the readings beside them,
    the placements judged and the answers that failed."""
    out: Dict[str, float] = {}
    attempted = 0
    for run in runs:
        got = ref.replay(**inputs(run, config), dtype=dtype, seed=run.seed)
        got["input_errors"] = input_errors(run, config.get("stated", {}))
        attempted += got.pop("placements")
        for k, v in got.items():
            if k in _COUNTS:
                out[k] = out.get(k, 0) + v
            else:
                out[k] = max(out.get(k, 0.0), v)
    readings = {k: out.pop(k) for k in _READINGS if k in out}
    failed = sum(int(out.get(k, 0)) for k in _COUNTS)
    return {"numbers": out, "readings": readings, "attempted": attempted,
            "failed": failed}


def verdict(numbers: Dict[str, float]) -> Dict[str, dict]:
    """Each number beside its limit; a missing limit is an error."""
    lim = limits()
    return {k: {"value": v, "limit": lim[k], "ok": bool(v <= lim[k])}
            for k, v in sorted(numbers.items())}
