"""The comparison that decides ``correct``: each simulator the window drove,
judged by the plain reference (``bench/reference.py``) over its event log.

What the reference takes from a simulator is its input (host capacities and
pools, each VM's request and bid, each pool's shock sigma) and the event
records under check; the rules it applies come from the configuration file
(policy parameters, price model, the market's stated process, on-demand
rate, persistence and seeds).  The inputs are the program's own
preparation, so ``input_errors`` holds each of them to what the
configuration's ``stated`` block says of it.

A configuration may name checks of its own (``"checks": [<name>, ...]``),
each a file ``bench/checks/<name>.py`` with the limits of its numbers in
``bench/checks/<name>.limits.json`` (the schema of ``limits.json``).  A
check judges decisions the base reference does not, and holds the VMs the
program created while it ran, which it claims, to its own part of
``stated``.  It adds to the result and weakens nothing: the base reference
runs as without it, every VM no check claims keeps the base input rules,
a claim on a VM of the generated workload, and a number that the base
returns or ``limits.json`` lists, judge the run wrong.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import reference as ref

LIMITS = Path(__file__).resolve().parent / "limits.json"
#: a configuration's own checks, ``<name>.py`` and ``<name>.limits.json``
CHECKS = Path(__file__).resolve().parent / "checks"
CHECK_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")

#: counts add up over the simulators of a window; other numbers take the
#: widest
_COUNTS = ("input_errors", "placement_errors", "wave_victim_diff")
#: reported beside the numbers, not compared
_READINGS = ("placement_gap",)
#: what the base reference returns besides the numbers under a limit
_BASE = _COUNTS + _READINGS + ("placements",)


def _limits_file(path: Path) -> Dict[str, float]:
    return {k: float(v["limit"]) for k, v in
            json.loads(path.read_text())["limits"].items()}


def check_names(config: Optional[dict]) -> List[str]:
    """The configuration's own checks, by name."""
    names = list((config or {}).get("checks", []))
    for n in names:
        if not isinstance(n, str) or not CHECK_NAME.fullmatch(n):
            raise ValueError(f"bad check name {n!r}")
    if len(set(names)) != len(names):
        raise ValueError(f"a check named twice in {names}")
    return names


def limits(config: Optional[dict] = None) -> Dict[str, float]:
    """``limits.json``, and the limits of the configuration's checks; a
    check may not give a limit of its own to a number that has one."""
    out = _limits_file(LIMITS)
    for name in check_names(config):
        for k, v in _limits_file(CHECKS / f"{name}.limits.json").items():
            if k in out or k in _BASE:
                raise ValueError(f"check {name!r} gives {k!r} a limit of "
                                 f"its own")
            out[k] = v
    return out


def load_check(name: str):
    """The function ``check(run, config, dtype)`` of
    ``bench/checks/<name>.py``; it returns ``counts`` (added over runs),
    ``widest`` (the widest value over runs), ``attempted`` (decisions
    judged), ``claims`` (ids of VMs the program created while it ran) and
    ``input_errors`` (the claimed VMs against the check's part of
    ``stated``)."""
    path = CHECKS / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_check_" + re.sub(r"[.-]", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.check


def workload_vms(stated: dict) -> Optional[int]:
    """How many VMs the configuration's generated workload holds, as the
    profile table of its ``stated`` block gives them, or None where it has
    none.  The generators number them from 0, and a VM the program makes
    later takes a free id above them."""
    table = (stated.get("vms") or {}).get("table")
    if table is None:
        return None
    return sum(int(row[4]) + int(row[5]) for row in table)


def _within(x: float, span, eps: float = 1e-9) -> bool:
    return span[0] - eps <= x <= span[1] + eps


def input_errors(run, stated: dict, claimed=frozenset()) -> int:
    """The inputs of one simulator that depart from what the configuration
    states: hosts (count, pools, capacities, the mix of types), VMs other
    than those in ``claimed`` (the profile table, or per kind: count,
    sizes, durations, submission times, minimum running time, bids, pool
    pins) and each pool's shock sigma."""
    sim, bad = run.sim, 0
    own = [x for x in sim.vms.values() if x.id not in claimed]
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
            for vm in own:
                key = (tuple(float(x) for x in vm.demand), bool(vm.is_spot))
                got[key] = got.get(key, 0) + 1
            bad += sum(abs(got.get(x, 0) - want.get(x, 0))
                       for x in set(got) | set(want))
        for kind, spot in (("spot", True), ("on_demand", False)):
            rule = v.get(kind)
            if not rule:
                continue
            vms = [x for x in own if bool(x.is_spot) == spot]
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


def _claims(run, stated: dict, name: str, ids, taken: set) -> set:
    """The VMs a check claims, each one the program made while it ran: not
    of the generated workload, and claimed by no other check."""
    ids = {int(i) for i in ids}
    if not ids:
        return ids
    n = workload_vms(stated)
    if n is None:
        raise ValueError(f"check {name!r} claims VMs, but the configuration "
                         f"states no table of its generated VMs")
    bad = sorted(i for i in ids if i < n or i not in run.sim.vms)
    if bad:
        raise ValueError(f"check {name!r} claims VMs the program did not "
                         f"make while it ran: {bad[:10]}")
    if ids & taken:
        raise ValueError(f"check {name!r} claims VMs another check claims")
    return ids


def judge(runs: List, config: dict, dtype=np.float64) -> dict:
    """Numbers over every simulator of the window, the readings beside them,
    the decisions judged and the answers that failed; the configuration's
    own checks (``config["checks"]``) add theirs."""
    out: Dict[str, float] = {}
    attempted = 0
    stated = config.get("stated", {})
    checks = [(n, load_check(n)) for n in check_names(config)]
    reserved = set(_limits_file(LIMITS)) | set(_BASE)
    #: each number a check returns -> (check, "counts" or "widest")
    owner: Dict[str, Tuple[str, str]] = {}
    for run in runs:
        got = ref.replay(**inputs(run, config), dtype=dtype, seed=run.seed)
        attempted += got.pop("placements")
        claimed: set = set()
        extra_inputs = 0
        for name, fn in checks:
            res = fn(run, config, dtype)
            claimed |= _claims(run, stated, name, res.get("claims", ()),
                               claimed)
            extra_inputs += int(res.get("input_errors", 0))
            attempted += int(res.get("attempted", 0))
            for kind in ("counts", "widest"):
                for k, v in res.get(kind, {}).items():
                    if k in reserved or k in got or \
                            owner.get(k, (name, kind)) != (name, kind):
                        raise ValueError(f"check {name!r} returns {k!r}, a "
                                         f"number it does not own")
                    owner[k] = (name, kind)
                    if kind == "counts":
                        out[k] = out.get(k, 0) + int(v)
                    else:
                        out[k] = max(out.get(k, 0.0), float(v))
        got["input_errors"] = (input_errors(run, stated, claimed)
                               + extra_inputs)
        for k, v in got.items():
            if k in _COUNTS:
                out[k] = out.get(k, 0) + v
            else:
                out[k] = max(out.get(k, 0.0), v)
    readings = {k: out.pop(k) for k in _READINGS if k in out}
    failed = sum(int(out.get(k, 0)) for k in _COUNTS)
    failed += sum(int(out[k]) for k, (_, kind) in owner.items()
                  if kind == "counts")
    return {"numbers": out, "readings": readings, "attempted": attempted,
            "failed": failed}


def verdict(numbers: Dict[str, float],
            config: Optional[dict] = None) -> Dict[str, dict]:
    """Each number beside its limit, from ``limits.json`` and the
    configuration's checks; a missing limit is an error."""
    lim = limits(config)
    return {k: {"value": v, "limit": lim[k], "ok": bool(v <= lim[k])}
            for k, v in sorted(numbers.items())}
