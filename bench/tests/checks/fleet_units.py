"""A configuration's own check, written for the tests: the VMs a spot fleet
launches while the run runs.  It claims the VM of every ``fleet-launch``
record and holds it to ``stated["fleet"]``: the unit's size, a spot
launch's bid (a share of the on-demand rate) and its pin to the launch
pool, an on-demand launch's lease.  ``fleet_launch_diff`` counts the
fleet's VMs with no launch record, and launch records whose VM the run
does not hold."""
import math


def check(run, config, dtype):
    sim = run.sim
    rule = config["stated"]["fleet"]
    od = float(config["stated"]["market"]["on_demand_rate"])
    size = [float(x) for x in rule["size"]]
    bid = float(rule["spot_bid_of_od_rate"]) * od
    launches = [r for r in sim.events.records() if r[1] == "fleet-launch"]
    claims, bad, missing = set(), 0, 0
    for t, _, vid, pool, _, a, _, aux in launches:
        vm = sim.vms.get(vid)
        if vm is None:
            missing += 1
            continue
        claims.add(vid)
        ok = ([float(x) for x in vm.demand] == size
              and float(vm.submit_time) == t and int(vm.pool) == pool)
        if aux == "spot":
            ok &= (bool(vm.is_spot) and float(vm.bid) == bid
                   and float(a) == bid)
        else:
            ok &= (not vm.is_spot and math.isinf(float(vm.bid))
                   and float(vm.duration) == float(rule["od_lease_s"]))
        bad += int(not ok)
    made = set(sim.metrics.fleet_spot_ids) | set(sim.metrics.fleet_od_ids)
    return {"counts": {"fleet_launch_diff": missing + len(made - claims)},
            "attempted": len(launches), "claims": claims,
            "input_errors": bad}
