"""Cells of the benchmark at a size a CPU test run holds: the trace cut to
fewer machines (and the stated host count with it), everything else as
configured."""
import copy
import json

from bench import run

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
#: machines of the trace configuration in the tests
MACHINES = 300
#: every cell of the benchmark
CELLS = [c["name"] for c in json.loads(
    (run.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def small_cell(name: str) -> dict:
    c = copy.deepcopy(run.load_cell(name))
    params = c["config"]["spec"]["scenario"]["workload_params"]
    if "n_machines" in params:
        params["n_machines"] = MACHINES
        c["config"]["stated"]["hosts"]["count"] = MACHINES
    return c
