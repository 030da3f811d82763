"""The benchmark's data layer: every name resolves to its files, names and
units keep to their characters, and each configuration builds."""
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run
from bench.tests._small import small_cell

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = run.load_cell(cell)
    entry = {k["name"]: k for k in BENCH["configs"]}[c["cell"]["config"]]
    assert c["config"]["name"] == entry["name"]
    assert sorted(c["config"]["reduced"]) == sorted(entry["reduced"])
    assert c["traffic"]["mode"] in ("continuous", "sweep")
    assert c["end_to_end"] and c["per_layer"]


def test_names_and_units_keep_to_their_characters():
    names = [c["name"] for c in BENCH["configs"]] + CELLS
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["traffic"] for c in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names[:len(BENCH["configs"]) + len(CELLS)])) == \
        len(BENCH["configs"]) + len(CELLS)
    units = [m["unit"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(UNIT.match(u) for u in units), units


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader_and_its_cells_report_what_it_moves(
        metric):
    m = {x["name"]: x for x in BENCH["per_layer"]}[metric]
    e2e = {x["name"]: x for x in BENCH["end_to_end"]}
    assert m["moves"] in e2e
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in e2e[m["moves"]].get("workloads", CELLS)
    assert callable(run.reader(metric))


@pytest.mark.parametrize("cell", CELLS)
def test_configuration_builds_through_the_api(cell):
    from repro.api import RunSpec, build

    c = small_cell(cell)
    sim = build(RunSpec.from_dict(c["config"]["spec"]), 7)
    assert sim.pool.n > 0 and len(sim.vms) > 0
    assert sim.policy.backend == "jax"


def test_refuses_a_machine_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_refuses_a_checkout_without_the_program(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert "{" not in out.stdout
