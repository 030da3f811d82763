"""tools/host_pick_bench.py: its lines at a tiny size, and the crossover it
reads off them."""
import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools.host_pick_bench import crossover, main  # noqa: E402


def test_lines_time_both_layouts_and_agree(capsys):
    assert main(["--m", "2", "40", "300", "--repeats", "3"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [r["m"] for r in lines[:-1]] == [2, 40, 300]
    for r in lines[:-1]:
        assert r["same_pick"] is True
        for layout in ("rows", "columns"):
            q = r[layout]
            assert 0 < q["q1"] <= q["median"] <= q["q3"]
    assert set(lines[-1]) == {"crossover", "PICK_NP_M_CUTOVER", "repeats"}


def _line(m, rows, columns):
    return {"m": m, "rows": {"median": rows}, "columns": {"median": columns}}


def test_crossover_is_where_columns_win_from_there_on():
    lines = [_line(40, 30, 40), _line(256, 60, 55), _line(512, 100, 101),
             _line(2048, 400, 200), _line(16384, 3000, 1500)]
    assert crossover(lines) == 2048
    assert crossover(lines[:3]) is None
    assert crossover([_line(40, 30, 20), _line(80, 40, 30)]) == 40
