"""tools/pick_roundtrip.py rehearsed on the CPU at a tiny size: a line per
way and run length, and a run's first step picking as the resident
program on the same storage."""
import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools.pick_roundtrip import main  # noqa: E402


def test_run_way_lines_and_first_step_pick(capsys):
    assert main(["--rehearse", "--rows", "64", "--way", "resident", "run",
                 "--steps", "1", "4"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    timed = [r for r in lines if "way" in r]
    assert [(r["way"], r.get("steps")) for r in timed] == [
        ("resident", None), ("run", 1), ("run", 4)]
    for r in timed:
        assert r["rehearsal"] is True and r["h2d_bytes"] > 0
        assert r["call_us"]["median"] > 0
    assert timed[2]["round_trip_us_per_step"] > 0
    # a run of 4 sends 4 mask bytes a row where a run of 1 sends one
    assert timed[2]["h2d_bytes"] - timed[1]["h2d_bytes"] >= 3 * 64
    check = [r for r in lines if "resident_equals_reference" in r]
    assert check and check[0]["resident_equals_reference"]
    picks = lines[-1]["first_step_picks"]
    assert set(picks) == {"resident", "1", "4"}
    assert len(set(picks.values())) == 1
