"""The control: the reference computed in float32 in the program's place.
On each cell, at a CPU-sized cut, the program's own readings keep within
every limit, and the control's fail at least one."""
import numpy as np
import pytest

from bench import check, control
from bench.tests._small import CELLS, small_cell


@pytest.mark.parametrize("cell", CELLS)
def test_program_passes_and_control_fails(cell):
    got = control.readings(small_cell(cell), seed=2_147_483_659,
                           seconds=1.5)
    assert got["attempted"] > 0 and got["device_picks"] > 0
    numbers = check.limits()
    program = check.verdict({k: v for k, v in got["program"].items()
                             if k in numbers})
    assert all(v["ok"] for v in program.values()), program
    ctl = check.verdict({k: v for k, v in got["control"].items()
                         if k in numbers})
    assert not all(v["ok"] for v in ctl.values()), ctl


def test_control_accounts_in_its_own_precision():
    from bench import reference as ref

    r = ref.Replay(np.array([[64.0, 98304.0, 20000.0, 8e5]]),
                   np.zeros(1, np.int64), {}, None, dtype=np.float32)
    assert r.used_c.dtype == np.float32 and r.used.dtype == np.float64
