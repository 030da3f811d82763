"""The least bytes of the device pick's program, which ``scorer_roofline``
divides by its device time: the mirror read, the packed input, the scores
and the tolerance written."""
import pytest

from bench import roofline


@pytest.mark.parametrize("rows,packed,total", [(16384, 25604, 615432),
                                               (256, 836, 10056)])
def test_scorer_bytes_count_the_resident_program(rows, packed, total):
    assert roofline.packed_input_bytes(rows) == packed
    assert roofline.scorer_bytes(rows) == total
    assert roofline.scorer_least_s(rows, "TPU v5 lite") == \
        pytest.approx(total / 819e9)


def test_packed_input_matches_the_programs_layout():
    from repro.core.hlem import _pick_layout

    for rows in (1, 5, 256, 1000, 16384, 65536):
        assert roofline.packed_input_bytes(rows) == _pick_layout(rows, 4)[2]
