"""The control: the reference computed in fp8 and put in the program's
place has to come out as not correct, where the served path does not.
At a size the CPU holds, over three seeds, through the same loop that
sets the limits on the chip (bench/calibrate.py)."""
from bench import calibrate
from bench.tests.conftest import TINY_LIMIT


def test_fp8_control_fails_where_the_served_path_passes(tiny_cell):
    cell = tiny_cell("llava-sharegpt4o-burst16")
    got = list(calibrate.readings_over_seeds(cell, [11, 12, 13]))
    for g in got:
        assert g["failed"] == 0 and g["tokens_compared"] > 0
        assert g["max_logit_gap"] <= TINY_LIMIT, g
        assert g["control_max_logit_gap"] > TINY_LIMIT, g
        # the benchmark's own rule, against the limit the cell states
        assert g["correct"] and not g["control_correct"], g
    lower = max(g["max_logit_gap"] for g in got)
    upper = min(g["control_max_logit_gap"] for g in got)
    assert upper >= 3 * lower
