"""The check that decides ``correct`` fails where it must: the control (the
configuration's one broken guarantee in the program's place) and each fault
planted under the timed path come out not correct, while the sound run
comes out correct.  At this tiny size the limits are set from CPU readings
of this size (sound runs on three seeds, the control, each fault), since
the cells' own limits were set at scale 20."""

import pytest

from bench_faults import BUILD_FAULTS, SERVE_FAULTS
from bench_paths import run_tiny, tiny_cell

# (sound runs' largest reading, control's reading) at scale 11 on the CPU,
# four seeds: serve mean 0.0059 / 0.044, max 0.026 / 0.11; build mean
# 0.037 / 0.40, max 0.11 / 2.29
TINY_LIMITS = {
    "g500s20-r100.serve-uniform": {"sq_err_mean": 0.02},
    "g500s20-r100.build": {"sq_err_mean": 0.12, "sq_err_max": 0.5},
}
CASES = [(name, fault) for name in TINY_LIMITS
         for fault in ("sound", "control", "state_unchanged", "half_batch",
                       "answer_altered")]


def limited_cell(name):
    cell = tiny_cell(name)
    kind = cell["traffic"]["kind"]
    cell["config"]["limits"] = {kind: TINY_LIMITS[name]}
    return cell


@pytest.mark.parametrize("name,fault", CASES)
def test_check_separates(name, fault):
    cell = limited_cell(name)
    if fault == "sound":
        result = run_tiny(cell)
        assert result["correct"], result["checks"]
        return
    if fault == "control":
        result = run_tiny(cell, control=True)
    else:
        faults = SERVE_FAULTS if cell["traffic"]["kind"] == "serve" \
            else BUILD_FAULTS
        with faults[fault]():
            result = run_tiny(cell)
    assert not result["correct"], result["checks"]
