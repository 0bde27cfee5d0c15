"""The control comes out not correct: the plain reference computed in
float8 (the step below the configurations' bfloat16), put in the
program's place, fails each cell's comparison at its own limits.  Run at
a size a CPU test holds; the same readings at the cells' own sizes on the
chip set the limits (``study.py``, PERF.md)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(CHIP)), "src"))

from bench import manifest as M  # noqa: E402
from test_chipbench_cells import CELLS, MAN, stand_in  # noqa: E402

STUDY = M.load_module(os.path.join(CHIP, "study.py"), "chipbench_study")
SEEDS = (2**31 + 11, 2**31 + 12, 2**31 + 13)


def cell(workload):
    c = M.cell(MAN, workload)
    for part, over in stand_in(workload).items():
        c[part] = {**c[part], **over}
    c["config"] = {**c["config"], **M.family(c["config"]).TINY_CONTROL}
    if c["traffic"]["kind"] != "train":
        c["cell"] = {**c["cell"], "limits": {**c["cell"]["limits"],
                                             "checked_tokens": 160,
                                             "max_requests": 16}}
    return c


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    c = cell(workload)
    for i, seed in enumerate(SEEDS):
        if c["traffic"]["kind"] == "train":
            ctl = STUDY.train_readings(c, seed, True)["control"]
            assert any(v["value"] > v["limit"] for v in ctl.values()), ctl
        else:
            out = STUDY.serve_readings(c, seed, 1.0, True, warm=i == 0)
            limit = out["checks"]["logit_gap_max"]["limit"]
            assert out["control"]["logit_gap_max"] > limit, out
