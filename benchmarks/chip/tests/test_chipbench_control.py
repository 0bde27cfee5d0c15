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
# a larger stand-in than the cell test's, so that a run serves enough
# tokens for near-ties, where a lower precision flips the top token
WIDER = {"n_layers": 4, "d_model": 256, "n_heads": 8, "n_kv_heads": 2,
         "head_dim": 32, "d_ff": 512, "vocab": 1024,
         "quant": {"bits": 4, "group_size": 32, "rank": 8, "s": 2.0,
                   "scale_dtype": "bfloat16"},
         "init": {"embed_std": 0.02, "head_std": 0.1, "norm_jitter": 0.1,
                  "adapter_b_std": 0.01}}


def cell(workload):
    c = M.cell(MAN, workload)
    for part, over in stand_in(workload).items():
        c[part] = {**c[part], **over}
    c["config"] = {**c["config"], **WIDER}
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
