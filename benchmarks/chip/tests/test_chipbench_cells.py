"""Every cell of BENCHMARK.json, run on the CPU at a tiny stand-in of its
configuration, untraced and traced: the last line is one JSON object with
the result's keys, ``metrics`` holds every metric the cell declares, and
the run is correct.  With the timed path broken underneath (a served
token altered where it is produced; a train step that returns its state
unchanged; a train step that drops half of its batch) the same run comes
out not correct."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(CHIP)), "src"))

from bench import faults, manifest as M  # noqa: E402
from bench.cell import run_cell  # noqa: E402

MAN = M.load()
CELLS = [w["name"] for w in MAN["workloads"]]
SEED = 2**33 + 12345  # past 32 bits, as the benchmark's seeds may be

TINY = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 32, "d_ff": 256, "vocab": 512,
        "quant": {"bits": 4, "group_size": 32, "rank": 8, "s": 2.0,
                  "scale_dtype": "bfloat16"},
        "init": {"embed_std": 0.02, "head_std": 0.1, "norm_jitter": 0.1,
                 "adapter_b_std": 0.01}}
SERVE = {"clients": 4, "rate": 20.0, "lead_s": 0.3, "drain_s": 5,
         "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
         "output": {"median": 10, "sigma": 0.6, "min": 2, "max": 24}}
ENGINE = {"n_slots": 4, "max_len": 64, "prefill_chunk": 16,
          "decode_burst": 4, "queue_cap": 256}
TRAIN = {"batch": 2, "seq": 64,
         "doc": {"median": 20, "sigma": 1.0, "min": 4, "max": 100}}


def stand_in(workload: str) -> dict:
    c = M.cell(MAN, workload)
    if c["traffic"]["kind"] == "train":
        return {"config": TINY, "traffic": TRAIN}
    return {"config": TINY, "traffic": SERVE,
            "cell": {"engine": ENGINE,
                     "limits": {**c["cell"]["limits"], "checked_tokens": 16,
                                "max_requests": 4}}}


def run(workload: str, trace: bool, capsys) -> dict:
    run_py = M.load_module(os.path.join(CHIP, "run.py"), "chipbench_run")
    result = run_cell(workload, SEED, 1.0, trace, 0.0, allow_cpu=True,
                      stand_in=stand_in(workload), peak_kind="TPU v5 lite")
    capsys.readouterr()
    run_py.emit(result)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert err.strip().splitlines()[-1].startswith("check ")
    return last


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_prints_every_declared_metric(workload, trace, capsys):
    last = run(workload, trace, capsys)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert list(last)[-1] == "checks"
    c = M.cell(MAN, workload)
    declared = [m["name"] for m in c["end_to_end"]]
    if trace:
        declared += [m["name"] for m in c["per_layer"]]
        assert 0 < last["device"]["busy_s"] <= last["device"]["window_s"]
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert sorted(last["metrics"]) == sorted(declared)
    units = {m["name"]: m["unit"] for m in MAN["end_to_end"] + MAN["per_layer"]}
    for name, m in last["metrics"].items():
        assert m["unit"] == units[name]
        assert isinstance(m["value"], float) and m["value"] > 0, name
        if units[name] == "%":
            assert m["value"] <= 100, name
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["correct"] is True, last["checks"]


SERVING = [w for w in CELLS if M.cell(MAN, w)["traffic"]["kind"] != "train"]
TRAINING = [w for w in CELLS if M.cell(MAN, w)["traffic"]["kind"] == "train"]


@pytest.mark.parametrize("workload", SERVING)
def test_altered_token_is_not_correct(workload, capsys, monkeypatch):
    from repro.serving.scheduler import Scheduler

    for name, fn in faults.altered_commits(Scheduler, TINY["vocab"]).items():
        monkeypatch.setattr(Scheduler, name, fn)
    last = run(workload, False, capsys)
    assert last["correct"] is False
    gap = last["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("kind", ["frozen", "half_batch"])
@pytest.mark.parametrize("workload", TRAINING)
def test_broken_train_step_is_not_correct(workload, kind, capsys,
                                          monkeypatch):
    from repro.launch import steps

    monkeypatch.setattr(steps, "make_train_fn", faults.broken_train_fn(
        steps.make_train_fn, kind))
    last = run(workload, False, capsys)
    assert last["correct"] is False
    assert any(v["value"] > v["limit"] for v in last["checks"].values())


def test_no_tpu_means_no_result(capsys):
    run_py = M.load_module(os.path.join(CHIP, "run.py"), "chipbench_run")
    rc = run_py.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                      "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert "no TPU" in err
