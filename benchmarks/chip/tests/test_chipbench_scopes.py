"""The scope reduction (bench/scopes.py) and the linear layer's work
(bench/linear_work.py): device seconds per named scope read through the
compiled programs' metadata, idle gaps named by the innermost span, on a
hand-made trace with known answers and on the recorded v5e slice, where
it agrees with trace.py; and layers.py end to end on the CPU stand-ins."""

import gzip
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(CHIP)), "src"))

from bench import linear_work as LW, manifest as M, scopes as SC  # noqa: E402
from bench import spans as S, trace as T, work  # noqa: E402
from repro.runtime import tracing as R  # noqa: E402

MS = 1_000_000  # ns

HLO = """HloModule jit_step, entry_computation_layout={()}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/attention/mul"}
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/linear/dot_general" source_file="x.py"}
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(attention))/mul"}
  %copy.3 = f32[4]{0} copy(%fusion.2), metadata={op_name="jit(step)/kv_insert/dynamic_update_slice"}
  %add.4 = f32[4]{0} add(%copy.3, %p), metadata={op_name="jit(step)/linear_like/add"}
  ROOT %while.5 = f32[4]{0} copy(%add.4)
}
"""
MOD = "jit_step(123)"

HAND = {
    "host": [
        [S.WINDOW, 0, 100 * MS],
        [S.STEP, 10 * MS, 60 * MS],
        [R.FRONTEND_STEP, 11 * MS, 58 * MS],
        [R.PLAN, 12 * MS, 8 * MS],           # 12..20
        [R.DISPATCH, 20 * MS, 1 * MS],       # 20..21
        [R.SYNC, 21 * MS, 40 * MS],          # 21..61
        [R.COMMIT, 61 * MS, 5 * MS],         # 61..66
        [SC.COMPILE_LABEL, 14 * MS, 4 * MS],  # a compile in the plan
        [SC.COMPILE_LABEL, 15 * MS, 2 * MS],  # nested in it: one compile
        [S.COMMIT, 70 * MS, 30 * MS],
    ],
    "devices": {"/device:TPU:0": [
        ["fusion.1", 0, 10 * MS, MOD],           # 0..10 linear
        ["fusion.2", 21 * MS, 20 * MS, MOD],     # 21..41 attention
        ["copy.3", 41 * MS, 9 * MS, MOD],        # 41..50 kv_insert
        ["add.4", 50 * MS, 5 * MS, MOD],         # 50..55 no scope
        ["while.5", 21 * MS, 40 * MS, MOD],      # 21..61 the loop
        ["dot.9", 61 * MS, 1 * MS, "jit_other(7)"],  # in no program
    ]},
}


def test_op_scopes_read_the_metadata():
    assert SC.op_scopes(HLO) == {"fusion.1": "linear", "fusion.2": "attention",
                                 "copy.3": "kv_insert", "mul": "attention"}
    assert {"fusion.1", "add.4", "while.5", "p"} <= SC.instructions(HLO)


def test_hand_made_trace():
    r = SC.reduce(HAND, SC.Programs({"jit_step": [HLO]}))
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.051)   # 0-10, 21-62
    sc = r["device_scopes"]
    assert sc == pytest.approx({"linear": 0.010, "attention": 0.020,
                                "kv_insert": 0.009, "optimizer": 0.0,
                                "other": 0.012}, abs=1e-12)
    assert sum(sc.values()) == pytest.approx(r["busy_s"])
    assert r["unmapped_s"] == pytest.approx(0.001)
    # 62-100: the bench commit span 70-100 covers most; 10-21: the bench
    # step (10-70) and frontend.step (11-69) cover 11 and 10 ms
    assert r["idle_gaps"] == [["host_commit", pytest.approx(0.038)],
                              ["frontend_step", pytest.approx(0.011)]]
    idle = r["idle_by_span"]
    assert idle == pytest.approx({
        "frontend_step": 0.001 + 0.001,      # 10-11, 69-70
        "frontend.step": 0.001 + 0.003,      # 11-12, 66-69
        "engine.plan": 0.004,                # 12-14, 18-20
        "jax.compile": 0.004,                # 14-18
        "engine.dispatch": 0.001,            # 20-21
        "engine.commit": 0.004,              # 62-66
        "host_commit": 0.030}, abs=1e-12)    # 70-100
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["compiles"] == 1


def test_a_gap_inside_one_span_takes_the_innermost_name():
    host = [[S.STEP, 0, 50 * MS], [R.FRONTEND_STEP, 1 * MS, 48 * MS],
            [R.PLAN, 5 * MS, 20 * MS]]
    assert SC.label(10 * MS, 20 * MS, host) == "engine.plan"
    assert SC.label(10 * MS, 40 * MS, host) == "frontend.step"
    assert SC.label(60 * MS, 70 * MS, host) == "no host span"


def test_recorded_v5e_trace_agrees_with_trace_py():
    """On the recorded slice (bench spans only, no module lines) the
    scope reduction's busy time and gaps are trace.py's, and all busy
    time is ``other``."""
    with gzip.open(os.path.join(HERE, "fixtures", "v5e_decode_events.json.gz"),
                   "rt") as f:
        ev = json.load(f)
    old = T.reduce(ev)
    ev4 = {"host": ev["host"],
           "devices": {k: [op + [""] for op in v]
                       for k, v in ev["devices"].items()}}
    r = SC.reduce(ev4, SC.Programs({}))
    assert r["busy_s"] == pytest.approx(old["busy_s"])
    assert r["window_s"] == pytest.approx(old["window_s"])
    assert r["idle_gaps"] == [[n, pytest.approx(s)] for n, s in
                              old["idle_gaps"]]
    assert r["device_scopes"]["other"] == pytest.approx(old["busy_s"])
    # idle by span leaves out the gaps under a microsecond, as the gap
    # list does
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        old["window_s"] - old["busy_s"], rel=1e-4)


def test_linear_work_by_hand():
    cfg = M.read_json(os.path.join(CHIP, "configs", "starcoder2-7b.json"))
    d, v, L = 4608, 49152, 32
    per_layer = d * d + 2 * d * 512 + d * d + 2 * d * 18432
    assert work.linear_params(cfg) == L * per_layer
    rows = [(100, 1), (0, 256), (50, 0)]
    f, b = LW.serve_pass(cfg, rows)
    assert f == 2 * 257 * L * per_layer + 2 * 2 * d * v
    assert b == (work.quantized_bytes(cfg) + d * v * 2 + 257 * d * 2)
    f, b = LW.train_step(cfg, 2, 2048)
    ad = work.adapter_params(cfg)
    assert f == 4096 * (4 * L * per_layer + 6 * ad)
    assert b == work.quantized_bytes(cfg) + 2 * ad
    # the linear layer is part of each step's required work
    assert f < work.train_step(cfg, 2, 2048)[0]


@pytest.mark.parametrize("workload",
                         [w["name"] for w in M.load()["workloads"]])
def test_layers_reads_every_cell_on_the_cpu(workload):
    """layers.py on the tiny stand-in of each cell: the program's counters
    and scopes are there and add up."""
    from test_chipbench_cells import SEED, stand_in

    L = M.load_module(os.path.join(CHIP, "layers.py"), "chipbench_layers")
    r = L.readings(workload, SEED, 1.0, allow_cpu=True,
                   stand_in=stand_in(workload), peak_kind="TPU v5 lite")
    sc = r["device_scopes"]
    assert set(sc) == set(R.SCOPES) | {SC.OTHER}
    assert sum(sc.values()) == pytest.approx(r["busy_s"])
    assert sc["linear"] > 0 and sc["attention"] > 0
    assert r["unmapped_s"] == pytest.approx(0, abs=1e-3 * r["busy_s"])
    assert 0 < r["linear_roofline"] < 100
    assert r["compiles"] == 0 and r["trace_compiles"] == 0
    if "host_ms" in r:
        assert r["host_ms"] > 0 and r["sync_ms"] > 0
        assert r["prefill_ms"] > 0 and r["n_prefill"] > 0
        assert sc["kv_insert"] > 0
        assert "engine.sync" in r["idle_by_span"]
    else:
        assert sc["optimizer"] > 0
