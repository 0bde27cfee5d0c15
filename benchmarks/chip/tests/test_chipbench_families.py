"""Model families (``families/<family>.py``): the gqa family's required
work counted by hand, its weights and counts pinned to those the harness
gave before families were files of their own, and a second family's cell
run through the unchanged harness when only new files are added."""

import filecmp
import hashlib
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(CHIP)), "src"))

from bench import manifest as M, weights as W  # noqa: E402

MAN = M.load()
CONFIGS = {c["name"]: M.read_json(os.path.join(M.REPO, c["file"]))
           for c in MAN["configs"]}
G = M.family({"family": "gqa"})

# what the harness reads of a family (the manifest's docstring)
CONTRACT = ("PROGRAM_KEYS", "raw_weights", "fill_program", "adapters",
            "serve_pass", "train_step", "linear_serve_pass",
            "linear_train_step", "TINY", "TINY_CONTROL")


@pytest.mark.parametrize("family",
                         sorted({c["family"] for c in CONFIGS.values()}))
def test_family_has_what_the_harness_reads(family):
    fam = M.family({"family": family})
    assert [n for n in CONTRACT if not hasattr(fam, n)] == []


# ---------------------------------------------------------------------------
# gqa: required work counted by hand
# ---------------------------------------------------------------------------

# one layer's projections, counted by hand from the published widths
LAYER_WEIGHTS = {
    # wq, wo 4608x4608; wk, wv 4608x512; up, down 4608x18432
    "starcoder2-7b": 2 * 4608 * 4608 + 2 * 4608 * 512 + 2 * 4608 * 18432,
    # wq, wo 2560x2560; wk, wv 2560x640; gate, up, down 2560x6912
    "h2o-danube-1.8b": 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912,
}


@pytest.mark.parametrize("name", sorted(LAYER_WEIGHTS))
def test_one_layer_counted_by_hand(name):
    cfg = CONFIGS[name]
    n = LAYER_WEIGHTS[name]
    assert G.linear_params(cfg) == cfg["n_layers"] * n
    # INT4: half a byte a weight; a bf16 scale and zero per 32 input rows
    assert G.quantized_bytes(cfg) == cfg["n_layers"] * (n // 2 + n * 4 // 32)
    kv = cfg["n_layers"] * 2 * cfg["n_kv_heads"] * cfg["head_dim"] * 2
    assert G.kv_bytes_per_token(cfg) == kv
    assert G.head_bytes(cfg) == cfg["d_model"] * cfg["vocab"] * 2
    one = cfg["n_layers"] * 4 * cfg["n_heads"] * cfg["head_dim"]
    assert G.attention_flops(cfg, 0, 1) == one
    assert G.attention_flops(cfg, 9, 1) == 10 * one


def test_starcoder2_base_is_the_published_size():
    # the INT4 base with its bf16 embedding and head: 5.25 GB
    cfg = CONFIGS["starcoder2-7b"]
    total = G.quantized_bytes(cfg) + 2 * G.head_bytes(cfg)
    assert abs(total - 5.247e9) < 0.01e9


def test_serve_pass_and_train_step_arithmetic():
    cfg = CONFIGS["starcoder2-7b"]
    f, b = G.serve_pass(cfg, [(100, 1), (7, 0)])
    assert f == (2 * G.linear_params(cfg) + G.attention_flops(cfg, 100, 1)
                 + 2 * cfg["d_model"] * cfg["vocab"])
    assert b == (G.quantized_bytes(cfg) + G.head_bytes(cfg)
                 + 101 * G.kv_bytes_per_token(cfg) + cfg["d_model"] * 2)
    f2, _ = G.train_step(cfg, 2, 16)
    tokens = 32
    assert f2 == (tokens * (4 * G.linear_params(cfg)
                            + 6 * G.adapter_params(cfg)
                            + 4 * cfg["d_model"] * cfg["vocab"])
                  + 2 * 3 * G.attention_flops(cfg, 0, 16))
    # the window never binds danube below its 4096 positions
    d = CONFIGS["h2o-danube-1.8b"]
    assert G.attention_flops(d, 5000, 1) == G.attention_flops(d, 4095, 1)


def test_linear_work_by_hand():
    cfg = CONFIGS["starcoder2-7b"]
    d, v, L = 4608, 49152, 32
    per_layer = d * d + 2 * d * 512 + d * d + 2 * d * 18432
    assert G.linear_params(cfg) == L * per_layer
    rows = [(100, 1), (0, 256), (50, 0)]
    f, b = G.linear_serve_pass(cfg, rows)
    assert f == 2 * 257 * L * per_layer + 2 * 2 * d * v
    assert b == (G.quantized_bytes(cfg) + d * v * 2 + 257 * d * 2)
    f, b = G.linear_train_step(cfg, 2, 2048)
    ad = G.adapter_params(cfg)
    assert f == 4096 * (4 * L * per_layer + 6 * ad)
    assert b == G.quantized_bytes(cfg) + 2 * ad
    # the linear layer is part of each step's required work
    assert f < G.train_step(cfg, 2, 2048)[0]


# ---------------------------------------------------------------------------
# gqa: the same weights and counts as before families were files
# ---------------------------------------------------------------------------

SEED = 2**33 + 12345
ROWS = [(100, 1), (0, 256), (50, 0), (4000, 8), (5000, 1)]
# sha256 over every leaf's dtype, shape and bytes of make_raw and
# make_program at the family's TINY sizes and SEED, and the work counts
# of the published configurations on ROWS and 2 rows of 2048, all taken
# with bench/weights.py and bench/work.py as they were before
PINNED = {
    "starcoder2-7b": {
        "raw": "4a7c51da7bef8ee9ee04e0f965e188917dbc1e5342c28cb52af3e7e78ed7bc86",
        "program": "f768a05ed8ae946e878b0adead003dd57d866e40fea361b8a35aed1eee1f1f92",
        "serve_pass": (3738267942912, 5410351104),
        "train_step": (126675899645952, 4935712768),
        "linear_serve_pass": (3696960208896, 4796540928),
        "linear_train_step": (115539720536064, 4482727936),
    },
    "h2o-danube-1.8b": {
        "raw": "6b83498e474bea13fb584a35721765f5bf77959d04c78791aba382d7bce2cbe3",
        "program": "d9afdaf6885a93205f92c9c8d5b2667c8dd90cdb238329b56c02d650b314c7bc",
        "serve_pass": (904613969920, 1782671360),
        "train_step": (32638396006400, 1277992960),
        "linear_serve_pass": (887624826880, 1207224320),
        "linear_train_step": (28202332323840, 1114152960),
    },
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for x in jax.tree.leaves(tree):
        x = jax.device_get(x)
        h.update(str(x.dtype).encode())
        h.update(str(x.shape).encode())
        h.update(x.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PINNED))
def test_weights_are_bit_identical(name):
    from repro.models.lm import LM

    cfg = {**CONFIGS[name], **M.family(CONFIGS[name]).TINY}
    assert digest(W.make_raw(cfg, SEED)) == PINNED[name]["raw"]
    lm = LM(W.arch_config(cfg))
    assert digest(W.make_program(lm, cfg, SEED)) == PINNED[name]["program"]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_work_counts_are_pinned(name):
    cfg = CONFIGS[name]
    fam = M.family(cfg)
    pin = PINNED[name]
    assert fam.serve_pass(cfg, ROWS) == pin["serve_pass"]
    assert fam.linear_serve_pass(cfg, ROWS) == pin["linear_serve_pass"]
    assert fam.train_step(cfg, 2, 2048) == pin["train_step"]
    assert fam.linear_train_step(cfg, 2, 2048) == pin["linear_train_step"]


# ---------------------------------------------------------------------------
# a second family is new files only
# ---------------------------------------------------------------------------

STUB = ('''"""A second family: the gqa family's functions under another name."""
import os

from bench import manifest as M

_gqa = M.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "gqa.py"), "family_gqa_under_stub")
''' + "".join(f"{n} = _gqa.{n}\n" for n in CONTRACT))
LIKE = "sc2-7b.decode"   # the cell whose traffic and metrics the stub's takes


def _for(metrics, workload):
    return [{**m, "workloads": [workload]} if "workloads" in m else m
            for m in metrics if M._applies(m, LIKE)]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_a_new_family_is_new_files_only(trace, tmp_path, monkeypatch,
                                        capsys):
    from test_chipbench_cells import assert_complete_and_correct, run

    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("__pycache__"))
    like = M.cell(MAN, LIKE)
    conf = {**like["config"], "name": "stub-7b", "family": "stub"}
    (chip / "families" / "stub.py").write_text(STUB)
    (chip / "configs" / "stub-7b.json").write_text(json.dumps(conf))
    (chip / "cells" / "stub.decode.json").write_text(json.dumps(like["cell"]))
    man = {**MAN,
           "configs": [{"name": "stub-7b", "source": conf["source"],
                        "file": "benchmarks/chip/configs/stub-7b.json",
                        "reduced": [], "why": "the gqa family, renamed"}],
           "workloads": [{**like["workload"], "name": "stub.decode",
                          "config": "stub-7b"}],
           "end_to_end": _for(MAN["end_to_end"], "stub.decode"),
           "per_layer": _for(MAN["per_layer"], "stub.decode")}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    monkeypatch.setattr(M, "REPO", str(tmp_path))
    monkeypatch.setattr(M, "CHIP_DIR", str(chip))
    monkeypatch.setattr(M, "MANIFEST", str(tmp_path / "BENCHMARK.json"))

    man = M.load()
    fam = M.family(M.cell(man, "stub.decode")["config"])
    assert fam.__file__ == str(chip / "families" / "stub.py")
    last = run("stub.decode", trace, capsys, man)
    assert_complete_and_correct(last, "stub.decode", trace, man)
    same = filecmp.dircmp(str(chip / "bench"), os.path.join(CHIP, "bench"))
    assert (same.left_only, same.right_only, same.diff_files) == ([], [], [])
