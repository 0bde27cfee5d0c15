"""The benchmark's yardstick on its own: peaks, the seeded traffic, the
manifest loader and the per-layer readers (the required work is each
family's: test_chipbench_families.py)."""

import copy
import math
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

from bench import manifest as M  # noqa: E402
from bench import readers, traffic as T, work  # noqa: E402

MAN = M.load()


def test_peaks_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert work.least_seconds(197e12, 1, p) == pytest.approx(1.0)
    assert work.least_seconds(1, 819e9, p) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


MIX = {"prompt": {"median": 256, "sigma": 0.6, "min": 64, "max": 1024},
       "output": {"median": 384, "sigma": 0.6, "min": 128, "max": 1024}}


def test_same_seed_same_trace_other_seed_other_order():
    big = 2**40 + 3
    a, b, c = (T.Requests(MIX, 1000, s) for s in (big, big, big + 1))
    for i in (0, 5, 300):
        pa, oa = a(i)
        pb, ob = b(i)
        assert oa == ob and np.array_equal(pa, pb)
    la = [len(a(i)[0]) for i in range(T.POOL)]
    lc = [len(c(i)[0]) for i in range(T.POOL)]
    assert la != lc
    # every seed draws the same lengths, in another order
    assert sorted(la) == sorted(lc)
    assert min(la) >= 64 and max(la) <= 1024
    assert sorted(la)[T.POOL // 2] == pytest.approx(256, rel=0.02)


def test_arrivals_keep_the_rate():
    a = T.stratified_poisson_arrivals(T.POOL, 4.0, 7)
    b = T.stratified_poisson_arrivals(T.POOL, 4.0, 8)
    assert a[-1] == pytest.approx(b[-1])           # same gaps
    assert not np.allclose(a, b)                   # other order
    assert a[-1] / T.POOL == pytest.approx(0.25, rel=0.05)
    assert np.all(np.diff(a) > 0)
    assert T.replay_due([0.1, 0.2, 0.5], 10.0, 10.3, 0) == 2


def test_packed_documents():
    mix = {"batch": 2, "seq": 64,
           "doc": {"median": 20, "sigma": 1.0, "min": 4, "max": 100}}
    d1, d2 = T.Documents(mix, 512, 5), T.Documents(mix, 512, 5)
    t1, l1 = d1.next_batch()
    t2, l2 = d2.next_batch()
    assert t1.shape == (2, 64) and np.array_equal(t1, t2)
    assert np.array_equal(t1[:, 1:], l1[:, :-1])
    assert (t1 == T.SEPARATOR).any()
    t3, _ = d1.next_batch()
    assert not np.array_equal(t1, t3)


def test_nearest_rank_counts_missing_answers():
    assert T.nearest_rank([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0.9) == 9
    assert T.nearest_rank([1] * 9 + [math.inf], 0.95) == math.inf


def test_the_manifest_and_its_files():
    names = {m["name"] for m in MAN["per_layer"]}
    for n in names:
        assert os.path.exists(os.path.join(CHIP, "metrics", n + ".py"))
    for w in MAN["workloads"]:
        c = M.cell(MAN, w["name"])
        reports = {m["name"] for m in c["end_to_end"]}
        assert "setup_s" in reports and len(reports) >= 2
        assert c["per_layer"]
        for m in c["per_layer"]:
            assert m["moves"] in reports
        M.reference(c["config"])
        M.family(c["config"])


def _bad(edit):
    man = copy.deepcopy(MAN)
    edit(man)
    with pytest.raises(M.ManifestError):
        M.validate(man)


def test_manifest_refuses_what_the_format_forbids():
    _bad(lambda m: m["per_layer"][0].update(name="step ms"))
    _bad(lambda m: m["per_layer"][0].update(name="a/b"))
    _bad(lambda m: m["end_to_end"][1].update(unit="tokens per second"))
    _bad(lambda m: m["per_layer"][2].update(unit="µs"))
    _bad(lambda m: m["end_to_end"][0].update(bound=0.3))
    _bad(lambda m: m["end_to_end"][0].update(why="x"))
    _bad(lambda m: m["workloads"][0].update(chips=2))
    _bad(lambda m: m["per_layer"][0].update(moves="nothing"))
    _bad(lambda m: m["workloads"].append(dict(m["workloads"][0])))


def test_readers_return_nothing_without_a_source():
    empty = {"layer": {"slot_steps": 0, "forward_passes": 0, "least_s": 0,
                       "window_s": 1.0}, "trace": None}
    for f in (readers.occupancy, readers.step_ms, readers.step_mfu,
              readers.idle_share):
        assert f(empty) is None
    ctx = {"layer": {"slot_steps": 160, "busy_slot_steps": 120,
                     "forward_passes": 4, "step_span_s": 1.0,
                     "least_s": 0.25, "window_s": 2.0},
           "trace": {"window_s": 2.0, "idle_share": 0.125}}
    assert readers.occupancy(ctx) == 75.0
    assert readers.step_ms(ctx) == 250.0
    assert readers.step_mfu(ctx) == 12.5
    assert readers.idle_share(ctx) == 12.5
