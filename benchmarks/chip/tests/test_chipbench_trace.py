"""Trace reduction: busy time as the union of device intervals inside the
window, the idle share, the top device operations and the idle gaps
labelled by the host span they fell in, on a hand-made trace with known
answers and on a slice of a trace recorded on a TPU v5e; and extraction
from a profiler file."""

import gzip
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from bench import spans as S, trace as T  # noqa: E402

MS = 1_000_000  # ns

HAND = {
    "host": [
        [S.WINDOW, 0, 100 * MS],
        [S.SUBMIT, 0, 10 * MS],
        [S.STEP, 10 * MS, 60 * MS],
        [S.COMMIT, 70 * MS, 30 * MS],
    ],
    "devices": {"/device:TPU:0": [
        ["fusion.1", -5 * MS, 15 * MS],   # 0..10 inside the window
        ["copy.6", 10 * MS + 500, MS],    # after a 500 ns gap: not listed
        ["fusion.2", 20 * MS, 30 * MS],   # 20..50
        ["dot.3", 40 * MS, 20 * MS],      # 40..60, overlaps fusion.2
        ["while.7", 20 * MS, 40 * MS],    # the loop around both: busy time,
        ["copy.4", 61 * MS, 1],           # not ranked; 1 ns at 61 ms
        ["fusion.1", 90 * MS, 20 * MS],   # 90..100 inside
    ]},
}


def test_hand_made_trace():
    r = T.reduce(HAND)
    assert r["window_s"] == pytest.approx(0.100)
    # union: 0-10, 10.0005-11.0005, 20-60, 61-61.000001, 90-100
    assert r["busy_s"] == pytest.approx(0.061000001, abs=1e-12)
    assert r["idle_share"] == pytest.approx(0.38999999, abs=1e-9)
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({"fusion.2": 0.030, "dot.3": 0.020,
                                 "fusion.1": 0.020, "copy.6": 0.001,
                                 "copy.4": 1e-9}, abs=1e-12)
    assert r["device_ops"][0][0] == "fusion.2"
    # 61.000001-90 (the commit span 70-90 covers most of it), then
    # 11.0005-20 and 60-61 (inside the step span 10-70)
    assert r["idle_gaps"] == [
        ["host_commit", pytest.approx(0.028999999, abs=1e-12)],
        ["frontend_step", pytest.approx(0.0089995, abs=1e-12)],
        ["frontend_step", pytest.approx(0.001, abs=1e-12)]]


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        T.reduce({"host": [], "devices": HAND["devices"]})
    with pytest.raises(ValueError):
        T.reduce({"host": HAND["host"], "devices": {}})


def test_recorded_v5e_trace():
    """A slice of a traced sc2-7b.decode window recorded on one TPU v5e
    (benchmark run, seed 1001): the reduction's numbers, worked out
    independently of ``reduce`` from the same events."""
    with gzip.open(os.path.join(HERE, "fixtures", "v5e_decode_events.json.gz"),
                   "rt") as f:
        ev = json.load(f)
    r = T.reduce(ev)
    (w0, w1), = [(s, s + d) for n, s, d in ev["host"] if n == S.WINDOW]
    ops = [(max(s, w0), min(s + d, w1)) for _, s, d in
           ev["devices"]["/device:TPU:0"] if min(s + d, w1) > max(s, w0)]
    busy, end = 0, w0
    for a, b in sorted(ops):
        a = max(a, end)
        if b > a:
            busy, end = busy + b - a, b
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert 0 < r["idle_share"] < 1
    assert len(r["device_ops"]) == T.TOP and len(r["idle_gaps"]) <= T.TOP
    tops = [s for _, s in r["device_ops"]]
    assert tops == sorted(tops, reverse=True)
    assert sum(s for _, s in r["idle_gaps"]) <= (r["window_s"] - r["busy_s"]
                                                  + 1e-9)
    labels = {n[len(S.PREFIX):] for n, _, _ in ev["host"]} | {"no host span"}
    assert {g[0] for g in r["idle_gaps"]} <= labels


def test_extract_reads_a_profiler_file(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with S.span(S.WINDOW):
        for _ in range(3):
            with S.span(S.STEP):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = T.extract(T.find_xplane(str(tmp_path)), cpu=True)
    names = [n for n, _, _ in ev["host"]]
    assert names.count(S.STEP) == 3 and names.count(S.WINDOW) == 1
    r = T.reduce(ev)
    assert 0 < r["busy_s"] <= r["window_s"]
