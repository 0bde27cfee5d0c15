"""Data pipeline, optimizer, checkpointing, fault-tolerance runtime."""

import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import make_stream, DataConfig, InstructionStream
from repro.optim import (AdamWConfig, adamw_init, adamw_update, split_params,
                         merge_params, clip_by_global_norm,
                         int8_compress, int8_decompress)
from repro.checkpoint import CheckpointManager, save_pytree, load_pytree
from repro.runtime import (StragglerDetector, Heartbeat, PreemptionGuard,
                           RestartableLoop)


# --------------------------------- data -----------------------------------


def test_stream_deterministic_and_seekable():
    s1 = make_stream("alpaca", vocab=64, seq_len=32, global_batch=4)
    batches = [s1.next_batch() for _ in range(5)]
    s2 = make_stream("alpaca", vocab=64, seq_len=32, global_batch=4)
    s2.skip_to(3)
    t, l = s2.next_batch()
    np.testing.assert_array_equal(t, batches[3][0])
    np.testing.assert_array_equal(l, batches[3][1])


def test_stream_host_sharding():
    full = InstructionStream(DataConfig(vocab=64, seq_len=32, global_batch=4))
    h0 = InstructionStream(DataConfig(vocab=64, seq_len=32, global_batch=4,
                                      host_id=0, n_hosts=2))
    h1 = InstructionStream(DataConfig(vocab=64, seq_len=32, global_batch=4,
                                      host_id=1, n_hosts=2))
    ft, _ = full.next_batch()
    t0, _ = h0.next_batch()
    t1, _ = h1.next_batch()
    np.testing.assert_array_equal(np.concatenate([t0, t1]), ft)


def test_stream_labels_supervise_answers_only():
    s = make_stream("selfinst", vocab=64, seq_len=64, global_batch=2)
    toks, labs = s.next_batch()
    assert (labs >= -1).all() and (labs < 64).all()
    assert (labs >= 0).any()      # some supervised positions
    assert (labs == -1).any()     # some masked positions


def test_all_datasets_learnable_structure():
    from repro.data.pipeline import TASKS, _answer
    rng = np.random.default_rng(0)
    p = rng.integers(4, 64, size=8)
    for t in TASKS:
        a = _answer(t, p, 64)
        assert a.ndim == 1 and len(a) >= len(p)


# -------------------------------- optim -----------------------------------


def test_adamw_minimizes_quadratic():
    params = {"ad": {"x": jnp.array([3.0, -2.0])}}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=0.1, max_grad_norm=0.0)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum(p["ad"]["x"] ** 2))(params)
        params, opt, _ = adamw_update(cfg, g, opt, params)
    assert float(jnp.abs(params["ad"]["x"]).max()) < 1e-2


def test_clip_by_global_norm():
    tree = {"a": jnp.ones((10,)) * 10}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) > 1.0
    from repro.optim import global_norm
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5


def test_split_merge_roundtrip():
    params = {"blocks": {"attn": {"wq": {"q": jnp.ones((4, 4)),
                                         "ad": {"a": jnp.zeros((2, 1))}}}},
              "embed": jnp.ones((8, 4))}
    tr, fr = split_params(params)
    assert tr["embed"] is None
    assert tr["blocks"]["attn"]["wq"]["ad"]["a"] is not None
    assert fr["blocks"]["attn"]["wq"]["q"] is not None
    merged = merge_params(tr, fr)
    np.testing.assert_array_equal(np.asarray(merged["embed"]),
                                  np.asarray(params["embed"]))


def test_int8_compression_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (128,))
    q, s = int8_compress(x)
    err = jnp.abs(int8_decompress(q, s) - x)
    assert float(err.max()) <= float(s) * 0.5 + 1e-6
    assert q.dtype == jnp.int8


# ------------------------------ checkpoint --------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16)}}
    save_pytree(jax.tree.map(np.asarray, tree), str(tmp_path / "ck"))
    out = load_pytree(str(tmp_path / "ck"), tree)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert np.asarray(out["nested"]["b"]).dtype == np.asarray(tree["nested"]["b"]).dtype


def test_manager_async_retention_resume(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    state = {"x": jnp.zeros((3,))}
    for step in (10, 20, 30):
        m.save(step, {"x": jnp.full((3,), step, jnp.float32)})
    m.wait()
    assert m.all_steps() == [20, 30]  # retention dropped step 10
    assert m.latest_step() == 30
    out = m.restore(30, state)
    np.testing.assert_array_equal(np.asarray(out["x"]), np.full((3,), 30.0))
    m.close()


def test_manager_base_snapshot_immutable(tmp_path):
    m = CheckpointManager(str(tmp_path), async_write=False)
    m.save_base({"q": jnp.ones((2,))})
    m.save_base({"q": jnp.zeros((2,))})  # second call is a no-op
    out = m.restore_base({"q": jnp.zeros((2,))})
    np.testing.assert_array_equal(np.asarray(out["q"]), np.ones((2,)))


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    m = CheckpointManager(str(tmp_path), async_write=False)
    m.save(1, {"x": jnp.ones((2,))})
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


# ------------------------------- runtime ----------------------------------


def test_straggler_detector():
    d = StragglerDetector(ratio=2.0, warmup=2)
    for _ in range(10):
        assert not d.check(1.0)
    assert d.check(5.0)          # clear outlier
    assert not d.check(1.0)      # ewma not polluted
    assert d.flagged == 1


def test_heartbeat(tmp_path):
    p = str(tmp_path / "hb.json")
    hb = Heartbeat(p, host_id=3, interval=0.05).start()
    time.sleep(0.2)
    assert Heartbeat.is_alive(p, timeout=1.0)
    hb.stop()
    time.sleep(0.2)
    assert not Heartbeat.is_alive(p, timeout=0.1)


def test_restartable_loop_resume_and_cadence(tmp_path):
    saves = []
    loop = RestartableLoop(total_steps=10, ckpt_every=4,
                           save_cb=lambda s: saves.append(s), start_step=2)
    seen = []
    end = loop.run(lambda s: seen.append(s) or {})
    assert seen == list(range(2, 10))
    assert end == 10
    assert 4 in saves and 8 in saves and saves[-1] == 10


def test_preemption_guard_graceful():
    saves = []
    with PreemptionGuard() as guard:
        loop = RestartableLoop(total_steps=1000, ckpt_every=1000,
                               save_cb=lambda s: saves.append(s), guard=guard)

        def body(step):
            if step == 3:
                os.kill(os.getpid(), signal.SIGTERM)
            return {}

        end = loop.run(body)
    assert end == 4           # stopped right after the signal
    assert saves[-1] == 4     # final save happened


# ----------------------------- compile cache -------------------------------


def test_compile_cache_follows_env_var(monkeypatch, tmp_path):
    """A directory set from outside is used as is; no other is set in code."""
    from repro.runtime import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    from repro.runtime import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path  # fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
