"""Multi-device distribution tests, run in a subprocess with a forced
8-device CPU platform (the main test process must keep 1 device)."""

import subprocess
import sys
import textwrap



def _run(src: str) -> str:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        capture_output=True, text=True, timeout=420,
        # JAX_PLATFORMS=cpu is load-bearing: without it jax probes the TPU
        # runtime (libtpu ships in this image) and hangs on its lockfile —
        # these tests are about the forced multi-device CPU platform.
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/tmp", "JAX_PLATFORMS": "cpu"},
        cwd=".",
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_int8_compressed_cross_pod_sync():
    """compressed_mean over a real 'pod' axis: int8 wire format, exact-ish
    mean, and the sync step wiring from launch.steps."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.optim import compressed_mean
        from repro.launch.mesh import auto_mesh

        mesh = auto_mesh((2, 4), ("pod", "data"))
        x = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)  # per-pod values
        def f(xs):
            return compressed_mean(xs, "pod")
        y = jax.shard_map(f, mesh=mesh, in_specs=P("pod", "data"),
                          out_specs=P("pod", "data"), check_vma=False)(x)
        expect = jnp.broadcast_to(x.mean(0, keepdims=True), x.shape)
        err = float(jnp.max(jnp.abs(y - expect)))
        assert err < 0.05, err
        print("SYNC_OK", err)
    """)
    assert "SYNC_OK" in out


def test_train_step_multi_device_loss_matches_single():
    """The sharded train step computes the same loss as single-device."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        import repro.configs as C
        from repro.models import LM
        from repro.optim import adamw_init, split_params, AdamWConfig
        from repro.launch import steps as S
        from repro.launch.mesh import make_local_mesh

        cfg = C.reduced("gemma3-1b")
        lm = LM(cfg)
        params = lm.init(jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab),
        }
        ref, _ = jax.jit(lm.loss)(params, batch)

        mesh = make_local_mesh(2, 4)
        with jax.set_mesh(mesh):
            trainable, frozen = split_params(params)
            opt = adamw_init(trainable)
            jit_for, specs = S.make_train_step(lm, mesh, AdamWConfig(lr=1e-3),
                                               donate=False)
            trainable, frozen, opt = S.place(mesh, specs,
                                             (trainable, frozen, opt))
            jitted, _ = jit_for(jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch))
            _, _, metrics = jitted(trainable, frozen, opt, batch)
        np.testing.assert_allclose(float(metrics["loss"]), float(ref),
                                   rtol=2e-3, atol=2e-3)
        print("DIST_LOSS_OK", float(metrics["loss"]), float(ref))
    """)
    assert "DIST_LOSS_OK" in out


def test_elastic_checkpoint_reshard():
    """Save on a 4-device mesh, restore on an 8-device mesh (elastic)."""
    out = _run("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_pytree, load_pytree
        from repro.launch.mesh import auto_mesh

        d = tempfile.mkdtemp()
        m4 = auto_mesh((4,), ("model",), devices=jax.devices()[:4])
        m8 = auto_mesh((8,), ("model",))
        x4 = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                            NamedSharding(m4, P("model", None)))
        save_pytree({"w": x4}, d + "/ck")
        like = jax.ShapeDtypeStruct((8, 8), jnp.float32,
                                    sharding=NamedSharding(m8, P("model", None)))
        out = load_pytree(d + "/ck", {"w": like})
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(64, dtype=np.float32).reshape(8, 8))
        assert len(out["w"].sharding.device_set) == 8
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out
