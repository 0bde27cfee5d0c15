"""Pallas kernel validation: interpret-mode sweeps vs the pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize, QALoRAParams
from repro.kernels import qmatmul, qalora_matmul, qmatmul_ref, qalora_matmul_ref

SHAPES = [  # (M, K, N, group)
    (1, 64, 48, 16),
    (7, 128, 96, 32),
    (33, 256, 256, 64),
    (128, 512, 128, 32),
]
BITS = [2, 3, 4, 8]


def _setup(bits, m, k, n, g, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (k, n))
    qt = quantize(w, bits, g)
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, k)).astype(dtype)
    p = QALoRAParams(
        a=jax.random.normal(jax.random.fold_in(key, 2), (k // g, 8), dtype) * 0.3,
        b=jax.random.normal(jax.random.fold_in(key, 3), (8, n), dtype) * 0.3)
    return x, qt, p


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("shape", SHAPES)
def test_qmatmul_matches_ref(bits, shape):
    m, k, n, g = shape
    x, qt, _ = _setup(bits, m, k, n, g, jnp.float32)
    y = qmatmul(x, qt, interpret=True)
    yr = qmatmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("shape", SHAPES[:3])
def test_qalora_fused_matches_ref(bits, shape):
    m, k, n, g = shape
    x, qt, p = _setup(bits, m, k, n, g, jnp.float32)
    y = qalora_matmul(x, qt, p, s=0.7, interpret=True)
    yr = qalora_matmul_ref(x, qt, p, 0.7)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    x, qt, p = _setup(4, 16, 128, 64, 32, dtype)
    y = qalora_matmul(x, qt, p, s=1.0, interpret=True)
    yr = qalora_matmul_ref(x, qt, p, 1.0)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)
    assert y.dtype == dtype


def test_kernel_leading_dims():
    """ops.py flattens [B, S, K] activations."""
    x, qt, p = _setup(4, 12, 128, 64, 32, jnp.float32)
    x3 = jax.random.normal(jax.random.PRNGKey(5), (3, 4, 128))
    y = qalora_matmul(x3, qt, p, s=0.5, interpret=True)
    yr = qalora_matmul_ref(x3.reshape(12, 128), qt, p, 0.5).reshape(3, 4, 64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)


def test_block_picker_constraints():
    from repro.kernels import pick_blocks
    from repro.core.quant import codes_per_byte
    for bits in BITS:
        for k in (64, 512, 22016):
            for n in (48, 1152, 14336):
                bm, bn, bk = pick_blocks(128, k, n, bits, 32)
                assert k % bk == 0 and n % bn == 0
                assert bk % 32 == 0 and bk % codes_per_byte(bits) == 0


# ---------------------------------------------------------------------------
# decode GEMV path (M <= GEMV_MAX_M dispatches to kernels/qmatvec.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("m", [1, 2, 8])
def test_qmatvec_matches_qmatmul_and_ref(bits, m):
    """GEMV path == matmul kernel == dense reference at decode M."""
    from repro.kernels.qmatmul import qmatmul_pallas

    k, n, g = 128, 96, 32
    x, qt, _ = _setup(bits, m, k, n, g, jnp.float32)
    y = qmatmul(x, qt, interpret=True)  # dispatches to qmatvec (m <= 8)
    yr = qmatmul_ref(x, qt)
    ym = qmatmul_pallas(x, qt.qweight, qt.scale, qt.zero, bits=bits,
                        group_size=g, block_m=m, block_n=48, block_k=64,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ym), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("m", [1, 2, 8])
def test_qalora_matvec_fused_matches_ref(bits, m):
    k, n, g = 128, 96, 32
    x, qt, p = _setup(bits, m, k, n, g, jnp.float32)
    y = qalora_matmul(x, qt, p, s=0.7, interpret=True)  # fused GEMV path
    yr = qalora_matmul_ref(x, qt, p, 0.7)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)


def test_gemv_dispatch_threshold():
    """M <= GEMV_MAX_M must take the no-M-tiling GEMV kernel; above it the
    tiled matmul. Both agree with the oracle at the boundary."""
    from repro.kernels import GEMV_MAX_M
    assert GEMV_MAX_M == 8
    for m in (GEMV_MAX_M, GEMV_MAX_M + 1):
        x, qt, p = _setup(4, m, 128, 96, 32, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(qalora_matmul(x, qt, p, s=1.0, interpret=True)),
            np.asarray(qalora_matmul_ref(x, qt, p, 1.0)),
            rtol=2e-4, atol=2e-4)


def test_qmatvec_decode_token_shape():
    """[B, 1, K] decode activations flatten to M=B and round-trip."""
    x, qt, p = _setup(4, 4, 128, 64, 32, jnp.float32)
    x3 = jax.random.normal(jax.random.PRNGKey(6), (4, 1, 128))
    y = qalora_matmul(x3, qt, p, s=0.5, interpret=True)
    yr = qalora_matmul_ref(x3.reshape(4, 128), qt, p, 0.5).reshape(4, 1, 64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# autotune cache
# ---------------------------------------------------------------------------


def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    from repro.kernels import autotune, pick_blocks, heuristic_blocks

    path = tmp_path / "autotune_cache.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    autotune.clear_cache(persist=False)
    m, k, n, bits, g = 1, 256, 128, 4, 32
    # no cache, no measure -> heuristic
    assert pick_blocks(m, k, n, bits, g) == heuristic_blocks(m, k, n, bits, g)
    # measured result is persisted and then served from the cache
    best = autotune.measure_qmatmul(m, k, n, bits, g, reps=1)
    assert k % best[2] == 0 and n % best[1] == 0
    assert path.exists()
    assert pick_blocks(m, k, n, bits, g) == best
    # cache survives a reload from disk
    autotune.clear_cache(persist=False)
    autotune._cache = None
    assert pick_blocks(m, k, n, bits, g) == best
    autotune.clear_cache()
    assert not path.exists()
    assert pick_blocks(m, k, n, bits, g) == heuristic_blocks(m, k, n, bits, g)
    monkeypatch.delenv(autotune.CACHE_ENV)
    autotune.clear_cache(persist=False)
    autotune._cache = None


def test_autotune_raises_when_every_candidate_fails(tmp_path, monkeypatch):
    """A backend that refuses every block shape is an error naming the
    first refusal, never a heuristic recorded as if it were measured."""
    from repro.kernels import autotune, qmatvec

    path = tmp_path / "autotune_cache.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    autotune.clear_cache(persist=False)

    def refuse(*args, **kwargs):
        raise NotImplementedError("Unsupported cast: uint8 -> float32")

    monkeypatch.setattr(qmatvec, "qmatvec_pallas", refuse)
    m, k, n, bits, g = 1, 512, 256, 4, 32
    try:
        with pytest.raises(RuntimeError, match="every block candidate") as ei:
            autotune.measure_qmatmul(m, k, n, bits, g, reps=1)
        assert isinstance(ei.value.__cause__, NotImplementedError)
        key = autotune.cache_key(m, k, n, bits, g, 0, jax.default_backend())
        assert autotune.lookup(key) is None
        assert not path.exists()
    finally:
        autotune.clear_cache(persist=False)
        autotune._cache = None
