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


# (m, d_in, d_out) of every merged INT4 linear the served cells run:
# starcoder2-7b's decode bursts (16 rows) and h2o-danube-1.8b's (32 rows)
SERVED_LINEARS = (
    [(16, k, n) for k in (4608, 18432) for n in (4608, 512, 18432, 49152)]
    + [(32, k, n) for k in (2560, 6912) for n in (2560, 640, 6912, 32000)])


def test_block_picker_constraints():
    from repro.kernels import pick_blocks
    from repro.kernels.ops import (DECODE_VMEM_BYTES, decode_blocks,
                                   gemv_vmem_bytes)
    from repro.core.quant import codes_per_byte
    for bits in BITS:
        for k in (64, 512, 22016):
            for n in (48, 1152, 14336):
                bm, bn, bk = pick_blocks(128, k, n, bits, 32)
                assert k % bk == 0 and n % bn == 0
                assert bk % 32 == 0 and bk % codes_per_byte(bits) == 0
    # the decode GEMV's blocks: legal TPU tiles (x's code planes are
    # [cpb, m, bk/cpb], codes [bk/cpb, bn], scale/zero [bk/g, bn]) within
    # the VMEM budget, at every linear shape of both served models
    for m, k, n in SERVED_LINEARS:
        bn, bk = decode_blocks(m, k, n, 4, 32)
        assert k % bk == 0 and n % bn == 0 and bk % 32 == 0
        assert (bk // 2) % 128 == 0 and (bk // 32) % 8 == 0
        assert bn % 128 == 0
        assert gemv_vmem_bytes(m, bk, bn, 4, 32) <= DECODE_VMEM_BYTES


# ---------------------------------------------------------------------------
# decode GEMV path (M <= GEMV_MAX_M dispatches to kernels/qmatvec.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("m", [1, 2, 8, 16, 32])
def test_qmatvec_matches_qmatmul_and_ref(bits, m):
    """GEMV path == matmul kernel == dense reference at decode M: through
    ``qmatmul`` up to GEMV_MAX_M, and at the merged intq route's decode
    bursts (16 and 32 rows) through ``qmatvec_pallas`` itself."""
    from repro.kernels import GEMV_MAX_M
    from repro.kernels.qmatmul import qmatmul_pallas
    from repro.kernels.qmatvec import qmatvec_pallas

    k, n, g = 128, 96, 32
    x, qt, _ = _setup(bits, m, k, n, g, jnp.float32)
    if m <= GEMV_MAX_M:
        y = qmatmul(x, qt, interpret=True)  # dispatches to qmatvec
    else:
        y = qmatvec_pallas(x, qt.qweight, qt.scale, qt.zero, bits=bits,
                           group_size=g, block_n=48, block_k=64,
                           interpret=True)
    yr = qmatmul_ref(x, qt)
    ym = qmatmul_pallas(x, qt.qweight, qt.scale, qt.zero, bits=bits,
                        group_size=g, block_m=m, block_n=48, block_k=64,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ym), rtol=2e-4, atol=2e-4)


def test_qmatvec_kernel_never_interleaves_codes():
    """The GEMV dequantizes each code plane where it lies: its kernel body
    stacks no planes back into row order.  The interleaving body ran 4-5x
    slower on a v5e and, loaded from the persistent compile cache, stalled
    the first decode burst for seconds (PERF.md)."""
    from repro.kernels.qmatvec import qmatvec_pallas

    x, qt, _ = _setup(4, 16, 512, 256, 32, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda x: qmatvec_pallas(
        x, qt.qweight, qt.scale, qt.zero, bits=4, group_size=32,
        block_n=128, block_k=256, interpret=False))(x)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    body = str(call.params["jaxpr"])
    assert "shift_right" in body  # the planes are cut from the bytes
    assert "concatenate" not in body


def test_qmatvec_refuses_rows_past_the_intq_bound():
    """The GEMV holds all rows in one block: past the bound the merged
    intq route uses, it refuses instead of tiling."""
    from repro.kernels import INTQ_GEMV_MAX_M
    from repro.kernels.qmatvec import qmatvec_pallas

    x, qt, _ = _setup(4, INTQ_GEMV_MAX_M + 1, 128, 96, 32, jnp.float32)
    with pytest.raises(AssertionError):
        qmatvec_pallas(x, qt.qweight, qt.scale, qt.zero, bits=4,
                       group_size=32, block_n=48, block_k=64, interpret=True)


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
