"""Compile-only checks for a described TPU v5e: the quantized Pallas
kernels and one serving step at starcoder2-7b's published widths.

Nothing runs: the chip's compiler refuses what the chip would refuse
(block tiling, Mosaic casts, a program larger than device memory).  The
topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the one running this
file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.qalora import QALoRAParams
from repro.core.quant import QuantizedLinear

HBM_BYTES = 16 * 10**9  # one v5e chip
BITS, GROUP, RANK = 4, 32, 64  # starcoder2-7b's QuantPolicy
# (d_in, d_out): the MLP up and down projections and the GQA k/v projection
SHAPES = [(4608, 18432), (18432, 4608), (4608, 512)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back here; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _qt(one_chip, k, n):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    return QuantizedLinear(s((k // 2, n), jnp.uint8),
                           s((k // GROUP, n), jnp.bfloat16),
                           s((k // GROUP, n), jnp.bfloat16), BITS, GROUP)


def _lower(one_chip, kernel, k, n):
    from repro.kernels import qalora_matmul, qalora_slot_matmul, qmatmul
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    qt = _qt(one_chip, k, n)
    ad = QALoRAParams(s((k // GROUP, RANK), jnp.bfloat16),
                      s((RANK, n), jnp.bfloat16))
    if kernel == "qmatvec":
        return jax.jit(lambda x, q: qmatmul(x, q, interpret=False)).lower(
            s((1, k), jnp.bfloat16), qt)
    if kernel == "qalora_matvec":
        return jax.jit(lambda x, q, p: qalora_matmul(
            x, q, p, s=2.0, interpret=False)).lower(
                s((4, k), jnp.bfloat16), qt, ad)
    if kernel == "qalora_slot_matvec":
        return jax.jit(lambda x, q, a, b, i: qalora_slot_matmul(
            x, q, a, b, i, s=2.0, interpret=False)).lower(
                s((4, k), jnp.bfloat16), qt,
                s((4, k // GROUP, RANK), jnp.bfloat16),
                s((4, RANK, n), jnp.bfloat16), s((4,), jnp.int32))
    assert kernel == "qmatmul"
    return jax.jit(lambda x, q: qmatmul(x, q, interpret=False)).lower(
        s((256, k), jnp.bfloat16), qt)


@pytest.mark.parametrize("k,n", SHAPES, ids=[f"{k}x{n}" for k, n in SHAPES])
@pytest.mark.parametrize("kernel", ["qmatvec", "qalora_matvec",
                                    "qalora_slot_matvec", "qmatmul"])
def test_kernel_compiles_for_v5e(one_chip, no_compile_cache, kernel, k, n):
    compiled = _lower(one_chip, kernel, k, n).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (m, d_in, d_out): a starcoder2-7b decode burst's MLP up projection and
# an h2o-danube-1.8b one's gate/up projection, at both burst widths
GEMV_CASES = [(m, k, n) for m in (16, 32)
              for k, n in ((4608, 18432), (2560, 6912))]


@pytest.mark.parametrize("m,k,n", GEMV_CASES,
                         ids=[f"m{m}-{k}x{n}" for m, k, n in GEMV_CASES])
def test_decode_gemv_compiles_for_v5e(one_chip, no_compile_cache, m, k, n):
    """The merged intq route's GEMV at decode-burst rows, with the decode
    block rule's blocks."""
    from repro.kernels.ops import decode_blocks
    from repro.kernels.qmatvec import qmatvec_pallas
    bn, bk = decode_blocks(m, k, n, BITS, GROUP)
    qt = _qt(one_chip, k, n)
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, q: qmatvec_pallas(
        x, q.qweight, q.scale, q.zero, bits=BITS, group_size=GROUP,
        block_n=bn, block_k=bk, interpret=False)).lower(x, qt).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_step_ragged_fits_one_v5e(one_chip, no_compile_cache):
    """One full-width ragged serving step of the merged INT4 model (4
    slots x 1024 positions, 64-token chunks, f32 cache) fits the chip."""
    import repro.configs as C
    from repro.core.schemes import merge_tree
    from repro.models.lm import LM

    lm = LM(C.get("starcoder2-7b"))
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    params = jax.eval_shape(lambda: merge_tree(lm.init(jax.random.PRNGKey(0))))
    cache = jax.eval_shape(lambda: lm.init_cache(4, 1024, dtype=jnp.float32))
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(lm.step_ragged).lower(
        on_chip(params), on_chip(cache), i32((4, 64)), i32((4,))).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < HBM_BYTES, total
