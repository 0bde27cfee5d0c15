"""chip_smoke.py's refusals, checked on the CPU: no TPU, a split that is
not a near-tie, and a kernel step that did not lower to Mosaic."""

import importlib.util
import os

import jax
import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chip_smoke.py")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_without_tpu(cs, capsys, monkeypatch, tmp_path):
    # the cache directory comes from outside, so nothing global changes
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_first_split_accepts_only_near_ties(cs):
    want = [5, 6, 7, 8]
    assert cs.first_split(want, want, [1.0] * 4, "same") is None
    assert cs.first_split([5, 6, 9, 9], want, [1.0, 1.0, 0.01, 1.0],
                          "tie") == 2
    with pytest.raises(cs.PhaseFailed, match="token 2 is 9, not 7"):
        cs.first_split([5, 6, 9, 8], want, [1.0, 1.0, cs.NEAR_TIE, 1.0],
                       "wide")


def test_kernel_phase_refuses_a_step_without_mosaic(cs):
    """On the CPU the merged INT-N linears never take the Pallas GEMV, so
    the compiled decode burst holds no tpu_custom_call and the phase must
    fail before checking the kernels."""
    import repro.configs as C
    from repro.core.schemes import merge_tree
    from repro.models.lm import LM

    lm = LM(C.reduced("starcoder2-7b"))
    merged = merge_tree(lm.init(jax.random.PRNGKey(0)))
    with pytest.raises(cs.PhaseFailed, match="tpu_custom_call"):
        cs.kernel_phase(lm, merged, 0, max_len=16, prefill_chunk=4,
                        decode_burst=1)
