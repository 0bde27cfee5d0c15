"""Serve decode path: prefill + lax.scan generation must be token-identical
to the legacy per-token loop, and the cache embedding must be exact."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import (merge_model, generate_scan,
                                generate_loop_reference)
from repro.models.lm import LM


def _serve_setup(arch="gemma3-1b", b=2, prompt_len=5):
    cfg = C.reduced(arch)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    merged = merge_model(params, cfg.quant)
    prompts = np.random.default_rng(0).integers(
        4, cfg.vocab, size=(b, prompt_len)).astype(np.int32)
    return cfg, lm, merged, prompts


def test_scan_decode_matches_loop_gemma():
    """Greedy generations from prefill+scan == the per-token loop."""
    cfg, lm, merged, prompts = _serve_setup()
    gen_len, max_len = 4, 9
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        g_scan, _ = generate_scan(lm, mesh, merged, prompts, gen_len, max_len)
        g_loop, _ = generate_loop_reference(lm, merged, prompts, gen_len,
                                            max_len)
    assert g_scan.shape == (2, gen_len)
    np.testing.assert_array_equal(g_scan, g_loop)


def test_merge_prefill_cache_exact():
    """The padded prefill cache must continue decoding exactly like a cache
    built by feeding the prompt through decode steps."""
    cfg, lm, merged, prompts = _serve_setup()
    b, prompt_len = prompts.shape
    max_len = prompt_len + 3
    toks = jnp.asarray(prompts)

    logits_p, pre = jax.jit(lm.prefill)(merged, {"tokens": toks})
    decode_cache = lm.init_cache(b, max_len, dtype=jnp.float32)
    cache_scan = lm.merge_prefill_cache(pre, decode_cache)

    cache_loop = lm.init_cache(b, max_len, dtype=jnp.float32)
    step = jax.jit(lm.decode_step)
    logits_l = None
    for i in range(prompt_len):
        logits_l, cache_loop = step(merged, cache_loop, toks[:, i:i + 1])

    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_l),
                               rtol=1e-4, atol=1e-4)
    # same structure, same lengths; next decode step agrees
    np.testing.assert_array_equal(np.asarray(cache_scan["len"]),
                                  np.asarray(cache_loop["len"]))
    nxt = jnp.argmax(logits_p, -1)[:, None].astype(jnp.int32)
    l1, _ = step(merged, cache_scan, nxt)
    l2, _ = step(merged, cache_loop, nxt)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-4, atol=1e-4)


def test_lm_generate_greedy_chain():
    """lm.generate's token i+1 is argmax of decode_step on token i."""
    cfg, lm, merged, prompts = _serve_setup(b=1, prompt_len=3)
    toks = jnp.asarray(prompts)
    logits, pre = jax.jit(lm.prefill)(merged, {"tokens": toks})
    cache = lm.merge_prefill_cache(pre, lm.init_cache(1, 8, jnp.float32))
    gen, _ = lm.generate(merged, cache, logits, 3)
    assert int(gen[0, 0]) == int(jnp.argmax(logits, -1)[0])

    cache2 = lm.merge_prefill_cache(pre, lm.init_cache(1, 8, jnp.float32))
    step = jax.jit(lm.decode_step)
    lg = logits
    for j in range(3):
        tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        assert int(gen[0, j]) == int(tok[0, 0])
        lg, cache2 = step(merged, cache2, tok)


def test_generate_zero_and_one_len():
    cfg, lm, merged, prompts = _serve_setup(b=2, prompt_len=3)
    logits, pre = jax.jit(lm.prefill)(merged, {"tokens": jnp.asarray(prompts)})
    cache = lm.merge_prefill_cache(pre, lm.init_cache(2, 8, jnp.float32))
    g0, _ = lm.generate(merged, cache, logits, 0)
    assert g0.shape == (2, 0)
    cache = lm.merge_prefill_cache(pre, lm.init_cache(2, 8, jnp.float32))
    g1, _ = lm.generate(merged, cache, logits, 1)
    np.testing.assert_array_equal(np.asarray(g1[:, 0]),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_serve_main_prompt_len_zero():
    """Regression: --prompt-len 0 used to hit an unbound `logits`."""
    from repro.launch.serve import main
    main(["--arch", "gemma3-1b", "--reduced", "--requests", "1",
          "--prompt-len", "0", "--gen-len", "2"])


def test_serve_main_unsupported_family_names_family_and_docs(monkeypatch,
                                                             capsys):
    """A family without ragged support must fail as a clear CLI error
    naming the family and pointing at the README family-support matrix —
    not as the bare engine-constructor traceback."""
    from repro.launch.serve import main
    monkeypatch.setattr(LM, "supports_ragged", lambda self: False)
    with pytest.raises(SystemExit):
        main(["--arch", "gemma3-1b", "--reduced", "--engine", "continuous",
              "--requests", "1", "--gen-len", "2"])
    err = capsys.readouterr().err
    assert "'gqa'" in err and "family-support" in err
    assert "--engine static" in err
