"""Encoder-decoder continuous batching: per-slot FROZEN cross-attention
caches filled at admission from each request's encoder frames, slotted
self-KV through the shared ragged chunk path, and token-for-token
equivalence with the static prefill+generate path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.launch.serve import merge_model, generate_loop_reference
from repro.models.lm import LM
from repro.serving import ContinuousEngine, make_trace

MAX_SRC = 8


@pytest.fixture(scope="module")
def served_encdec():
    cfg = C.reduced("seamless-m4t-medium")
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    return cfg, lm, merged


def _src(cfg, ss, seed):
    if ss == 0:
        return None
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(ss, cfg.d_model)) * 0.3).astype(np.float32)


def _reference(lm, merged, req, src, max_len):
    """One request alone through the static path: prefill over (tokens,
    src) + scan generate when the request has encoder frames, the legacy
    per-token loop over a zero cross cache when it does not."""
    if src is None:
        toks, _ = generate_loop_reference(lm, merged, req.prompt[None, :],
                                          req.max_new_tokens, max_len)
        return [int(t) for t in toks[0]]
    batch = {"tokens": jnp.asarray(req.prompt[None, :]),
             "src": jnp.asarray(src[None])}
    logits, pre = jax.jit(lm.prefill)(merged, batch)
    cache = lm.merge_prefill_cache(
        pre, lm.slot_state().init(1, max_len, jnp.float32, src_cap=MAX_SRC))
    toks, _ = lm.generate(merged, cache, logits, req.max_new_tokens)
    return [int(t) for t in toks[0]]


# ---------------------------------------------------------------------------
# equivalence (slow lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_encdec_engine_matches_static_path_with_frozen_cross(served_encdec):
    """The tentpole gate: mixed prompt/gen/src lengths, more requests
    than slots (eviction + refill triggers), each slot pinning its own
    frozen cross cache at admission — token streams identical to running
    each request alone through the static path.  One request has NO src
    and must serve with a zero cross context even though its slot's
    previous occupant had real encoder frames (reset wipes the cross
    cache, not just its length)."""
    cfg, lm, merged = served_encdec
    trace = make_trace(5, cfg.vocab, seed=3, prompt_lens=(3, 6),
                       gen_lens=(2, 7, 4))
    src_lens = (4, 7, 0, 5, 4)
    srcs = {r.rid: _src(cfg, ss, 100 + r.rid)
            for r, ss in zip(trace, src_lens)}
    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=16,
                           prefill_chunk=4, decode_burst=4, max_src=MAX_SRC)
    for r in trace:
        eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid,
                   src=srcs[r.rid])
    out = eng.run()
    assert sorted(out) == [r.rid for r in trace]
    for r in trace:
        ref = _reference(lm, merged, r, srcs[r.rid], 16)
        assert out[r.rid] == ref, f"rid {r.rid} (src {src_lens[r.rid]})"


@pytest.mark.slow
def test_encdec_cross_cache_is_frozen_per_slot(served_encdec):
    """Two slots with DIFFERENT memories decode concurrently: each
    request's stream matches its solo reference, i.e. slots never read
    each other's cross cache and the cross cache never advances with the
    decode position."""
    cfg, lm, merged = served_encdec
    trace = make_trace(2, cfg.vocab, seed=9, prompt_lens=(4,), gen_lens=(6,))
    srcs = {0: _src(cfg, 6, 1), 1: _src(cfg, 3, 2)}
    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=12,
                           prefill_chunk=4, decode_burst=4, max_src=MAX_SRC)
    for r in trace:
        eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid,
                   src=srcs[r.rid])
    out = eng.run()
    for r in trace:
        assert out[r.rid] == _reference(lm, merged, r, srcs[r.rid], 12)
    # the engine's cross lens reflect the admitted memories (order-free)
    assert sorted(np.asarray(
        eng.cache["layers"]["cross"]["len"]).tolist()) == [3, 6]


# ---------------------------------------------------------------------------
# fast lane
# ---------------------------------------------------------------------------


def test_encdec_engine_smoke_fast(served_encdec):
    """Fast-lane gate: encdec serves through the continuous engine end
    to end (admission + cross pinning + eviction/refill) with a mix of
    src-bearing and src-less requests."""
    cfg, lm, merged = served_encdec
    trace = make_trace(3, cfg.vocab, seed=2, prompt_lens=(2, 4),
                       gen_lens=(2, 3))
    srcs = {0: _src(cfg, 3, 7), 1: None, 2: _src(cfg, 5, 8)}
    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=8,
                           prefill_chunk=4, decode_burst=2, max_src=MAX_SRC)
    for r in trace:
        eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid,
                   src=srcs[r.rid])
    out = eng.run()
    assert sorted(out) == [r.rid for r in trace]
    for r in trace:
        assert len(out[r.rid]) == r.max_new_tokens
        assert all(0 <= t < cfg.vocab for t in out[r.rid])


def test_encdec_submit_validates_src(served_encdec):
    cfg, lm, merged = served_encdec
    eng = ContinuousEngine(lm, merged, n_slots=1, max_len=8, max_src=4)
    with pytest.raises(ValueError, match="max_src=4"):
        eng.submit(np.arange(4, 6, dtype=np.int32), 2,
                   src=np.zeros((5, cfg.d_model), np.float32))
    with pytest.raises(ValueError, match="d_model"):
        eng.submit(np.arange(4, 6, dtype=np.int32), 2,
                   src=np.zeros((3, cfg.d_model + 1), np.float32))
    # a [0, d] src is almost certainly a caller bug (an empty memory
    # spelled as an array instead of None) — reject it loudly rather
    # than burn an encoder dispatch at admission to pin nothing
    with pytest.raises(ValueError, match="zero frames"):
        eng.submit(np.arange(4, 6, dtype=np.int32), 2,
                   src=np.zeros((0, cfg.d_model), np.float32))
    # src=None remains the supported spelling for a src-less request
    eng.submit(np.arange(4, 6, dtype=np.int32), 2, src=None)


def test_src_rejected_for_non_encdec_family():
    cfg = C.reduced("gemma3-1b")
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    eng = ContinuousEngine(lm, merged, n_slots=1, max_len=8)
    with pytest.raises(ValueError, match="encdec"):
        eng.submit(np.arange(4, 6, dtype=np.int32), 2,
                   src=np.zeros((2, cfg.d_model), np.float32))


# ---------------------------------------------------------------------------
# src-length bucketing (compile-count pin)
# ---------------------------------------------------------------------------


def test_encode_compiles_are_bucketed_to_pow2_lengths(served_encdec):
    """Live traffic carries arbitrary src lengths; without bucketing,
    each distinct length would compile its own encoder program.  The pin
    (migrated from a `_JIT_ENCODE` monkeypatch spy to a CompileGuard
    budget): the engine declares bit_length(max_src) encoder programs,
    and 16 distinct request lengths must compile only the pow2 buckets
    {1, 2, 4, 8, 16} — the true length rides in as a traced mask, not a
    compile key.  An unbucketed encoder (one program per length) blows
    the budget and raises CompileBudgetExceeded on the very step that
    over-compiled, via the engine's own per-step guard.check()."""
    from repro.runtime.compile_guard import CompileGuard
    cfg, lm, merged = served_encdec
    with CompileGuard("encdec-pin") as g:
        # max_src=16 (not the module-wide 8) so the top bucket's encoder
        # shape is fresh in this process: the guard must observe >= 1
        # real compile, not just an already-warm cache
        eng = ContinuousEngine(lm, merged, n_slots=2, max_len=12,
                               prefill_chunk=4, decode_burst=2, max_src=16)
        for ss in range(1, 17):  # 16 distinct true lengths
            eng.submit(np.arange(4, 7, dtype=np.int32), 2, rid=ss,
                       src=_src(cfg, ss, 40 + ss))
        out = eng.run()
        assert len(out) == 16
        g.check()
        count, budget = g.counts()["engine._JIT_ENCODE"]
        assert budget == 5, budget  # O(log max_src): {1, 2, 4, 8, 16}
        assert 1 <= count <= budget, (count, budget)


@pytest.mark.slow
def test_bucketed_encode_is_bit_identical_to_unpadded(served_encdec):
    """Masked keys hit exp(NEG_INF) == 0 exactly, so the pinned cross
    K/V from a padded+masked encode must match encoding the unpadded
    source to within a few ulps — bucketing is a compile-count
    optimization, never a numerics change.  Not bit-identical: XLA's CPU
    backend reduces over the padded axis in a different order (on JAX
    0.9, 177 of 192 elements differ by 1 ulp, max 9.5e-7), and two
    encoder layers carry that on.  So each element may differ by 8 f32
    epsilons of itself, plus 4 ulps of the array's largest magnitude for
    elements near zero."""
    cfg, lm, merged = served_encdec
    for ss in (3, 5, 7):  # none on a bucket boundary
        src = _src(cfg, ss, 70 + ss)
        ks, vs = jax.jit(lm.encode_cross)(merged, jnp.asarray(src)[None])
        bs = 1 << (ss - 1).bit_length()
        pad = np.zeros((bs, cfg.d_model), np.float32)
        pad[:ss] = src
        ks2, vs2 = jax.jit(lm.encode_cross)(
            merged, jnp.asarray(pad)[None], jnp.asarray([ss], jnp.int32))
        for ref, got in ((ks, ks2), (vs, vs2)):
            ref = np.asarray(ref)
            ulp = np.spacing(np.abs(ref).max())
            np.testing.assert_allclose(np.asarray(got[:, :, :ss]), ref,
                                       rtol=8 * np.finfo(np.float32).eps,
                                       atol=4 * ulp)
