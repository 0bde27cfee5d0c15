"""MLA (deepseek-v3 compressed-KV) continuous-batching serving: absorbed
ragged-chunk attention primitives, chunk==decode equivalence, engine
token-for-token equivalence with the static per-request path on the
all-dense config, eviction + refill without stale compressed-KV leakage,
and the hoisted absorbed-weight dequant contract.

Equivalence is gated on the ALL-DENSE config (every layer MLP, no MoE):
capacity-routed MoE layers make logits depend on batch composition (the
documented gqa_moe caveat applies unchanged), so the fast smoke test
only checks the real dense+MoE layer split runs end to end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import merge_model, generate_scan
from repro.models.attention import (MLAConfig, mla_chunk_attention,
                                    mla_decode, mla_init, mla_init_cache,
                                    mla_prefill_chunk)
from repro.models.common import QuantPolicy
from repro.models.lm import LM
from repro.serving import ContinuousEngine, make_trace

FP = QuantPolicy(mode="fp")


@pytest.fixture(scope="module")
def served_mla():
    """All-dense reduced deepseek-v3: MLA attention, plain MLP blocks."""
    cfg = C.reduced("deepseek-v3-671b", n_layers=2, n_dense_layers=2,
                    mtp=False)
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    return cfg, lm, merged


def _reference(lm, merged, req):
    """One request alone through the static prefill+scan path."""
    gen_len = req.max_new_tokens
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        toks, _ = generate_scan(lm, mesh, merged, req.prompt[None, :],
                                gen_len, len(req.prompt) + gen_len)
    return [int(t) for t in toks[0]]


# ---------------------------------------------------------------------------
# primitives: absorbed chunk attention
# ---------------------------------------------------------------------------


def _mla_cfg():
    return MLAConfig(d_model=16, n_heads=4, q_lora_rank=8, kv_lora_rank=8,
                     qk_nope_dim=4, qk_rope_dim=4, v_head_dim=4)


def test_mla_chunk_equals_decode_across_ragged_lengths():
    """Chunked ragged prefill through mla_prefill_chunk reproduces the
    per-token mla_decode path exactly — outputs on consumed rows and the
    resulting compressed caches are identical, for slots sitting at
    DIFFERENT lengths in the same batch."""
    cfg = _mla_cfg()
    key = jax.random.PRNGKey(7)
    p = mla_init(key, cfg, FP)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 6, 16)) * 0.5

    # reference: each slot ALONE, token-by-token through mla_decode
    # (slot 0 consumes 4 rows, slot 1 all 6)
    y_ref, c_ref = {}, {}
    for slot, n in ((0, 4), (1, 6)):
        cache1 = mla_init_cache(1, 8, cfg, dtype=jnp.float32)
        ys = []
        for t in range(n):
            y, cache1 = mla_decode(p, x[slot:slot + 1, t:t + 1], cache1,
                                   jnp.array([t]), cfg, FP)
            ys.append(y)
        y_ref[slot] = jnp.concatenate(ys, 1)[0]
        c_ref[slot] = cache1

    # ragged chunks, both slots in one batch: slot 0 takes [3, 1] rows,
    # slot 1 takes [3, 3]
    cache = mla_init_cache(2, 8, cfg, dtype=jnp.float32)
    y1, cache = mla_prefill_chunk(p, x[:, :3], cache,
                                  jnp.array([0, 0]), jnp.array([3, 3]),
                                  cfg, FP)
    y2, cache = mla_prefill_chunk(p, x[:, 3:], cache,
                                  jnp.array([3, 3]), jnp.array([1, 3]),
                                  cfg, FP)

    got = {0: jnp.concatenate([y1[0], y2[0, :1]], 0),
           1: jnp.concatenate([y1[1], y2[1]], 0)}
    for slot in (0, 1):
        np.testing.assert_allclose(np.asarray(got[slot]),
                                   np.asarray(y_ref[slot]),
                                   rtol=1e-5, atol=1e-5)
        for k in ("c", "kr"):
            n = y_ref[slot].shape[0]
            np.testing.assert_allclose(np.asarray(cache[k][slot, :n]),
                                       np.asarray(c_ref[slot][k][0, :n]),
                                       rtol=1e-5, atol=1e-5)


def test_mla_decode_is_c1_chunk_wrapper():
    """mla_decode == mla_prefill_chunk at C=1 always-active (one copy of
    the absorbed math for both engines)."""
    cfg = _mla_cfg()
    key = jax.random.PRNGKey(9)
    p = mla_init(key, cfg, FP)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 1, 16))
    cache = mla_init_cache(2, 8, cfg, dtype=jnp.float32)
    cur = jnp.array([2, 5])
    yd, cd = mla_decode(p, x, cache, cur, cfg, FP)
    yc, cc = mla_prefill_chunk(p, x, cache, cur, jnp.ones_like(cur), cfg, FP)
    np.testing.assert_array_equal(np.asarray(yd), np.asarray(yc))
    for k in ("c", "kr"):
        np.testing.assert_array_equal(np.asarray(cd[k]), np.asarray(cc[k]))


def test_mla_chunk_attention_fully_masked_rows_stay_finite():
    """The garbage-logits contract: a fully-masked row (qpos < 0 — an
    idle slot) softmaxes an all-NEG_INF score row; the result must be
    garbage-but-FINITE so idle slots can never poison a batch with NaN."""
    key = jax.random.PRNGKey(11)
    b, c, s, h, r, d = 2, 3, 8, 4, 8, 4
    q_c = jax.random.normal(key, (b, c, h, r))
    q_r = jax.random.normal(jax.random.fold_in(key, 1), (b, c, h, d))
    cc = jax.random.normal(jax.random.fold_in(key, 2), (b, s, r))
    kr = jax.random.normal(jax.random.fold_in(key, 3), (b, s, d))
    qpos = jnp.array([[-1, -1, -1], [0, 1, -1]])  # slot 0 fully idle
    out = mla_chunk_attention(q_c, q_r, cc, kr, qpos, scale=0.5)
    assert np.isfinite(np.asarray(out)).all()


def test_mla_stale_cache_beyond_qpos_never_leaks():
    """Compressed-cache entries past each row's position must not change
    results — stale latent from an evicted request is invisible."""
    key = jax.random.PRNGKey(13)
    b, c, s, h, r, d = 1, 2, 8, 2, 6, 4
    q_c = jax.random.normal(key, (b, c, h, r))
    q_r = jax.random.normal(jax.random.fold_in(key, 1), (b, c, h, d))
    cc = jax.random.normal(jax.random.fold_in(key, 2), (b, s, r))
    kr = jax.random.normal(jax.random.fold_in(key, 3), (b, s, d))
    qpos = jnp.array([[2, 3]])
    base = mla_chunk_attention(q_c, q_r, cc, kr, qpos, scale=0.5)
    poisoned = mla_chunk_attention(q_c, q_r, cc.at[:, 4:].set(99.0),
                                   kr.at[:, 4:].set(-99.0), qpos, scale=0.5)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))


# ---------------------------------------------------------------------------
# hoisted absorbed-weight dequant
# ---------------------------------------------------------------------------


def test_absorbed_dequant_stays_out_of_step_graph(served_mla):
    """With aux threaded, the per-step graph never touches _kv_up_split
    (the engine computes the effective W_uk/W_uv once at construction).
    Migrated from a monkeypatch-raise pin to a CompileGuard wrap_counter
    with budget 0: the guard counts calls instead of exploding inside
    the traced graph, and restores the real function on exit."""
    import repro.models.attention as A
    from repro.runtime.compile_guard import (CompileBudgetExceeded,
                                             CompileGuard)
    cfg, lm, merged = served_mla
    aux = lm.absorbed_weights(merged)
    assert aux is not None and aux["dense"][0].shape[0] == cfg.n_layers
    cache = lm.init_cache(2, 8, jnp.float32)
    toks = jnp.asarray(np.full((2, 1), 5, np.int32))
    ones = jnp.ones((2,), jnp.int32)
    with CompileGuard("mla-pin") as g:
        g.wrap_counter(A, "_kv_up_split", budget=0)
        logits, _ = lm.step_ragged(merged, cache, toks, ones, aux=aux)
        g.check()  # aux threaded: ZERO dequant calls on the step path
        assert np.isfinite(np.asarray(logits)).all()
        lm.step_ragged(merged, cache, toks, ones)  # aux=None re-dequantizes
        assert g.count("repro.models.attention._kv_up_split") >= 1
        with pytest.raises(CompileBudgetExceeded, match="_kv_up_split"):
            g.check()
    # guard exit restored the real function (no counting wrapper left)
    assert not hasattr(A._kv_up_split, "__wrapped__")


# ---------------------------------------------------------------------------
# engine: fast-lane smoke (real dense+MoE layer split)
# ---------------------------------------------------------------------------


def test_mla_moe_engine_smoke_fast():
    """Fast-lane gate: the continuous engine serves the REAL reduced
    deepseek-v3 layer split (1 dense + 2 MoE layers) end to end —
    admission, chunked prefill, bursts, eviction + refill — and every
    request completes with its full token budget.  Stream equivalence is
    NOT asserted here (MoE capacity routing is batch-dependent); the
    slow lane gates that on the all-dense config."""
    cfg = C.reduced("deepseek-v3-671b", mtp=False)
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    trace = make_trace(3, cfg.vocab, seed=2, prompt_lens=(2, 5),
                       gen_lens=(2, 3))
    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=10,
                           prefill_chunk=4, decode_burst=2)
    for r in trace:
        eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
    out = eng.run()
    assert sorted(out) == [r.rid for r in trace]
    for r in trace:
        assert len(out[r.rid]) == r.max_new_tokens
        assert all(0 <= t < cfg.vocab for t in out[r.rid])


# ---------------------------------------------------------------------------
# engine: equivalence with the static path (slow lane, all-dense)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_mla_engine_matches_per_request_scan_on_mixed_trace(served_mla):
    """The tentpole gate: a mixed-length trace with more requests than
    slots (eviction + refill + chunked prefill all trigger) through the
    compressed-KV slotted cache emits per-request token streams identical
    to running each request alone through ``generate_scan``."""
    cfg, lm, merged = served_mla
    trace = make_trace(7, cfg.vocab, seed=3,
                       prompt_lens=(3, 6, 11), gen_lens=(2, 9, 4))
    eng = ContinuousEngine(lm, merged, n_slots=3, max_len=24,
                           prefill_chunk=4, decode_burst=4)
    for r in trace:
        eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
    out = eng.run()
    assert sorted(out) == [r.rid for r in trace]
    for r in trace:
        assert out[r.rid] == _reference(lm, merged, r), f"rid {r.rid}"
    st = eng.stats
    assert st.tokens_out == sum(r.max_new_tokens for r in trace)
    assert 0.0 < st.occupancy <= 1.0


@pytest.mark.slow
def test_mla_engine_invariant_to_chunk_and_burst(served_mla):
    """prefill_chunk / decode_burst are pure scheduling knobs for the
    compressed cache too: any setting gives identical token streams."""
    cfg, lm, merged = served_mla
    trace = make_trace(5, cfg.vocab, seed=11,
                       prompt_lens=(2, 7), gen_lens=(3, 8))
    outs = []
    for chunk, burst in ((1, 1), (4, 2), (8, 8)):
        eng = ContinuousEngine(lm, merged, n_slots=2, max_len=20,
                               prefill_chunk=chunk, decode_burst=burst)
        for r in trace:
            eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
        outs.append(eng.run())
    assert outs[0] == outs[1] == outs[2]


@pytest.mark.slow
def test_mla_slot_refill_no_stale_compressed_kv(served_mla):
    """Evicting a long request and prefilling a short one into the same
    slot gives the same logits as a fresh cache — the previous occupant's
    compressed latent beyond the new length is never read."""
    cfg, lm, merged = served_mla
    rng = np.random.default_rng(17)
    long_p = rng.integers(4, cfg.vocab, size=(1, 9)).astype(np.int32)
    short_p = rng.integers(4, cfg.vocab, size=(1, 4)).astype(np.int32)
    step = jax.jit(lm.step_ragged)

    def chunked_prefill(cache, prompt, slot, n_slots):
        logits = None
        for i in range(0, prompt.shape[1], 3):
            chunk = prompt[:, i:i + 3]
            toks = np.zeros((n_slots, chunk.shape[1]), np.int32)
            toks[slot, :chunk.shape[1]] = chunk[0]
            n_new = np.zeros((n_slots,), np.int32)
            n_new[slot] = chunk.shape[1]
            logits, cache = step(merged, cache, jnp.asarray(toks),
                                 jnp.asarray(n_new))
        return logits, cache

    cache = lm.init_cache(2, 12, jnp.float32)
    _, cache = chunked_prefill(cache, long_p, slot=1, n_slots=2)
    assert cache["len"].tolist() == [0, 9]
    cache["len"] = cache["len"].at[1].set(0)         # evict
    reused, cache = chunked_prefill(cache, short_p, slot=1, n_slots=2)

    fresh_cache = lm.init_cache(2, 12, jnp.float32)
    fresh, _ = chunked_prefill(fresh_cache, short_p, slot=1, n_slots=2)
    np.testing.assert_allclose(np.asarray(reused)[1], np.asarray(fresh)[1],
                               rtol=1e-5, atol=1e-5)
