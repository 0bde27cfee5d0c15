"""Recurrent-family continuous batching over the unified SlotState:
mamba_hybrid (Zamba2 geometry: Mamba2 recurrences + slotted shared-attn
KV) and rwkv (RWKV6 time/channel-mix recurrences) through
``LM.step_ragged``, token-for-token against the static per-request path,
with slot eviction reinitializing the recurrence via ``SlotState.reset``.

Also pins the decode_step -> step_ragged C=1 delegation for EVERY family
(no family-specific decode math outside step_ragged) and the SlotState
reset/snapshot/advance contract itself.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import merge_model, generate_scan
from repro.models.lm import LM
from repro.models.slot_state import CACHE, STATE, LEN
from repro.serving import ContinuousEngine, make_trace

ALL_FAMILY_ARCHS = ["gemma3-1b", "mixtral-8x22b", "deepseek-v3-671b",
                    "zamba2-7b", "rwkv6-7b", "seamless-m4t-medium"]


@pytest.fixture(scope="module", params=["zamba2-7b", "rwkv6-7b"])
def served_recurrent(request):
    cfg = C.reduced(request.param)
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
# engine equivalence (slow lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_recurrent_engine_matches_per_request_scan_on_mixed_trace(
        served_recurrent):
    """The tentpole gate: a mixed-length trace with more requests than
    slots (eviction + refill + chunked prefill all trigger) through the
    per-slot recurrence emits token streams identical to running each
    request alone through ``generate_scan`` — no stale recurrence after
    a slot refill."""
    cfg, lm, merged = served_recurrent
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
def test_recurrent_engine_invariant_to_chunk_and_burst(served_recurrent):
    """prefill_chunk / decode_burst are pure scheduling knobs for the
    recurrent slot state too: any setting gives identical streams."""
    cfg, lm, merged = served_recurrent
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
def test_slot_refill_reinitializes_recurrence(served_recurrent):
    """Prefill a long request into a slot, evict it via SlotState.reset,
    prefill a short one into the SAME slot: the logits must equal a
    fresh-cache run — the previous occupant's recurrence (and its conv /
    token-shift windows) must be gone, not merely length-masked."""
    cfg, lm, merged = served_recurrent
    rng = np.random.default_rng(17)
    long_p = rng.integers(4, cfg.vocab, size=(1, 9)).astype(np.int32)
    short_p = rng.integers(4, cfg.vocab, size=(1, 4)).astype(np.int32)
    step = jax.jit(lm.step_ragged)
    ss = lm.slot_state()

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
    cache = ss.reset(cache, np.array([False, True]))     # evict slot 1
    assert cache["len"].tolist() == [0, 0]
    reused, cache = chunked_prefill(cache, short_p, slot=1, n_slots=2)

    fresh_cache = lm.init_cache(2, 12, jnp.float32)
    fresh, _ = chunked_prefill(fresh_cache, short_p, slot=1, n_slots=2)
    np.testing.assert_allclose(np.asarray(reused)[1], np.asarray(fresh)[1],
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fast-lane engine smokes (CI: the recurrent path can't silently regress
# between full-lane runs; mirrors PR 4's mla_moe smoke)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["rwkv6-7b", "zamba2-7b"])
def test_recurrent_engine_smoke_fast(arch):
    """Fast-lane gate: the continuous engine serves the recurrent family
    end to end — admission, chunked prefill, bursts, eviction + refill —
    and every request completes its full token budget."""
    cfg = C.reduced(arch)
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


def test_idle_slots_freeze_recurrent_state_bit_exactly():
    """n_new == 0 must be IDENTITY on the recurrence (decay forced to 1,
    input contribution to 0) — an idle slot's state after a step is
    bit-identical, not merely close."""
    cfg = C.reduced("rwkv6-7b")
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    cache = lm.init_cache(2, 8, jnp.float32)
    toks = jnp.asarray(np.full((2, 1), 5, np.int32))
    # give both slots one real token of state first
    _, cache = lm.step_ragged(merged, cache, toks, jnp.array([1, 1]))
    before = jax.tree.map(np.asarray, lm.slot_state().snapshot(cache, 0))
    # slot 0 idles while slot 1 decodes
    _, cache = lm.step_ragged(merged, cache, toks, jnp.array([0, 1]))
    after = jax.tree.map(np.asarray, lm.slot_state().snapshot(cache, 0))
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# decode_step == C=1 ragged delegation, for EVERY family (acceptance pin)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ALL_FAMILY_ARCHS)
def test_decode_step_is_pure_delegation_to_step_ragged(arch, monkeypatch):
    """decode_step contains NO family-specific decode math: for every
    family it is exactly step_ragged at C=1, n_new == 1."""
    cfg = C.reduced(arch)
    lm = LM(cfg)
    calls = []

    def fake(self, params, cache, tokens, n_new, aux=None):
        calls.append((tuple(tokens.shape), np.asarray(n_new).tolist(), aux))
        return "SENTINEL"

    monkeypatch.setattr(LM, "step_ragged", fake)
    cache = {"len": jnp.array([2, 5], jnp.int32)}
    out = lm.decode_step(None, cache, jnp.zeros((2, 1), jnp.int32),
                         aux="AUX")
    assert out == "SENTINEL"
    assert calls == [((2, 1), [1, 1], "AUX")], arch


# ---------------------------------------------------------------------------
# SlotState contract units
# ---------------------------------------------------------------------------


def _filled_cache(ss, fam):
    cache = ss.init(3, 8, jnp.float32, src_cap=4 if fam == "encdec" else None)
    return jax.tree.map(lambda a: jnp.ones_like(a), cache)


@pytest.mark.parametrize("arch", ALL_FAMILY_ARCHS)
def test_slot_state_reset_zeroes_state_and_len_not_cache(arch):
    """reset(slot_mask): LEN and STATE leaves of the masked slots go to
    their init value (zero); unmasked slots and all length-indexed CACHE
    leaves are untouched (stale rows are masked by length, never read)."""
    cfg = C.reduced(arch)
    ss = LM(cfg).slot_state()
    filled = _filled_cache(ss, cfg.family)
    reset = ss.reset(filled, np.array([True, False, True]))
    spec = ss.layout(*ss._dims(filled))

    def check(s, before, after):
        b, a = np.asarray(before), np.asarray(after)
        if s.kind == CACHE:
            np.testing.assert_array_equal(a, b)
            return
        for slot, wiped in ((0, True), (1, False), (2, True)):
            got = np.take(a, slot, axis=s.slot_axis)
            want = (np.zeros_like(got) if wiped
                    else np.take(b, slot, axis=s.slot_axis))
            np.testing.assert_array_equal(got, want)

    jax.tree.map(check, spec, filled, reset)
    # every family has at least one resettable leaf (its length)
    kinds = {s.kind for s in jax.tree.leaves(spec)}
    assert LEN in kinds
    if cfg.family in ("mamba_hybrid", "rwkv", "encdec"):
        assert STATE in kinds


@pytest.mark.parametrize("arch", ["gemma3-1b", "zamba2-7b", "rwkv6-7b",
                                  "seamless-m4t-medium"])
def test_slot_state_snapshot_drops_slot_axis(arch):
    cfg = C.reduced(arch)
    ss = LM(cfg).slot_state()
    cache = ss.init(3, 8, jnp.float32,
                    src_cap=4 if cfg.family == "encdec" else None)
    spec = ss.layout(*ss._dims(cache))
    snap = ss.snapshot(cache, 1)
    jax.tree.map(
        lambda s, full, one: np.testing.assert_array_equal(
            np.asarray(one),
            np.take(np.asarray(full), 1, axis=s.slot_axis)),
        spec, cache, snap)


def test_slot_state_advance_bumps_only_lengths():
    ss = LM(C.reduced("gemma3-1b")).slot_state()
    cache = ss.init(2, 8, jnp.float32)
    out = ss.advance(cache, cache["layers"], np.array([3, 0]))
    assert out["len"].tolist() == [3, 0]
    assert out["layers"] is cache["layers"]


def test_supports_ragged_is_engine_source_of_truth(monkeypatch):
    """The engine's family guard derives from LM.supports_ragged — no
    separate supported-families constant to desync.  A family the LM
    does not claim raises with the family named."""
    import repro.serving.engine as E
    assert not hasattr(E, "SLOTTED_FAMILIES")  # the old constant is gone
    cfg = C.reduced("gemma3-1b")
    lm = LM(cfg)
    monkeypatch.setattr(LM, "supports_ragged", lambda self: False)
    with pytest.raises(NotImplementedError, match="'gqa'"):
        ContinuousEngine(lm, {}, n_slots=1, max_len=8)
