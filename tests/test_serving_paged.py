"""Paged KV cache serving: the paged engine must be token-for-token
identical to the contiguous layout (and the static ``generate_scan``
path) on mixed traces with eviction + refill, for slotted-KV (gqa) AND
compressed-KV (mla) families; hash-based prefix reuse must prefill a
shared prompt's full pages exactly once; admission must back off LOUDLY
when the pool is dry (and still complete once pages free up); and
eviction must release pages + republish live adapter ids atomically so
an over-capacity register never evicts a still-referenced page or
adapter."""

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import generate_scan, merge_model
from repro.models.lm import LM
from repro.serving import (AdapterStore, ContinuousEngine, Request,
                           make_trace)


@pytest.fixture(scope="module")
def served():
    cfg = C.reduced("gemma3-1b")
    lm = LM(cfg)
    raw = lm.init(jax.random.PRNGKey(0))  # tagged qalora tree (unmerged)
    return cfg, lm, raw, merge_model(raw, cfg.quant)


@pytest.fixture(scope="module")
def served_mla():
    """All-dense reduced deepseek-v3: MLA attention, plain MLP blocks
    (the config where engine equivalence is exact — see
    tests/test_serving_mla.py for the MoE caveat)."""
    cfg = C.reduced("deepseek-v3-671b", n_layers=2, n_dense_layers=2,
                    mtp=False)
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    return cfg, lm, merged


def _reference(lm, merged, req):
    """One request alone through the static prefill+scan path."""
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        toks, _ = generate_scan(lm, mesh, merged, req.prompt[None, :],
                                req.max_new_tokens,
                                len(req.prompt) + req.max_new_tokens)
    return [int(t) for t in toks[0]]


def _serve(lm, merged, trace, **kw):
    eng = ContinuousEngine(lm, merged, **kw)
    for r in trace:
        eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
    return eng, eng.run()


# ---------------------------------------------------------------------------
# equivalence gates (slow lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_paged_engine_matches_contiguous_and_scan_gqa(served):
    """The tentpole gate (slotted KV): a mixed trace with more requests
    than slots (eviction + refill, chunked prefill, decode bursts all
    trigger) through the PAGED engine emits streams identical to the
    contiguous engine AND to each request alone via generate_scan."""
    cfg, lm, _, merged = served
    trace = make_trace(7, cfg.vocab, seed=3,
                       prompt_lens=(3, 6, 11), gen_lens=(2, 9, 4))
    kw = dict(n_slots=3, max_len=24, prefill_chunk=4, decode_burst=4)
    _, cont = _serve(lm, merged, trace, **kw)
    eng, paged = _serve(lm, merged, trace, page_size=4, **kw)
    assert paged == cont
    for r in trace:
        assert paged[r.rid] == _reference(lm, merged, r), f"rid {r.rid}"
    eng.page_table.check_invariants()
    assert eng.page_table.n_used == 0  # drained: every page released


@pytest.mark.slow
def test_paged_engine_matches_contiguous_mla(served_mla):
    """Compressed-KV paging: the MLA cache's ``c``/``kr`` leaves ride the
    same page pool mechanics; streams match the contiguous engine and the
    static path on the all-dense deepseek config."""
    cfg, lm, merged = served_mla
    trace = make_trace(5, cfg.vocab, seed=9,
                       prompt_lens=(3, 7), gen_lens=(3, 6))
    kw = dict(n_slots=2, max_len=16, prefill_chunk=4, decode_burst=4)
    _, cont = _serve(lm, merged, trace, **kw)
    eng, paged = _serve(lm, merged, trace, page_size=4, **kw)
    assert paged == cont
    for r in trace:
        assert paged[r.rid] == _reference(lm, merged, r), f"rid {r.rid}"
    eng.page_table.check_invariants()


# ---------------------------------------------------------------------------
# prefix reuse / backoff (fast lane: tiny reduced model)
# ---------------------------------------------------------------------------


def test_prefix_reuse_prefills_shared_pages_exactly_once(served):
    """n_slots=1 serializes the trace, so every request after the first
    must hit the previous occupant's registered prompt pages: the shared
    8-token prefix (2 full pages) prefills ONCE, each successor skips it
    (reused_tokens_total counts exactly (N-1) * 8), and the engine does
    measurably less prefill work — with identical tokens."""
    cfg, lm, _, merged = served
    trace = make_trace(3, cfg.vocab, seed=5, shared_prefix=8,
                       prompt_lens=(3,), gen_lens=(4,))
    kw = dict(n_slots=1, max_len=16, prefill_chunk=4, decode_burst=4)
    ec, cont = _serve(lm, merged, trace, **kw)
    ep, paged = _serve(lm, merged, trace, page_size=4, **kw)
    assert paged == cont
    pt = ep.page_table
    # cap: (11 - 1) // 4 = 2 full pages = 8 tokens reused per successor
    assert pt.reused_tokens_total == (len(trace) - 1) * 8
    # the skipped chunks are real model-step savings
    assert ep.stats.busy_slot_steps < ec.stats.busy_slot_steps
    assert ep.stats.model_steps < ec.stats.model_steps
    pt.check_invariants()


def test_admission_backoff_completes_when_pages_free(served):
    """A pool too small for two concurrent requests forces the FIFO head
    to back off (counted, nothing overwritten) until the first request
    finishes and releases pages — every request still completes, with the
    same tokens as the contiguous engine."""
    cfg, lm, _, merged = served
    trace = make_trace(3, cfg.vocab, seed=7, prompt_lens=(4,), gen_lens=(4,))
    kw = dict(n_slots=2, max_len=16, prefill_chunk=4, decode_burst=4)
    _, cont = _serve(lm, merged, trace, **kw)
    # 3 usable pages; each request needs pages_for(8, 4) = 2 -> the second
    # admission cannot fit while the first is in flight
    eng, paged = _serve(lm, merged, trace, page_size=4, n_pages=4, **kw)
    assert paged == cont
    assert eng.page_table.alloc_backoffs >= 1
    assert sorted(len(v) for v in paged.values()) == [4, 4, 4]
    eng.page_table.check_invariants()


def test_submit_rejects_request_the_pool_can_never_cover(served):
    """An oversized request fails loudly AT SUBMIT (like the max_len
    guard): waiting for pages that can never exist would deadlock the
    FIFO queue."""
    cfg, lm, _, merged = served
    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=16,
                           page_size=4, n_pages=3)  # 2 usable pages
    with pytest.raises(ValueError, match="page pool"):
        eng.submit(np.arange(4, 12, dtype=np.int32), 4)  # needs 3 pages
    # within-pool requests still pass the guard
    eng.submit(np.arange(4, 8, dtype=np.int32), 4)       # 2 pages


def test_rwkv_engine_refuses_paging():
    """rwkv carries no length-indexed CACHE leaves (pure recurrent
    state): a paged engine over it would page nothing, so construction
    fails loudly instead of silently serving an unpaged pool."""
    cfg = C.reduced("rwkv6-7b")
    lm = LM(cfg)
    raw = lm.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="CACHE leaves"):
        ContinuousEngine(lm, raw, n_slots=2, max_len=16, page_size=4)


# ---------------------------------------------------------------------------
# atomic eviction: pages + live adapter ids (satellite 3)
# ---------------------------------------------------------------------------


def _bump(tree, mag, seed):
    """A distinct 'fine-tune': perturb every adapter (``ad``) leaf with
    seeded noise, leaving the quantized base untouched."""
    cnt = [0]

    def f(path, x):
        if any(getattr(k, "key", None) == "ad" for k in path):
            cnt[0] += 1
            k = jax.random.fold_in(jax.random.PRNGKey(seed), cnt[0])
            return x + mag * jax.random.normal(k, x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(f, tree)


def test_evict_never_frees_still_referenced_shared_page(served):
    """Two slots sharing prefix pages (sequential admission of the same
    prompt): cancelling the FIRST occupant drops its references but must
    not free the shared pages the survivor still reads — and the
    survivor's stream is exactly what it emits with no churn at all."""
    cfg, lm, _, merged = served
    prompt = np.arange(10, 21, dtype=np.int32)  # 11 tokens: 2 full pages
    ref = _reference(lm, merged, Request(prompt=prompt, max_new_tokens=4))

    eng = ContinuousEngine(lm, merged, n_slots=2, max_len=16,
                           prefill_chunk=4, decode_burst=4, page_size=4)
    eng.submit(prompt, 5, rid=0)
    while eng.sched.slots[0] is None or eng.sched.slots[0].prefilling:
        eng.step_once()  # slot 0 decoding: its prompt pages registered
    eng.submit(prompt, 4, rid=1)
    eng.step_once()      # admits slot 1 with a prefix hit on slot 0's pages
    pt = eng.page_table
    assert pt.reused_tokens_total == 8  # (11-1)//4 = 2 shared pages
    shared = [int(p) for p in pt.page_row(0)[:2]]
    assert [int(p) for p in pt.page_row(1)[:2]] == shared
    assert all(pt.ref[p] == 2 for p in shared)

    free_before = pt.n_free
    assert eng.evict_slot(0) is not None  # cancel the page writer
    # shared pages survive (slot 1 still holds a ref), private ones free
    assert all(pt.ref[p] == 1 for p in shared)
    assert pt.n_free > free_before
    pt.check_invariants()

    out = eng.run()
    assert out[1] == ref  # survivor untouched by the eviction churn
    assert 0 not in out   # the cancelled request never produced output
    assert pt.n_used == 0
    pt.check_invariants()


def test_evict_releases_pages_and_adapters_atomically(served):
    """Cancel-then-register-over-capacity: with both resident adapters
    live in slots, register() must refuse; after ``engine.evict_slot``
    (ONE call: pages released + live ids republished) the register
    succeeds by evicting the CANCELLED request's adapter — never the
    still-live one.  And the adapter id salts the prefix hashes, so the
    two tenants serving the IDENTICAL prompt share zero pages (tenant
    B must never read KV that tenant A's weights computed)."""
    cfg, lm, raw, _ = served
    prompt = np.arange(10, 21, dtype=np.int32)  # 11 tokens

    def fresh():
        store = AdapterStore(raw, capacity=2)
        store.register("alpha", _bump(raw, 0.02, 1))
        store.register("beta", _bump(raw, 0.03, 2))
        eng = ContinuousEngine(lm, store.base, n_slots=2, max_len=16,
                               prefill_chunk=4, decode_burst=4,
                               adapters=store, page_size=4)
        return store, eng

    # reference: beta's request alone, same paged engine, no churn
    store, eng = fresh()
    eng.submit(prompt, 4, rid=1, adapter_id="beta")
    ref = eng.run()[1]

    store, eng = fresh()
    eng.submit(prompt, 5, rid=0, adapter_id="alpha")
    while eng.sched.slots[0] is None or eng.sched.slots[0].prefilling:
        eng.step_once()  # slot 0 decoding: alpha's prompt pages registered
    eng.submit(prompt, 4, rid=1, adapter_id="beta")
    eng.step_once()
    pt = eng.page_table
    # salted hashes: beta's identical prompt hits NOTHING of alpha's
    assert pt.reused_tokens_total == 0
    assert not ((set(map(int, pt.page_row(0))) - {0})
                & (set(map(int, pt.page_row(1))) - {0}))

    # both adapters live -> the store must refuse a third tenant
    with pytest.raises(RuntimeError, match="live"):
        store.register("gamma", _bump(raw, 0.04, 3))

    n_used = pt.n_used
    assert eng.evict_slot(0) is not None  # cancel alpha's request
    assert pt.n_used < n_used             # pages back, same call
    pt.check_invariants()
    # the SAME call republished live ids: gamma now fits, beta survives
    store.register("gamma", _bump(raw, 0.04, 3))
    assert store.resolve("beta") and store.resolve("gamma")
    with pytest.raises(ValueError):
        store.resolve("alpha")  # the cancelled tenant was the evictee

    out = eng.run()
    assert out[1] == ref  # survivor untouched by the evict/register churn
    assert pt.n_used == 0
    pt.check_invariants()
