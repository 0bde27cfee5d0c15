"""Multi-tenant adapter serving: AdapterStore lifecycle, the per-slot
banked QA-LoRA epilogue (kernel + reference), and the mixed-adapter
engine's token-for-token equivalence with merged per-request serving.

The central property under test is QA-LoRA's separability: a group-pooled
adapter either merges EXACTLY into the INT-N base (zeros update only) or
serves UNMERGED via the banked gather — both must produce identical
tokens, so the merged single-adapter tree is the reference for every
mixed-adapter engine run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core import QALoRAParams, dequantize, quantize
from repro.core.qalora import adapter_delta, bank_adapter_delta
from repro.kernels import qalora_slot_matmul
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import generate_scan, merge_model
from repro.models.lm import LM
from repro.serving import (AdapterStore, ContinuousEngine, RequestStatus,
                           ServingFrontend, extract_pack, make_trace)

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback sweep (see _hypothesis_compat)
    from _hypothesis_compat import given, settings, strategies as st


@pytest.fixture(scope="module")
def served():
    cfg = C.reduced("gemma3-1b")
    lm = LM(cfg)
    raw = lm.init(jax.random.PRNGKey(0))  # tagged qalora tree (unmerged)
    return cfg, lm, raw


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


def _store(raw, capacity=3, tenants=(("alpha", 0.02, 1), ("beta", 0.03, 2))):
    store = AdapterStore(raw, capacity=capacity)
    for name, mag, seed in tenants:
        store.register(name, _bump(raw, mag, seed))
    return store


def _reference(lm, merged, req, max_len):
    """One request alone through the static prefill+scan path on a
    merged single-adapter tree."""
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        toks, _ = generate_scan(lm, mesh, merged, req.prompt[None, :],
                                req.max_new_tokens, max_len)
    return [int(t) for t in toks[0]]


# ---------------------------------------------------------------------------
# equivalence gate (slow lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_mixed_adapter_engine_matches_merged_references(served):
    """The tentpole gate: a mixed-adapter trace (two distinct tenants +
    null-adapter requests, more requests than slots so slots evict and
    refill mid-run) through ONE continuous engine is token-for-token
    identical to serving each request alone on its adapter's MERGED
    tree.  Also pins that the two tenants actually diverge — identical
    streams would mean the gather silently served one adapter."""
    cfg, lm, raw = served
    store = _store(raw)
    trace = make_trace(7, cfg.vocab, seed=5, prompt_lens=(3, 5, 4),
                       gen_lens=(6, 4, 5))
    whos = ["alpha", "beta", None, "alpha", "beta", "alpha", None]
    eng = ContinuousEngine(lm, store.base, n_slots=3, max_len=24,
                           prefill_chunk=4, decode_burst=4, adapters=store)
    for r, who in zip(trace, whos):
        eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid,
                   adapter_id=who)
    out = eng.run()
    assert sorted(out) == [r.rid for r in trace]
    for r, who in zip(trace, whos):
        ref = _reference(lm, store.merged(who), r, 24)
        assert out[r.rid] == ref, f"rid {r.rid} adapter {who!r}"
    # same prompt mix, different tenants -> the streams must not all agree
    assert not (out[0] == out[1][:len(out[0])] and
                out[3] == out[4][:len(out[3])]), \
        "alpha and beta produced identical streams — adapters not applied"


@pytest.mark.slow
def test_store_eviction_and_reregister_keep_equivalence(served):
    """Register past capacity (LRU-evicting a drained tenant), then
    serve against the refreshed store: the version counter must force
    the engine to rebind its serving tree, and the NEW tenant's stream
    must match its merged reference while the evicted tenant's id is
    rejected loudly."""
    cfg, lm, raw = served
    store = _store(raw, capacity=2)
    trace = make_trace(2, cfg.vocab, seed=11, prompt_lens=(4,), gen_lens=(5,))
    eng = ContinuousEngine(lm, store.base, n_slots=2, max_len=16,
                           prefill_chunk=4, decode_burst=4, adapters=store)
    eng.submit(trace[0].prompt, 5, rid=0, adapter_id="alpha")
    out = eng.run()
    assert out[0] == _reference(lm, store.merged("alpha"), trace[0], 16)

    alpha_id = store.resolve("alpha")
    store.touch(store.resolve("beta"))          # alpha becomes the LRU
    gamma_id = store.register("gamma", _bump(raw, 0.05, 3))
    assert gamma_id == alpha_id                  # row reuse via LRU evict
    assert "alpha" not in store and "gamma" in store
    with pytest.raises(ValueError, match="unknown adapter"):
        eng.submit(trace[1].prompt, 5, adapter_id="alpha")
    eng.submit(trace[1].prompt, 5, rid=1, adapter_id="gamma")
    out = eng.run()
    assert out[1] == _reference(lm, store.merged("gamma"), trace[1], 16)


# ---------------------------------------------------------------------------
# kernel epilogue vs reference (fast lane)
# ---------------------------------------------------------------------------


def _bank_setup(bits, g, m, k, n, rank=4, n_bank=3, seed=0):
    key = jax.random.PRNGKey(seed)
    qt = quantize(jax.random.normal(key, (k, n)), bits, g)
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, k), jnp.float32)
    a = jax.random.normal(jax.random.fold_in(key, 2),
                          (n_bank, k // g, rank), jnp.float32) * 0.3
    b = jax.random.normal(jax.random.fold_in(key, 3),
                          (n_bank, rank, n), jnp.float32) * 0.3
    a = a.at[0].set(0.0)  # row 0 = null adapter, like the store
    b = b.at[0].set(0.0)
    ids = jnp.asarray([i % n_bank for i in range(m)], jnp.int32)
    return x, qt, a, b, ids


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("g", [32, 64])
def test_slot_kernel_matches_reference_epilogue(bits, g):
    """Fused per-row gather GEMV (m <= GEMV_MAX_M) vs the dequant +
    einsum-gather reference, across the paper's bits x group grid."""
    x, qt, a, b, ids = _bank_setup(bits, g, m=4, k=2 * g * 2, n=64)
    y = qalora_slot_matmul(x, qt, a, b, ids, s=0.7, interpret=True)
    ref = x @ dequantize(qt, jnp.float32) + bank_adapter_delta(
        x, a, b, ids, 0.7, g)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_slot_matmul_large_m_fallback_matches_reference():
    """m past the GEMV row cap routes through qmatmul + banked einsum;
    per-row ids must still be honored exactly (no per-call collapse)."""
    x, qt, a, b, ids = _bank_setup(4, 32, m=24, k=128, n=64)
    y = qalora_slot_matmul(x, qt, a, b, ids, s=1.3, interpret=True)
    ref = x @ dequantize(qt, jnp.float32) + bank_adapter_delta(
        x, a, b, ids, 1.3, 32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_null_adapter_row_is_exact_base():
    """id 0 gathers the zero row: the epilogue must contribute EXACTLY
    nothing (not epsilon) so null-adapter slots serve the bare base."""
    x, qt, a, b, _ = _bank_setup(4, 32, m=4, k=128, n=64)
    ids0 = jnp.zeros((4,), jnp.int32)
    y = qalora_slot_matmul(x, qt, a, b, ids0, s=2.0, interpret=True)
    base = qalora_slot_matmul(x, qt, jnp.zeros_like(a), jnp.zeros_like(b),
                              ids0, s=2.0, interpret=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(base))


# ---------------------------------------------------------------------------
# property: bank gather == per-adapter delta
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(rank=st.integers(1, 6), l_groups=st.integers(1, 4),
       g=st.sampled_from([8, 16, 32]), n_bank=st.integers(1, 5),
       seed=st.integers(0, 2 ** 16))
def test_bank_gather_equals_per_adapter_delta(rank, l_groups, g, n_bank,
                                              seed):
    """For ANY ranks/groups/slot->adapter assignment, gathering (A, B)
    from the stacked banks per row gives the same delta as applying each
    row's own adapter alone — the algebraic contract the whole serving
    path rests on."""
    key = jax.random.PRNGKey(seed)
    k, n, m = l_groups * g, 24, 5
    x = jax.random.normal(key, (m, k), jnp.float32)
    a = jax.random.normal(jax.random.fold_in(key, 1),
                          (n_bank, l_groups, rank), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 2),
                          (n_bank, rank, n), jnp.float32)
    ids = jax.random.randint(jax.random.fold_in(key, 3), (m,), 0, n_bank)
    got = bank_adapter_delta(x, a, b, ids, 1.7, g)
    for i in range(m):
        want = adapter_delta(x[i:i + 1],
                             QALoRAParams(a=a[ids[i]], b=b[ids[i]]), 1.7, g)
        np.testing.assert_allclose(np.asarray(got[i:i + 1]),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# store lifecycle (fast lane)
# ---------------------------------------------------------------------------


def test_store_register_validates_and_resolves(served):
    cfg, lm, raw = served
    store = _store(raw)
    assert store.n_adapters == 2 and set(store.names) == {"alpha", "beta"}
    assert store.resolve(None) == 0 and store.resolve("alpha") >= 1
    assert store.resolve(store.resolve("beta")) == store.resolve("beta")
    with pytest.raises(ValueError, match="unknown adapter"):
        store.resolve("nope")
    with pytest.raises(ValueError, match="unknown adapter id"):
        store.resolve(99)
    assert store.name_of(store.resolve("alpha")) == "alpha"
    assert store.name_of(0) is None


def test_store_rejects_merged_and_foreign_trees(served):
    cfg, lm, raw = served
    merged = merge_model(raw, cfg.quant)
    with pytest.raises(ValueError, match="no QA-LoRA adapters"):
        extract_pack(merged)
    store = _store(raw, tenants=())
    with pytest.raises(ValueError, match="no QA-LoRA adapters"):
        store.register("m", merged)


def test_store_live_guard_and_evict_zeroing(served):
    """Full store + every tenant live -> register fails loudly; evict
    refuses live tenants; a successful evict ZEROES the bank row so its
    merged tree degenerates to the bare base (no stale-tenant leak)."""
    cfg, lm, raw = served
    store = _store(raw, capacity=2)
    store.set_live([store.resolve("alpha"), store.resolve("beta")])
    with pytest.raises(RuntimeError, match="live"):
        store.register("gamma", _bump(raw, 0.05, 3))
    with pytest.raises(RuntimeError, match="live"):
        store.evict("alpha")
    store.set_live([])
    aid = store.resolve("alpha")
    store.evict("alpha")
    with pytest.raises(KeyError):
        store.evict("alpha")
    for bank in store._banks.values():
        assert not np.asarray(bank.a[..., aid, :, :]).any()
        assert not np.asarray(bank.b[..., aid, :, :]).any()


def test_store_reregister_overwrites_in_place(served):
    cfg, lm, raw = served
    store = _store(raw, capacity=2)
    aid = store.resolve("alpha")
    v0 = store.version
    m1 = store.merged("alpha")
    assert store.register("alpha", _bump(raw, 0.08, 9)) == aid
    assert store.version > v0
    m2 = store.merged("alpha")
    diff = jax.tree_util.tree_reduce(
        lambda acc, pair: acc or bool(np.any(pair)), jax.tree.map(
            lambda x, y: np.asarray(x != y).any(), m1, m2), False)
    assert diff, "re-register left the merged tree unchanged"


def test_serving_tree_structure_is_mix_invariant(served):
    """Remapping slots to adapters must swap array VALUES only: the
    pytree structure (the jit retrace key) is identical across mixes,
    which is what keeps the compiled steps warm on adapter churn."""
    cfg, lm, raw = served
    store = _store(raw)
    t1 = store.with_slot_ids(np.array([0, store.resolve("alpha")]))
    t2 = store.with_slot_ids(np.array([store.resolve("beta"), 0]))
    s1 = jax.tree_util.tree_structure(t1)
    s2 = jax.tree_util.tree_structure(t2)
    assert s1 == s2
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree_util.tree_leaves(t1), jax.tree_util.tree_leaves(t2)))


# ---------------------------------------------------------------------------
# engine / frontend / trace plumbing (fast lane)
# ---------------------------------------------------------------------------


def test_engine_submit_rejects_unknown_adapter(served):
    cfg, lm, raw = served
    store = _store(raw)
    eng = ContinuousEngine(lm, store.base, n_slots=2, max_len=12,
                           adapters=store)
    with pytest.raises(ValueError, match="unknown adapter"):
        eng.submit(np.array([5, 6], np.int32), 2, adapter_id="nope")
    merged = merge_model(raw, cfg.quant)
    bare = ContinuousEngine(lm, merged, n_slots=2, max_len=12)
    with pytest.raises(ValueError, match="no AdapterStore"):
        bare.submit(np.array([5, 6], np.int32), 2, adapter_id="alpha")


def test_frontend_rejects_unknown_adapter_at_submit(served):
    """A typo'd tenant comes back as a REJECTED ticket with the store's
    error in ``.error`` — at submit time, not as a mid-serve crash."""
    cfg, lm, raw = served
    store = _store(raw)
    fe = ServingFrontend(lm, store.base, n_slots=2, max_len=16,
                         prefill_chunk=4, decode_burst=2, queue_cap=8,
                         adapters=store).start()
    try:
        bad = fe.submit(np.array([5, 6], np.int32), 2, adapter_id="nope")
        assert bad.status is RequestStatus.REJECTED
        assert "unknown adapter" in bad.error
        ok = fe.submit(np.array([5, 6, 7], np.int32), 3, adapter_id="alpha")
        ok.done.wait(timeout=120)
        assert ok.status is RequestStatus.FINISHED
        assert ok.adapter_id == store.resolve("alpha")
        assert len(ok.tokens) == 3
    finally:
        fe.stop()


def test_make_trace_adapter_ids_cycle_and_validate(served):
    cfg, lm, raw = served
    store = _store(raw)
    trace = make_trace(5, cfg.vocab, seed=1,
                       adapter_ids=["alpha", None, "beta"], store=store)
    al, be = store.resolve("alpha"), store.resolve("beta")
    assert [r.adapter_id for r in trace] == [al, 0, be, al, 0]
    with pytest.raises(ValueError, match="store"):
        make_trace(3, cfg.vocab, adapter_ids=["alpha"])
    with pytest.raises(ValueError, match="unknown adapter"):
        make_trace(3, cfg.vocab, adapter_ids=["nope"], store=store)
    with pytest.raises(ValueError, match="non-empty"):
        make_trace(3, cfg.vocab, adapter_ids=[], store=store)


def test_adapter_serving_guards_unsupported_families(served):
    """Families whose step reads weights OUTSIDE the per-slot params
    tree (encdec's out-of-batch encoder, MLA's hoisted absorbed
    weights) must refuse adapter serving loudly at construction."""
    ecfg = C.reduced("seamless-m4t-medium")
    elm = LM(ecfg)
    eraw = elm.init(jax.random.PRNGKey(0))
    estore = AdapterStore(eraw, capacity=2)
    with pytest.raises(NotImplementedError, match="encdec"):
        ContinuousEngine(elm, estore.base, n_slots=1, max_len=8,
                         max_src=4, adapters=estore)
    mcfg = C.reduced("deepseek-v3-671b")
    mlm = LM(mcfg)
    mraw = mlm.init(jax.random.PRNGKey(0))
    mstore = AdapterStore(mraw, capacity=2)
    with pytest.raises(NotImplementedError, match="absorbed"):
        ContinuousEngine(mlm, mstore.base, n_slots=1, max_len=8,
                         adapters=mstore)
