"""Speculative decoding on the continuous engine — the slow equivalence
gates: a speculative engine must be TOKEN-IDENTICAL to the
non-speculative greedy engine AND to running each request alone through
the static prefill+scan path, on mixed-length traces where eviction,
refill, chunked prefill and EOS termination all trigger.

Covered variants:
  * gqa self-speculation (intq8 reduced-bits drafter over the shared
    merged base), contiguous slots;
  * the same on the PAGED KV layout (rollback shrinks lens under a page
    table; transient verify rows ride the speculative headroom pages);
  * mla_moe with the MTP head as the drafter (k=1), on the all-dense
    reduced config (the documented MoE batch-composition caveat applies
    to equivalence gates unchanged).
"""

import jax
import pytest

import repro.configs as C
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import merge_model, generate_scan
from repro.models.lm import LM
from repro.serving import ContinuousEngine, make_trace


@pytest.fixture(scope="module")
def served():
    cfg = C.reduced("gemma3-1b")
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


def _drain(eng, trace):
    for r in trace:
        eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
    return eng.run()


def _mixed_trace(cfg):
    """More requests than slots: eviction + refill, ragged prompt and
    gen lengths, EOS ids live (make_trace assigns them)."""
    return make_trace(7, cfg.vocab, seed=3,
                      prompt_lens=(3, 6, 11), gen_lens=(2, 9, 4))


@pytest.mark.slow
def test_spec_engine_matches_plain_and_scan_on_mixed_trace(served):
    """The tentpole gate (contiguous gqa): speculative draft-and-verify
    with an intq8 self-drafter == non-speculative greedy engine == the
    per-request static path, token for token, through eviction+refill."""
    cfg, lm, merged = served
    trace = _mixed_trace(cfg)
    spec = ContinuousEngine(lm, merged, n_slots=3, max_len=27,
                            prefill_chunk=4, decode_burst=1,
                            speculate=3, drafter="*=intq8")
    plain = ContinuousEngine(lm, merged, n_slots=3, max_len=27,
                             prefill_chunk=4, decode_burst=1)
    out_s, out_p = _drain(spec, trace), _drain(plain, trace)
    assert out_s == out_p
    for r in trace:
        assert out_s[r.rid] == _reference(lm, merged, r), f"rid {r.rid}"
    st = spec.stats
    assert st.proposed_tokens > 0
    assert 0.0 <= st.acceptance_rate <= 1.0
    # speculation must have committed at least one multi-token dispatch
    assert st.accepted_tokens > 0


@pytest.mark.slow
def test_spec_engine_matches_plain_and_scan_on_paged_layout(served):
    """The same gate on the paged KV cache: per-slot rollback is a len
    shrink under the page table, and the verify step's transient rows
    land on real pages reserved by the speculative headroom."""
    cfg, lm, merged = served
    trace = _mixed_trace(cfg)
    spec = ContinuousEngine(lm, merged, n_slots=3, max_len=27,
                            prefill_chunk=4, decode_burst=1,
                            speculate=3, drafter="*=intq8", page_size=8)
    plain = ContinuousEngine(lm, merged, n_slots=3, max_len=27,
                             prefill_chunk=4, decode_burst=1, page_size=8)
    out_s, out_p = _drain(spec, trace), _drain(plain, trace)
    assert out_s == out_p
    for r in trace:
        assert out_s[r.rid] == _reference(lm, merged, r), f"rid {r.rid}"
    assert spec.page_table is not None
    assert spec.stats.accepted_tokens > 0


@pytest.mark.slow
def test_mtp_drafter_matches_plain_engine_on_mla_moe():
    """mla_moe with its multi-token-prediction head as the drafter
    (k=1): the MTP proposal rides the SAME fused program as the verify,
    and the stream must stay identical to the non-speculative engine on
    the all-dense reduced config (random-init MTP head -> near-zero
    acceptance; equivalence, not speedup, is the contract)."""
    cfg = C.reduced("deepseek-v3-671b", n_layers=2, n_dense_layers=2,
                    mtp=True)
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    trace = make_trace(4, cfg.vocab, seed=9, prompt_lens=(2, 5),
                       gen_lens=(3, 8))
    spec = ContinuousEngine(lm, merged, n_slots=2, max_len=25,
                            prefill_chunk=4, decode_burst=1,
                            speculate=1, drafter="mtp")
    plain = ContinuousEngine(lm, merged, n_slots=2, max_len=25,
                             prefill_chunk=4, decode_burst=1)
    assert _drain(spec, trace) == _drain(plain, trace)
    assert spec.stats.proposed_tokens > 0
