"""Multi-tenant adapter serving: one quantized base, many QA-LoRA tenants.

    PYTHONPATH=src python examples/serve_multi_adapter.py

QA-LoRA's group-pooled adapter either merges EXACTLY into the INT4 base
(the single-tenant deployment every other serving example uses) or stays
cleanly separable from it.  This example serves the separable side: an
AdapterStore banks two "fine-tunes" (here synthesized by perturbing the
adapters of a shared init) as stacked device-resident (A, B) rows over
ONE merged INT4 base, and the continuous engine applies a DIFFERENT
adapter per slot in the same dispatch — per-slot indices gather each
slot's (A, B) from the banks inside the QA-LoRA epilogue, with row 0
reserved as the zero "null adapter" for bare-base requests.

The punchline printed at the end: each tenant's mixed-batch stream is
token-for-token identical to serving that tenant ALONE on its merged
single-adapter model — multiplexing is free of cross-tenant interference.
"""

import jax

import repro.configs as C
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import generate_scan
from repro.models.lm import LM
from repro.serving import AdapterStore, ContinuousEngine, make_trace

cfg = C.reduced("gemma3-1b")
lm = LM(cfg)
params = lm.init(jax.random.PRNGKey(0))  # tagged QA-LoRA tree (unmerged)


def finetune(tree, mag, seed):
    """Stand-in for a real fine-tune: perturb only the adapter leaves."""
    cnt = [0]

    def f(path, x):
        if any(getattr(k, "key", None) == "ad" for k in path):
            cnt[0] += 1
            k = jax.random.fold_in(jax.random.PRNGKey(seed), cnt[0])
            return x + mag * jax.random.normal(k, x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(f, tree)


store = AdapterStore(params, capacity=4)   # merges the base on entry
store.register("alice", finetune(params, 0.02, 1))
store.register("bob", finetune(params, 0.03, 2))
print(f"[multi-adapter] store: tenants {list(store.names)} + null "
      f"adapter over one int{cfg.quant.default.bits} base")

# 6 requests cycling alice / bob / bare-base on 3 slots: slots evict and
# refill mid-run, and every dispatch mixes tenants
trace = make_trace(6, cfg.vocab, seed=1, prompt_lens=(3, 5),
                   gen_lens=(6, 4), adapter_ids=("alice", "bob", None),
                   store=store)
engine = ContinuousEngine(lm, store.base, n_slots=3, max_len=16,
                          prefill_chunk=4, decode_burst=4, adapters=store)
for r in trace:
    engine.submit(r.prompt, r.max_new_tokens, eos_id=r.eos_id, rid=r.rid,
                  adapter_id=r.adapter_id)
outputs = engine.run()

mesh = make_local_mesh()
with jax.set_mesh(mesh):
    for r in trace:
        who = store.name_of(r.adapter_id)
        ref, _ = generate_scan(lm, mesh, store.merged(who),
                               r.prompt[None, :], r.max_new_tokens, 16)
        ok = outputs[r.rid] == [int(t) for t in ref[0]]
        print(f"[multi-adapter] req {r.rid} ({who or 'base':5s}): "
              f"{outputs[r.rid]}  == merged-{who or 'base'} reference: {ok}")
        assert ok, "mixed-batch stream diverged from merged reference"

st = engine.stats
print(f"[multi-adapter] {st.tokens_out} tokens, {st.dispatches} dispatches, "
      f"occupancy {st.occupancy:.0%} — {store.n_adapters} tenants + base "
      f"multiplexed per-slot with zero cross-tenant interference")
