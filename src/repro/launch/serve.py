"""Batched serving driver: prefill + scan decode with the merged QA-LoRA model.

Demonstrates the paper's deployment claim: after `merge`, the served model
is STILL INT-N (integer codes + scales unchanged, zeros updated) — no
FP16 fallback, no PTQ step, identical outputs to the adapter model
(asserted at startup with --verify).

Engines (`--engine`):
  static      (default) one fixed-shape batch start-to-finish: jitted
              prefill over the whole prompt (`steps.make_prefill_step`),
              then `steps.make_generate_step` — a `jax.lax.scan` over
              `lm.decode_step` compiling the entire greedy generation
              into ONE program.  A request that finishes early wastes its
              slot until the longest request completes.  Kept as the
              reference path.  `--loop` falls back further, to the legacy
              per-token loop (the timing/equivalence reference).
  frontend    the continuous engine behind the fault-tolerant async
              frontend (`repro.serving.ServingFrontend`): bounded
              admission queue (--queue-cap; overload rejects with the
              queue depth in the error), per-request TTFT/total
              deadlines (--ttft-deadline-ms/--deadline-ms; expired
              slots are evicted like EOS), typed terminal statuses
              (FINISHED/REJECTED/TIMED_OUT/CANCELLED/FAILED), and
              deterministic crash recovery — --inject-faults schedules
              a seeded mid-trace engine crash plus straggler latency
              (repro.runtime.fault.FaultInjector) and the frontend
              replays in-flight requests token-identically.
  continuous  in-flight batching (`repro.serving.ContinuousEngine`):
              queued requests are admitted into free cache slots
              mid-flight, prompts prefill in chunks alongside decoding
              slots, and each request terminates at its own EOS/max-len
              with immediate slot eviction + refill.  Serves EVERY
              family through the unified per-slot SlotState: gqa /
              gqa_moe (per-head KV), mla_moe (deepseek-style
              compressed-KV, absorbed attention with the effective
              W_uk/W_uv dequantized once up front), mamba_hybrid / rwkv
              (per-slot recurrences, reinitialized on eviction) and
              encdec (frozen per-slot cross cache).  Token streams are
              identical to running each request alone through the
              static path (tests/test_serving_engine.py,
              tests/test_serving_mla.py, tests/test_serving_recurrent.py,
              tests/test_serving_encdec.py; MoE layers carry the
              capacity-routing caveat below).  See the README
              family-support matrix for the per-family state layout.

CPU demo:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
      --requests 4 --prompt-len 16 --gen-len 8 --verify
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
      --engine continuous --requests 8 --slots 4 --gen-len 12
  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v3-671b \
      --reduced --engine continuous --requests 6 --slots 2 --gen-len 6
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --reduced \
      --engine continuous --requests 8 --slots 3 --gen-len 8
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
      --engine frontend --requests 8 --slots 2 --gen-len 8 \
      --queue-cap 4 --deadline-ms 30000 --inject-faults
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
      --engine continuous --requests 9 --slots 3 --gen-len 8 \
      --adapters alice=demo:1,bob=demo:2
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def _decode_step(lm, params, cache, tok):
    return lm.decode_step(params, cache, tok)


# module-level jit (the engine's _JIT_* discipline): every caller shares
# one trace cache keyed on the hashable LM config
_JIT_DECODE = jax.jit(_decode_step, static_argnums=0)


# Merge check tolerance, relative to the logit scale max|logits|.  The
# merge is exact in real arithmetic (Appendix B); the two models differ
# only by rounding.  The merged zero points are stored in the scale dtype
# (bf16 on the published configs, where the paper stores FP16): half an
# ulp, 2^-9 of each zero, per (group, column).  And the adapter model adds
# its rank-r term in the compute dtype beside the base matmul, while the
# merged model folds it into the weights first.  Through L bf16 layers
# both stay within a few percent of the logit scale; a merge that drops
# or misplaces an adapter moves logits by the adapter's whole effect.
MERGE_LOGIT_RTOL = 5e-2


def merge_error(logits_adapter, logits_merged) -> float:
    """max|adapter - merged| logits over the logit scale max|adapter|."""
    a = jnp.asarray(logits_adapter, jnp.float32)
    m = jnp.asarray(logits_merged, jnp.float32)
    return float(jnp.max(jnp.abs(a - m)) / jnp.max(jnp.abs(a)))


def merge_model(params, pol=None):
    """Merge every adapter into its quantized base (exact; Appendix B).

    Tag-driven walk over the scheme registry (``schemes.merge_tree``);
    ``pol`` is only consulted for legacy untagged checkpoints."""
    from repro.core.schemes import merge_tree
    return merge_tree(params, pol=pol)


def make_scan_generator(lm, mesh, params, batch_shape, gen_len: int,
                        max_len: int, cache_dtype=jnp.float32):
    """Build the jitted prefill + scan-generate pair ONCE for a prompt
    shape; returns ``run(prompts) -> (tokens [B, gen_len], seconds)``.

    The prompt runs through `lm.prefill` as one batched forward (collecting
    every layer's cache), the prefill cache is embedded into the
    full-capacity decode cache, and the whole greedy generation runs as a
    single compiled `lax.scan` (see `LM.generate`).  Reusing the returned
    callable skips retracing — the first call compiles, later calls are
    pure decode (the benchmark times those).
    """
    from repro.launch import steps as S

    b, prompt_len = batch_shape
    batch_abs = {"tokens": jax.ShapeDtypeStruct((b, prompt_len), jnp.int32)}
    pabs = jax.eval_shape(lambda: params)
    prefill_for, _ = S.make_prefill_step(lm, mesh, params_abstract=pabs)
    prefill, _ = prefill_for(batch_abs)
    generate_for, _ = S.make_generate_step(lm, mesh, gen_len,
                                           params_abstract=pabs)
    cache_abs = jax.eval_shape(lambda: lm.init_cache(b, max_len,
                                                     dtype=cache_dtype))
    generate, _ = generate_for(cache_abs)

    def run(prompts):
        t0 = time.time()
        logits, pre_cache = prefill(params, {"tokens": jnp.asarray(prompts)})
        cache = lm.merge_prefill_cache(
            pre_cache, lm.init_cache(b, max_len, dtype=cache_dtype))
        toks, _ = generate(params, cache, logits)
        toks = np.asarray(jax.block_until_ready(toks))
        return toks, time.time() - t0

    return run


def generate_scan(lm, mesh, params, prompts, gen_len: int, max_len: int,
                  cache_dtype=jnp.float32):
    """One-shot prefill + scan decode (see :func:`make_scan_generator`)."""
    return make_scan_generator(lm, mesh, params, prompts.shape, gen_len,
                               max_len, cache_dtype)(prompts)


def make_loop_generator(lm, params, gen_len: int, max_len: int,
                        cache_dtype=jnp.float32):
    """Legacy per-token Python loop (prefill via decode steps), built once
    so repeat calls reuse the single jitted decode step.

    Kept as the reference implementation: the scan path must be
    token-identical to this (tests/test_serve_decode.py) and the decode
    benchmark reports its per-token dispatch cost against the scan path.
    """
    def step(params, cache, tok):
        return _JIT_DECODE(lm, params, cache, tok)

    def run(prompts):
        b, prompt_len = prompts.shape
        if gen_len <= 0:
            return np.zeros((b, 0), np.int32), 0.0
        cache = lm.init_cache(b, max_len, dtype=cache_dtype)
        toks = jnp.asarray(prompts)
        out = []
        logits = None
        t0 = time.time()
        for i in range(prompt_len + gen_len - 1):
            nxt = (toks[:, i:i + 1] if i < prompt_len
                   else jnp.argmax(logits, -1)[:, None].astype(jnp.int32))
            if i >= prompt_len:
                out.append(np.asarray(nxt)[:, 0])
            logits, cache = step(params, cache, nxt)
        out.append(np.asarray(jnp.argmax(logits, -1)))
        return np.stack(out, 1), time.time() - t0

    return run


def generate_loop_reference(lm, params, prompts, gen_len: int, max_len: int,
                            cache_dtype=jnp.float32):
    """One-shot per-token reference loop (see :func:`make_loop_generator`).
    Returns (tokens [B, gen_len], seconds)."""
    return make_loop_generator(lm, params, gen_len, max_len,
                               cache_dtype)(prompts)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--engine", choices=("static", "continuous", "frontend"),
                    default="static",
                    help="static: one fixed-shape batch (reference); "
                         "continuous: in-flight batching with slot refill; "
                         "frontend: continuous engine behind the "
                         "fault-tolerant async frontend (deadlines, "
                         "backpressure, crash recovery)")
    ap.add_argument("--slots", type=int, default=0,
                    help="continuous engine KV slots (default "
                         "min(4, requests))")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="continuous engine prompt chunk size")
    ap.add_argument("--decode-burst", type=int, default=None,
                    help="continuous engine fused decode steps per dispatch "
                         "(clamped down to a power of two; default 8, "
                         "forced to 1 under --speculate)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="continuous/frontend: draft this many tokens per "
                         "slot per decode dispatch and verify them all in "
                         "ONE ragged step (greedy spec decode — token "
                         "streams identical to non-speculative greedy). "
                         "Incompatible with --decode-burst > 1")
    ap.add_argument("--draft-policy", default="",
                    help="drafter for --speculate: 'mtp' (mla_moe's "
                         "multi-token-prediction head, k=1 only) or a "
                         "PolicyTree spec like '*=intq8' quantizing the "
                         "merged base into a cheap self-speculation "
                         "drafter.  Default: mtp when the arch has an MTP "
                         "head, else '*=intq8'")
    ap.add_argument("--page-size", type=int, default=0,
                    help="continuous/frontend: page the KV cache into "
                         "blocks of this many tokens (0 = contiguous "
                         "per-slot slabs).  Pages are pooled across slots "
                         "with hash-based prefix reuse; token streams are "
                         "identical to the contiguous layout")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="paged KV pool size incl. the reserved null page "
                         "(0 = match contiguous capacity: slots x "
                         "ceil(max_len/page_size) + 1; smaller "
                         "oversubscribes — admission backs off when dry)")
    ap.add_argument("--queue-cap", type=int, default=64,
                    help="frontend admission bound: submits past this "
                         "many waiting requests are REJECTED with the "
                         "queue depth in the error")
    ap.add_argument("--deadline-ms", type=float, default=0,
                    help="frontend per-request total deadline (0 = none); "
                         "an expired slot is evicted like EOS")
    ap.add_argument("--ttft-deadline-ms", type=float, default=0,
                    help="frontend per-request time-to-first-token "
                         "deadline (0 = none)")
    ap.add_argument("--inject-faults", action="store_true",
                    help="frontend only: seeded FaultInjector (one "
                         "mid-trace engine crash + straggler latency); "
                         "recovery replays in-flight requests "
                         "token-identically")
    ap.add_argument("--adapters", default="",
                    help="multi-tenant serving (continuous/frontend only): "
                         "comma list of name=spec adapter packs served "
                         "UNMERGED over one shared quantized base (a "
                         "different adapter per slot in the same "
                         "dispatch).  spec is 'demo:<seed>' — synthesize "
                         "a distinct fine-tune by perturbing the adapters "
                         "with seeded noise — or a checkpoint path saved "
                         "by repro.checkpoint.save_pytree from a trained "
                         "tagged tree.  Requests cycle through the "
                         "tenants (plus the bare base) round-robin, e.g. "
                         "--adapters alice=demo:1,bob=demo:2")
    ap.add_argument("--loop", action="store_true",
                    help="use the legacy per-token loop instead of scan")
    ap.add_argument("--policy", default="",
                    help='per-layer policy rules, e.g. '
                         '"*=int4,*/attn/wo=int8,lm_head=fp"')
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    import repro.configs as C
    from repro.core.schemes import PolicyTree
    from repro.launch.mesh import make_local_mesh
    from repro.models.lm import LM

    cfg = C.reduced(args.arch) if args.reduced else C.get(args.arch)
    if args.policy:
        cfg = cfg.scaled(quant=PolicyTree.parse(args.policy,
                                                base=cfg.quant.default))
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    pol = cfg.quant

    # give the adapters non-trivial weights (simulating a fine-tuned model)
    def bump(p):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: (x + 0.01 if any(
                getattr(k, "key", None) == "ad" for k in path) else x), p)
    params = bump(params)

    merged = merge_model(params, pol)

    store, tenants = None, []
    if args.adapters:
        if args.engine not in ("continuous", "frontend"):
            ap.error("--adapters needs --engine continuous|frontend "
                     "(per-slot adapters only apply to slotted serving)")
        from repro.serving import AdapterStore
        specs = [s for s in args.adapters.split(",") if s]
        try:
            store = AdapterStore(params, capacity=max(4, len(specs)))
        except ValueError as e:
            ap.error(f"--adapters: {e}")
        for spec in specs:
            name, eq, src_ = spec.partition("=")
            if not eq or not name or not src_:
                ap.error(f"--adapters entry {spec!r} is not name=spec")
            if src_.startswith("demo:"):
                seed = int(src_[len("demo:"):] or "0")

                def noise(path, x, _seed=seed, _cnt=[0]):
                    if any(getattr(k, "key", None) == "ad" for k in path):
                        _cnt[0] += 1
                        k = jax.random.fold_in(
                            jax.random.PRNGKey(1000 + _seed), _cnt[0])
                        return x + 0.02 * jax.random.normal(
                            k, x.shape, x.dtype)
                    return x

                tree = jax.tree_util.tree_map_with_path(noise, params)
            else:
                from repro.checkpoint import load_pytree
                tree = load_pytree(src_, like=params)
            store.register(name, tree)
            tenants.append(name)
        print(f"[serve] adapter store: {store.n_adapters} tenants "
              f"{tenants} over one int{pol.default.bits} base "
              f"(capacity {store.capacity}, + null adapter)")
        merged = store.base

    # requests cycle tenants round-robin, with a bare-base (null
    # adapter) request in the mix so eviction back to id 0 is exercised
    who = (lambda i: ([*tenants, None])[i % (len(tenants) + 1)]) \
        if tenants else (lambda i: None)

    b = args.requests
    # an empty prompt still needs one token to condition on: feed BOS (=0)
    prompt_len = max(args.prompt_len, 1)
    # +speculate: the ragged verify transiently writes up to k rows past
    # the committed stream (the scheduler demands the same headroom)
    max_len = prompt_len + args.gen_len + args.speculate
    prompts = np.random.default_rng(0).integers(
        4, cfg.vocab, size=(b, prompt_len)).astype(np.int32)
    if args.prompt_len == 0:
        prompts[:] = 0

    # encdec prefill needs a "src" frontend batch the token-only demo
    # doesn't have; its decode loop (zero cross-memory, as before) still
    # works, so route it through the reference loop.
    use_loop = args.loop or cfg.family == "encdec"
    if args.page_size and args.engine not in ("continuous", "frontend"):
        ap.error("--page-size needs --engine continuous|frontend (the "
                 "static path has no slot scheduler to drive a page pool)")
    if args.speculate and args.engine not in ("continuous", "frontend"):
        ap.error("--speculate needs --engine continuous|frontend (draft "
                 "+ ragged verify run on the slot scheduler)")
    if args.speculate and args.decode_burst is not None \
            and args.decode_burst > 1:
        ap.error("--speculate is incompatible with --decode-burst > 1: "
                 "the one-step ragged verify IS the multi-token dispatch; "
                 "drop --decode-burst (it is forced to 1 when speculating)")
    decode_burst = (1 if args.speculate
                    else (8 if args.decode_burst is None
                          else args.decode_burst))
    drafter = None
    if args.speculate:
        drafter = args.draft_policy or (
            "mtp" if getattr(cfg, "mtp", False) else "*=intq8")
    paging = dict(page_size=max(args.page_size, 0),
                  n_pages=args.n_pages or None)
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        if args.engine == "frontend":
            from repro.runtime.fault import FaultInjector, PreemptionGuard
            from repro.serving import ServingFrontend, slo_summary
            if args.loop:
                ap.error("--loop is the static reference path; "
                         "drop it or use --engine static")
            if args.gen_len < 1:
                ap.error("--engine frontend needs --gen-len >= 1")
            slots = args.slots or min(4, b)
            injector = None
            if args.inject_faults:
                # one crash once decode is underway + a sprinkle of
                # injected straggler latency; the frontend replays
                # in-flight requests token-identically after the rebuild
                injector = FaultInjector(seed=0, crash_steps=(5,),
                                         p_straggle=0.1, straggle_s=0.01)
            ms = lambda v: (v / 1e3) if v and v > 0 else None
            with PreemptionGuard() as guard:
                try:
                    fe = ServingFrontend(
                        lm, merged, n_slots=slots, max_len=max_len,
                        prefill_chunk=args.prefill_chunk,
                        decode_burst=decode_burst,
                        queue_cap=args.queue_cap,
                        default_deadline_s=ms(args.deadline_ms),
                        default_ttft_deadline_s=ms(args.ttft_deadline_ms),
                        injector=injector, guard=guard, adapters=store,
                        speculate=args.speculate, drafter=drafter,
                        **paging)
                except ValueError as e:
                    if args.page_size:
                        ap.error(f"--page-size: {e}")
                    if args.speculate:
                        ap.error(f"--speculate: {e}")
                    raise
                except NotImplementedError as e:
                    if args.speculate:
                        ap.error(f"--speculate: {e}")
                    if store is not None:
                        ap.error(f"--adapters with --engine frontend: {e}")
                    ap.error(
                        f"--engine frontend does not support the "
                        f"{cfg.family!r} family (arch {cfg.name}); fall "
                        f"back to --engine static, and see the "
                        f"family-support matrix in README.md 'Serving "
                        f"engine' for what each engine covers")
                tickets = [fe.submit(prompts[i], args.gen_len,
                                     adapter_id=who(i))
                           for i in range(b)]
                counts = fe.run_until_drained()
            s = slo_summary(fe)
            est = fe.engine_stats
            sp = (f", spec acceptance {est.acceptance_rate:.0%}"
                  if args.speculate else "")
            print(f"[serve] frontend: {counts} "
                  f"({fe.n_recoveries} recoveries, occupancy "
                  f"{est.occupancy:.0%}, {est.dispatches} dispatches{sp}, "
                  f"{est.compiles} compiles, host "
                  f"{1e3 * est.host_s / max(est.dispatches, 1):.2f} "
                  f"ms/dispatch)")
            print(f"[serve] SLO: ttft p50/p95 "
                  f"{s['ttft_p50_s'] * 1e3:.0f}/{s['ttft_p95_s'] * 1e3:.0f}ms"
                  f", tpot p50 {s['tpot_p50_s'] * 1e3:.1f}ms, goodput "
                  f"{s['goodput_tok_s']:.1f} tok/s, timeout rate "
                  f"{s['timeout_rate']:.0%}, reject rate "
                  f"{s['reject_rate']:.0%}")
            for t in tickets:
                if t.error:
                    print(f"[serve]   rid {t.rid}: {t.status.name} — "
                          f"{t.error}")
            done = [t for t in tickets
                    if t.status.name == "FINISHED"]
            if done:
                print(f"[serve] sample generation: "
                      f"{np.asarray(done[0].tokens[:8], np.int32)}")
            print("[serve] done")
            return
        if args.engine == "continuous":
            from repro.serving import ContinuousEngine
            if args.loop:
                ap.error("--loop is the static reference path; "
                         "drop it or use --engine static")
            if args.gen_len < 1:
                ap.error("--engine continuous needs --gen-len >= 1")
            slots = args.slots or min(4, b)
            try:
                eng = ContinuousEngine(lm, merged, n_slots=slots,
                                       max_len=max_len,
                                       prefill_chunk=args.prefill_chunk,
                                       decode_burst=decode_burst,
                                       adapters=store,
                                       speculate=args.speculate,
                                       drafter=drafter, **paging)
            except ValueError as e:
                # e.g. rwkv (no CACHE leaves to page) or a degenerate pool
                if args.page_size:
                    ap.error(f"--page-size: {e}")
                if args.speculate:
                    ap.error(f"--speculate: {e}")
                raise
            except NotImplementedError as e:
                if args.speculate:
                    # e.g. mamba_hybrid (no length-addressed rollback) or
                    # an mtp drafter on an arch without the head
                    ap.error(f"--speculate: {e}")
                if store is not None:
                    ap.error(f"--adapters with --engine continuous: {e}")
                # name the family and point at the docs instead of letting
                # the bare engine-constructor error surface to a CLI user
                ap.error(
                    f"--engine continuous does not support the "
                    f"{cfg.family!r} family (arch {cfg.name}); fall back "
                    f"to --engine static, and see the family-support "
                    f"matrix in README.md 'Serving engine' for what each "
                    f"engine covers")
            rids = [eng.submit(prompts[i], args.gen_len, adapter_id=who(i))
                    for i in range(b)]
            outputs = eng.run()
            st = eng.stats
            gen = np.asarray([outputs[r] for r in rids], dtype=np.int32)
            mix = (f", {store.n_adapters}+null tenants per-slot"
                   if store is not None else "")
            if args.speculate:
                mix += (f", spec k={args.speculate} "
                        f"({drafter}): {st.accepted_tokens}/"
                        f"{st.proposed_tokens} drafts accepted "
                        f"({st.acceptance_rate:.0%})")
            if eng.page_table is not None:
                pt = eng.page_table
                mix += (f", paged {pt.page_size}-token pages: "
                        f"{pt.peak_used}/{pt.capacity} peak, "
                        f"{pt.reused_tokens_total} prefix tokens reused, "
                        f"{pt.alloc_backoffs} backoffs")
            dt, path = st.seconds, (
                f"continuous, {slots} slots, occupancy {st.occupancy:.0%}, "
                f"{st.dispatches} dispatches, {st.compiles} compiles, host "
                f"{1e3 * st.host_s / max(st.dispatches, 1):.2f} ms/dispatch"
                f"{mix}")
        elif use_loop:
            gen, dt = generate_loop_reference(
                lm, merged, prompts, args.gen_len, max_len)
            path = "per-token loop"
        else:
            gen, dt = generate_scan(
                lm, mesh, merged, prompts, args.gen_len, max_len)
            path = "prefill+scan"

        dev = jax.devices()[0]
        print(f"[serve] {b} requests x {gen.shape[1]} tokens in {dt:.2f}s "
              f"({b * gen.shape[1] / max(dt, 1e-9):.1f} tok/s, {path}, "
              f"{dev.platform} {dev.device_kind})")
        print(f"[serve] sample generation: {gen[0][:8]}")

        if args.verify:
            toks = jnp.asarray(prompts)
            cache_a = lm.init_cache(b, max_len, dtype=jnp.float32)
            logits_a, _ = _JIT_DECODE(lm, params, cache_a, toks[:, :1])
            cache_m = lm.init_cache(b, max_len, dtype=jnp.float32)
            logits_m, _ = _JIT_DECODE(lm, merged, cache_m, toks[:, :1])
            err = merge_error(logits_a, logits_m)
            print(f"[serve] merge-exactness max|adapter - merged| / "
                  f"max|adapter| = {err:.2e} (limit {MERGE_LOGIT_RTOL:.0e})")
            if not err < MERGE_LOGIT_RTOL:
                raise SystemExit("merged model diverged from adapter model")
    print("[serve] done")


if __name__ == "__main__":
    main()
