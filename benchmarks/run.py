"""Benchmark harness — one function per paper table/figure.

  table1  MMLU proxy: QA-LoRA vs QLoRA vs QLoRA+PTQ across bit widths
  table2  learnable params + time/step (QLoRA vs QA-LoRA), incl. the
          paper's exact full-scale #Params (analytic, LLaMA geometries)
  table3  commonsense proxy: per-dataset eval suite at 4/3/2 bits
  table5  group-size ablation (g in {16, 32, 64} at 4 & 2 bits)
  table6  fine-tuning-dataset axis (3 unseen tasks)
  fig3    fine-tuning dataset-size axis
  kernels micro-bench of the Pallas kernels (interpret on CPU) + oracle
  decode  decode-path bench: M=1 GEMV vs padded matmul, autotuned blocks,
          prefill+scan vs per-token loop (tok/s, us/step)
  engine  serving-engine bench: continuous batching (slot eviction +
          refill) vs static batching on a mixed-length request trace
          (useful tok/s, slot occupancy)
  paged   paged KV cache vs contiguous slots on a shared-prefix trace
          (tok/s, prefill rows skipped via prefix reuse, peak cache
          bytes) — token streams asserted identical first
  spec    speculative decoding: quantized self-drafting + one-step
          ragged verify — accepted tokens per model step per slot
          (gated > 1.0 on the intq8 drafter), acceptance rate, and
          honest wall-clock vs the burst baseline; int2 realism row
  slo     latency-SLO harness: live Poisson/bursty arrivals replayed
          against the async ServingFrontend (threaded intake, bounded
          queue, deadlines), clean AND fault-injected — TTFT/TPOT
          p50/p95/p99, timeout/reject rates, goodput, recoveries
  roofline summary of experiments/roofline.json (run dryrun first)

Each prints CSV ``name,us_per_call,derived`` style rows and everything is
also dumped to experiments/bench_results.json.

Run: PYTHONPATH=src python -m benchmarks.run [--only table1,table5]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

RESULTS = {}


def emit(table, name, value, derived=""):
    RESULTS.setdefault(table, {})[name] = (value, derived)
    print(f"{table},{name},{value},{derived}")


# ---------------------------------------------------------------------------


def table1_mmlu_proxy():
    """Accuracy of the DEPLOYED model on the fine-tuned task (stride-5),
    mirroring Table 1's QA-LoRA vs QLoRA(+PTQ) x bits comparison."""
    from benchmarks.common import (finetune, answer_accuracy, merge_for_deploy,
                                   ptq_tree, get_pretrained)
    cfg0, base = get_pretrained()
    emit("table1", "base-noft", round(answer_accuracy(cfg0, base, "selfinst"), 4),
         "pretrained base, unseen task")

    # QLoRA: one fine-tune; deploy as fp merge ('4+16') and as PTQ'd INT-N
    cfg_ql, p_ql, st = finetune("qlora", 4, 16, "selfinst")
    merged_fp = merge_for_deploy(p_ql, cfg_ql.quant)
    emit("table1", "qlora-4+16", round(answer_accuracy(cfg_ql, merged_fp, "selfinst"), 4),
         "fp16 merge (paper's 4+16 row)")
    for bits in (4, 3, 2):
        ptq = ptq_tree(merged_fp, bits, 16)
        emit("table1", f"qlora-ptq-int{bits}",
             round(answer_accuracy(cfg_ql, ptq, "selfinst"), 4),
             "merge->PTQ (lossy)")

    for bits in (4, 3, 2):
        cfg_qa, p_qa, _ = finetune("qalora", bits, 16, "selfinst")
        merged = merge_for_deploy(p_qa, cfg_qa.quant)
        emit("table1", f"qalora-int{bits}",
             round(answer_accuracy(cfg_qa, merged, "selfinst"), 4),
             "exact merge, still INT-N")


def table2_efficiency():
    """Paper Table 2: learnable params + fine-tuning time."""
    from benchmarks.common import finetune

    # (a) analytic #Params at the paper's scales (r=64, g=32, all linears)
    LLAMA = {  # (layers, d_model, d_ff) and paper-reported params (M)
        "7B": (32, 4096, 11008, 160, 89),
        "13B": (40, 5120, 13824, 250, 140),
        "33B": (60, 6656, 17920, 488, 272),
        "65B": (80, 8192, 22016, 800, 447),
    }
    r, g = 64, 32
    for name, (L, d, ff, qlora_m, qalora_m) in LLAMA.items():
        mats = [(d, d)] * 4 + [(d, ff)] * 2 + [(ff, d)]
        qlora = sum((di + do) * r for di, do in mats) * L
        qalora = sum((di // g + do) * r for di, do in mats) * L
        emit("table2", f"llama-{name}-qlora-params", f"{qlora/1e6:.0f}M",
             f"paper reports {qlora_m}M")
        emit("table2", f"llama-{name}-qalora-params", f"{qalora/1e6:.0f}M",
             f"paper reports {qalora_m}M")

    # (b) measured time/step + trainable counts at toy scale
    for mode, bits in (("lora", 4), ("qlora", 4), ("qalora", 4)):
        _, _, st = finetune(mode, bits, 16, "selfinst", steps=30)
        emit("table2", f"{mode}-s_per_step", round(st["s_per_step"], 4),
             f"trainable={st['trainable']}")


def table3_commonsense_proxy():
    """Per-dataset eval suite of deployed models (Table 3 analogue)."""
    from benchmarks.common import (finetune, answer_accuracy, merge_for_deploy,
                                   ptq_tree)
    suites = ("alpaca", "flanv2", "selfinst")
    for bits in (4, 2):
        cfg_qa, p_qa, _ = finetune("qalora", bits, 16, "selfinst")
        merged = merge_for_deploy(p_qa, cfg_qa.quant)
        cfg_ql, p_ql, _ = finetune("qlora", bits, 16, "selfinst")
        ptq = ptq_tree(merge_for_deploy(p_ql, cfg_ql.quant), bits, 16)
        for s in suites:
            emit("table3", f"int{bits}-{s}-qalora",
                 round(answer_accuracy(cfg_qa, merged, s), 4), "")
            emit("table3", f"int{bits}-{s}-qlora-ptq",
                 round(answer_accuracy(cfg_ql, ptq, s), 4), "")


def table4_other_families():
    """Paper Table 4 shows QA-LoRA generalizes beyond LLaMA (to LLaMA2).
    Beyond-paper: validate across architecture FAMILIES — including an
    attention-free one — fine-tune each reduced arch with QA-LoRA INT4 and
    verify (a) learning, (b) exact merge."""
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.models import LM
    from repro.models.common import QuantPolicy
    from repro.core import convert_tree
    from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                             split_params, merge_params)
    from repro.data import make_stream
    from repro.launch.serve import merge_model
    from benchmarks.common import VOCAB, SEQ

    for arch in ("gemma3-1b", "rwkv6-7b", "zamba2-7b"):
        cfg = C.reduced(arch, vocab=VOCAB).scaled(
            quant=QuantPolicy(mode="fp", dtype=jnp.float32))
        lm = LM(cfg)
        params = lm.init(jax.random.PRNGKey(0))
        ocfg = AdamWConfig(lr=5e-3, max_grad_norm=1.0)

        @jax.jit
        def pstep(p, o, batch):
            loss, g = jax.value_and_grad(lambda q: lm.loss(q, batch)[0])(p)
            p, o, _ = adamw_update(ocfg, g, o, p)
            return p, o, loss

        stream = make_stream("alpaca", vocab=VOCAB, seq_len=SEQ, global_batch=8)
        opt = adamw_init(params)
        for _ in range(250):
            toks, labs = stream.next_batch()
            params, opt, _ = pstep(params, opt,
                                   {"tokens": jnp.asarray(toks),
                                    "labels": jnp.asarray(labs)})
        pol = QuantPolicy(mode="qalora", bits=4, group_size=16, rank=8,
                          dtype=jnp.float32)
        qp = convert_tree(params, pol, jax.random.PRNGKey(1))
        cfg_q = cfg.scaled(quant=pol)
        lmq = LM(cfg_q)
        tr, fr = split_params(qp)
        fopt = adamw_init(tr)
        focfg = AdamWConfig(lr=1e-2, max_grad_norm=1.0)

        @jax.jit
        def fstep(t, o, batch):
            loss, g = jax.value_and_grad(
                lambda t_: lmq.loss(merge_params(t_, fr), batch)[0])(t)
            t, o, _ = adamw_update(focfg, g, o, t)
            return t, o, loss

        ft = make_stream("selfinst", vocab=VOCAB, seq_len=SEQ, global_batch=8)
        first = last = None
        for i in range(150):
            toks, labs = ft.next_batch()
            tr, fopt, loss = fstep(tr, fopt, {"tokens": jnp.asarray(toks),
                                              "labels": jnp.asarray(labs)})
            if i == 0:
                first = float(loss)
            last = float(loss)
        tuned = merge_params(tr, fr)
        deployed = merge_model(tuned, pol)
        toks, labs = ft.next_batch()
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}
        l1, _ = jax.jit(lmq.loss)(tuned, batch)
        l2, _ = jax.jit(lmq.loss)(deployed, batch)
        emit("table4", f"{arch}-ft-loss", f"{first:.3f}->{last:.3f}",
             "QA-LoRA INT4 fine-tune on unseen task")
        emit("table4", f"{arch}-merge-delta",
             f"{abs(float(l1) - float(l2)):.2e}",
             "deployed INT4 vs fine-tuned (exact)")


def table5_group_size():
    from benchmarks.common import finetune, answer_accuracy, merge_for_deploy
    for bits in (4, 2):
        for g in (16, 32, 64):
            cfg, p, _ = finetune("qalora", bits, g, "selfinst")
            merged = merge_for_deploy(p, cfg.quant)
            emit("table5", f"int{bits}-g{g}",
                 round(answer_accuracy(cfg, merged, "selfinst"), 4),
                 f"L = d/{g}")


def table6_datasets():
    from benchmarks.common import finetune, answer_accuracy, merge_for_deploy
    for ds in ("selfinst", "longform", "chip2"):
        cfg, p, _ = finetune("qalora", 4, 16, ds)
        merged = merge_for_deploy(p, cfg.quant)
        emit("table6", f"qalora-int4-{ds}",
             round(answer_accuracy(cfg, merged, ds), 4), "unseen stride")


def ablation_rank():
    """Beyond-paper: adapter-rank axis at INT4 and INT2 (the paper fixes
    r=64; the DoF-balance story predicts diminishing returns in r once
    L provides enough quantization freedom)."""
    from benchmarks.common import finetune, answer_accuracy, merge_for_deploy
    for bits in (4, 2):
        for r in (2, 8, 32):
            cfg, p, st = finetune("qalora", bits, 16, "selfinst", rank=r)
            merged = merge_for_deploy(p, cfg.quant)
            emit("ablation_rank", f"int{bits}-r{r}",
                 round(answer_accuracy(cfg, merged, "selfinst"), 4),
                 f"trainable={st['trainable']}")


def fig3_dataset_size():
    from benchmarks.common import finetune, answer_accuracy, merge_for_deploy
    for n in (8, 64, 512):
        # bound the dataset by wrapping example indices (epochs over n)
        import benchmarks.common as bc

        orig = bc.make_stream

        def limited(ds, **kw):
            kw["n_examples"] = n
            return orig(ds, **kw)

        bc.make_stream = limited
        try:
            cfg, p, _ = finetune("qalora", 4, 16, "selfinst", steps=200)
            merged = merge_for_deploy(p, cfg.quant)
            emit("fig3", f"qalora-int4-n{n}",
                 round(answer_accuracy(cfg, merged, "selfinst"), 4),
                 f"{n} examples")
        finally:
            bc.make_stream = orig


def kernels_bench():
    from repro.core import quantize, QALoRAParams
    from repro.kernels import qmatmul, qalora_matmul, qmatmul_ref, qalora_matmul_ref
    key = jax.random.PRNGKey(0)
    m, k, n, g = 64, 512, 256, 32
    w = jax.random.normal(key, (k, n))
    x = jax.random.normal(key, (m, k))
    p = QALoRAParams(a=jax.random.normal(key, (k // g, 16)) * 0.1,
                     b=jax.random.normal(key, (16, n)) * 0.1)
    for bits in (2, 4, 8):
        qt = quantize(w, bits, g)
        for name, fn in (
            (f"qmatmul-int{bits}", lambda: qmatmul(x, qt, interpret=True)),
            (f"qmatmul-ref-int{bits}", lambda: qmatmul_ref(x, qt)),
            (f"qalora-fused-int{bits}",
             lambda: qalora_matmul(x, qt, p, s=1.0, interpret=True)),
            (f"qalora-ref-int{bits}", lambda: qalora_matmul_ref(x, qt, p, 1.0)),
        ):
            fn()  # compile
            t0 = time.time()
            for _ in range(5):
                jax.block_until_ready(fn())
            us = (time.time() - t0) / 5 * 1e6
            emit("kernels", name, round(us, 1),
                 "us/call CPU-interpret (correctness harness, not TPU perf)")


def decode_bench():
    """Decode-path micro-benchmarks (the serve hot path).

    (a) kernel level: M=1 dequant matvec via the GEMV kernel (grid over
        (N, K) only) vs the same call padded to an MXU block_m=128 — the
        cost a production matmul-only path pays per decode token — across
        2/3/4-bit and g in {16, 32, 64};
    (b) block-shape autotuner: measured-best blocks for the decode shape,
        persisted to experiments/autotune_cache.json;
    (c) model level: prefill + lax.scan decode (one compiled program for
        the whole generation) vs the legacy per-token Python loop.
    """
    import repro.configs as C
    from repro.core import quantize
    from repro.kernels import autotune, pick_blocks
    from repro.kernels.qmatmul import qmatmul_pallas
    from repro.kernels.qmatvec import qmatvec_pallas
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import (merge_model, make_scan_generator,
                                    make_loop_generator)
    from repro.models.common import QuantPolicy
    from repro.models.lm import LM

    def timed(fn, reps=5):
        fn()  # compile
        t0 = time.time()
        for _ in range(reps):
            jax.block_until_ready(fn())
        return (time.time() - t0) / reps * 1e6

    def flops_of(fn, *args):
        c = jax.jit(fn).lower(*args).compile().cost_analysis()
        return float(c.get("flops", 0.0))

    # (a) GEMV vs padded-to-128 matmul at the decode shape.  Wall clock in
    # interpret mode is dominated by the Python interpreter + the (shared)
    # dequant, so the headline metric is the XLA op count: the MXU work the
    # padded path issues per decode step vs the GEMV grid.
    key = jax.random.PRNGKey(0)
    k, n = 512, 256
    x1 = jax.random.normal(key, (1, k))
    x128 = jnp.concatenate([x1, jnp.zeros((127, k))], axis=0)
    for bits in (2, 3, 4):
        for g in (16, 32, 64):
            qt = quantize(jax.random.normal(key, (k, n)), bits, g)
            _, bn, bk = pick_blocks(1, k, n, bits, g)
            gemv = lambda a: qmatvec_pallas(
                a, qt.qweight, qt.scale, qt.zero, bits=bits, group_size=g,
                block_n=bn, block_k=bk, interpret=True)
            padded = lambda a: qmatmul_pallas(
                a, qt.qweight, qt.scale, qt.zero, bits=bits, group_size=g,
                block_m=128, block_n=bn, block_k=bk, interpret=True)
            f_gemv = flops_of(gemv, x1)
            f_pad = flops_of(padded, x128)
            us_gemv = timed(lambda: gemv(x1))
            us_pad = timed(lambda: padded(x128))
            if f_gemv > 0:  # some backends report no 'flops' key
                ratio, how = f_pad / f_gemv, "flops/step"
            else:
                ratio, how = us_pad / us_gemv, "us/step (no flops reported)"
            emit("decode", f"qmatvec-m1-int{bits}-g{g}", round(ratio, 1),
                 f"x fewer {how} vs padded-128 "
                 f"({f_gemv:.0f} vs {f_pad:.0f} flops); wall {us_gemv:.0f}us "
                 f"vs {us_pad:.0f}us CPU-interpret")

    # (b) autotune the decode shape and persist the winner
    best = autotune.measure_qmatmul(1, k, n, 4, 32)
    emit("decode", "autotune-m1-int4-g32", "x".join(map(str, best)),
         f"measured-best blocks -> {autotune.cache_path()}")

    # (c) whole-model: prefill+scan vs the per-token loop
    b, prompt_len, gen_len = 2, 8, 8
    max_len = prompt_len + gen_len
    for bits in (2, 3, 4):
        for g in (16, 32, 64):
            pol = QuantPolicy(mode="qalora", bits=bits, group_size=g, rank=4,
                              dtype=jnp.float32, scale_dtype=jnp.float32)
            # d_ff=128 so every group size in the sweep divides every linear
            cfg = C.reduced("gemma3-1b", quant=pol, d_ff=128)
            lm = LM(cfg)
            params = lm.init(jax.random.PRNGKey(0))
            merged = merge_model(params, pol)
            prompts = np.random.default_rng(0).integers(
                4, cfg.vocab, size=(b, prompt_len)).astype(np.int32)
            mesh = make_local_mesh()
            with jax.set_mesh(mesh):
                # build each path's jitted callables once, warm them
                # (compile on the first call), then time the second call —
                # so the row measures decode throughput, not trace/compile
                scan = make_scan_generator(lm, mesh, merged, prompts.shape,
                                           gen_len, max_len)
                loop = make_loop_generator(lm, merged, gen_len, max_len)
                scan(prompts), loop(prompts)
                toks_s, dt_s = scan(prompts)
                toks_l, dt_l = loop(prompts)
            assert np.array_equal(toks_s, toks_l), "scan != loop tokens"
            us_s = dt_s / gen_len * 1e6
            us_l = dt_l / gen_len * 1e6
            emit("decode", f"scan-int{bits}-g{g}",
                 round(b * gen_len / dt_s, 1),
                 f"tok/s; {us_s:.0f}us/step scan vs {us_l:.0f}us/step loop "
                 f"({us_l / us_s:.1f}x); 1 compiled program vs "
                 f"{max_len - 1} dispatches")


def _engine_compare(cfg, prefix, *, slots, prompt_len, long_gen, short_gen,
                    n_requests, decode_burst=16, note=""):
    """One continuous-vs-static engine row set for ``cfg``: same merged
    model, same FIFO trace (one long request per group of ``slots``, the
    rest short).  Static batching runs each group through the compiled
    prefill+scan path and must decode every slot to the group's LONGEST
    request; the continuous engine evicts each slot at its own max-len
    and refills it from the queue mid-flight.  tok/s counts USEFUL tokens
    over wall time; both paths are warmed (compiled) first, timed after."""
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import merge_model, make_scan_generator
    from repro.models.lm import LM
    from repro.serving import ContinuousEngine, make_trace, static_schedule

    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)

    trace = make_trace(n_requests, cfg.vocab, seed=0,
                       prompt_lens=(prompt_len,),
                       gen_lens=(long_gen, short_gen, short_gen, short_gen))
    useful = sum(r.max_new_tokens for r in trace)
    max_len = prompt_len + long_gen
    groups = static_schedule(trace, slots)

    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        runners = {}

        def run_static():
            dt = 0.0
            for grp, gen in groups:
                prompts = np.stack([r.prompt for r in grp])
                key = (prompts.shape, gen)
                if key not in runners:
                    runners[key] = make_scan_generator(
                        lm, mesh, merged, prompts.shape, gen, max_len)
                _, d = runners[key](prompts)
                dt += d
            return dt

        eng = ContinuousEngine(lm, merged, n_slots=slots, max_len=max_len,
                               prefill_chunk=prompt_len,
                               decode_burst=decode_burst)

        def run_continuous():
            eng.reset()
            for r in trace:
                eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
            eng.run()
            return eng.stats

        run_static(), run_continuous()            # warm (compile)
        dt_s = min(run_static() for _ in range(3))
        st = min((run_continuous() for _ in range(3)),
                 key=lambda s: s.seconds)

    static_steps = sum(g for _, g in groups)
    static_occ = useful / (static_steps * slots)
    tok_s_static = useful / dt_s
    emit("engine", f"{prefix}static-tok_s", round(tok_s_static, 1),
         f"{len(groups)} batches x{slots}, each decodes its longest "
         f"({static_steps} steps for {useful} useful tokens, "
         f"occupancy {static_occ:.0%}){note}")
    emit("engine", f"{prefix}continuous-tok_s", round(st.tok_per_s, 1),
         f"slot eviction+refill: occupancy {st.occupancy:.0%}, "
         f"{st.dispatches} dispatches, {st.model_steps} model steps{note}")
    emit("engine", f"{prefix}continuous-speedup",
         round(st.tok_per_s / tok_s_static, 2),
         f"continuous vs static on the mixed trace "
         f"({long_gen}/{short_gen}-token request mix){note}")


def engine_bench():
    """Serving-engine throughput: continuous batching vs static batching
    under a mixed-length request trace — one row set per slotted-cache
    family (gqa at a d128/L4 gemma3, MLA compressed-KV at a reduced
    deepseek-v3 with its real dense/MoE layer split)."""
    import repro.configs as C

    # a notch above smoke size: at d_model=64 a decode step is so cheap
    # that per-dispatch host overhead (which the engine pays more of)
    # swamps the slot-waste signal the table is about
    _engine_compare(
        C.reduced("gemma3-1b", d_model=128, n_layers=4, d_ff=256,
                  n_heads=8, n_kv_heads=2),
        "", slots=4, prompt_len=4, long_gen=96, short_gen=2, n_requests=16)

    # MLA compressed-KV serving (deepseek-v3 geometry, absorbed decode,
    # per-run hoisted W_uk/W_uv).  Kept smaller than the gqa row — the
    # smoke job runs this on every PR; MoE layers route over all B*C
    # rows, so this row measures throughput, not stream equivalence
    # (tests/test_serving_mla.py gates that on the all-dense config).
    _engine_compare(
        C.reduced("deepseek-v3-671b", d_model=128, n_heads=8,
                  q_lora_rank=64, kv_lora_rank=64, mtp=False),
        "mla-", slots=2, prompt_len=4, long_gen=48, short_gen=2,
        n_requests=8, note="; deepseek-v3 reduced, compressed-KV cache")

    # recurrent families through the unified SlotState ragged step:
    # per-slot Mamba2/RWKV6 recurrences advance raggedly, eviction
    # reinitializes them (SlotState.reset), and zamba2's shared
    # attention blocks ride the slotted-KV chunk path.  Same
    # mixed-trace shape as the mla row so the occupancy story is
    # comparable (tests/test_serving_recurrent.py gates equivalence).
    # (rwkv one notch larger: its per-token mix is so cheap at d128 that
    # per-dispatch host overhead — which the engine pays more of — would
    # swamp the slot-waste signal, as with the gqa row above)
    _engine_compare(
        C.reduced("rwkv6-7b", d_model=256, d_ff=512, n_layers=4),
        "rwkv-", slots=2, prompt_len=4, long_gen=48, short_gen=2,
        n_requests=8, note="; rwkv6 reduced, recurrent slot state")
    _engine_compare(
        C.reduced("zamba2-7b", d_model=128, d_ff=256, n_heads=8,
                  n_kv_heads=2),
        "zamba2-", slots=2, prompt_len=4, long_gen=48, short_gen=2,
        n_requests=8,
        note="; zamba2 reduced, hybrid mamba + shared-attn slot state")


def paged_bench():
    """Paged KV cache vs contiguous per-slot slabs on a shared-system-
    prompt trace (every request carries the same 16-token prefix — the
    workload hash-based prefix reuse targets).  Rows: useful tok/s for
    both layouts (token streams asserted identical first), prefill
    model-rows actually consumed (prefix hits skip whole chunks), and
    peak cache bytes — the contiguous engine reserves slots x max_len
    up front, the paged engine's high-water mark is ``peak_used`` pages
    of the pool, with shared prefix pages counted once."""
    import repro.configs as C
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import merge_model
    from repro.models.lm import LM
    from repro.models.slot_state import CACHE
    from repro.serving import ContinuousEngine, make_trace

    # same notch-above-smoke geometry as the gqa engine row
    cfg = C.reduced("gemma3-1b", d_model=128, n_layers=4, d_ff=256,
                    n_heads=8, n_kv_heads=2)
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)

    slots, page_size, shared = 4, 8, 16       # prefix = 2 full pages
    trace = make_trace(12, cfg.vocab, seed=0, shared_prefix=shared,
                       prompt_lens=(4,), gen_lens=(32, 16, 24))
    max_len = shared + 4 + 32

    def cache_bytes(eng):
        """Total bytes of the engine's CACHE-kind leaves (the KV that
        paging pools); STATE/LEN leaves are identical across layouts."""
        spec = eng.slot_state.layout(slots, eng.max_len)
        tot = [0]

        def one(s, x):
            if s.kind == CACHE:
                tot[0] += x.nbytes
            return 0

        jax.tree.map(one, spec, eng.cache)
        return tot[0]

    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        def build(**kw):
            return ContinuousEngine(lm, merged, n_slots=slots,
                                    max_len=max_len, prefill_chunk=page_size,
                                    decode_burst=16, **kw)

        cont, paged = build(), build(page_size=page_size)

        def run(eng):
            # first request alone until it finishes prefill: its prefix
            # pages register, so the following wave admits against a WARM
            # prefix cache (the steady state a shared system prompt
            # serves in); the contiguous engine runs the same schedule
            # for a fair clock
            eng.reset()
            r0 = trace[0]
            eng.submit(r0.prompt, r0.max_new_tokens, r0.eos_id, rid=r0.rid)
            while eng.sched.slots[0] is None or eng.sched.slots[0].prefilling:
                eng.step_once()
            for r in trace[1:]:
                eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
            return eng.run(), eng.stats

        (out_c, _), (out_p, _) = run(cont), run(paged)  # warm (compile)
        assert out_c == out_p, "paged engine diverged from contiguous"
        st_c = min((run(cont)[1] for _ in range(3)), key=lambda s: s.seconds)
        st_p = min((run(paged)[1] for _ in range(3)), key=lambda s: s.seconds)

    pt = paged.page_table                      # the timed run's pool
    assert pt.reused_tokens_total > 0, "no prefix hits on a shared trace"
    assert st_p.busy_slot_steps < st_c.busy_slot_steps, \
        "prefix reuse did not cut prefill model-rows"
    useful = sum(r.max_new_tokens for r in trace)
    emit("paged", "contiguous-tok_s", round(st_c.tok_per_s, 1),
         f"{useful} useful tokens, occupancy {st_c.occupancy:.0%}, "
         f"{st_c.busy_slot_steps} busy model-rows")
    emit("paged", "paged-tok_s", round(st_p.tok_per_s, 1),
         f"same trace, identical tokens (asserted); occupancy "
         f"{st_p.occupancy:.0%}, {st_p.busy_slot_steps} busy model-rows "
         f"({st_c.busy_slot_steps - st_p.busy_slot_steps} prefill rows "
         f"skipped via prefix hits)")
    emit("paged", "reused-prefill-tokens", pt.reused_tokens_total,
         f"prompt tokens served from shared pages across "
         f"{len(trace)} requests ({shared}-token shared prefix, "
         f"page_size {page_size}); {pt.alloc_backoffs} admission backoffs")
    contig_b = cache_bytes(cont)
    page_b = cache_bytes(paged) / paged.n_pages
    peak_b = int(pt.peak_used * page_b)
    assert peak_b < contig_b, "paged peak should undercut the static slabs"
    emit("paged", "contiguous-cache-bytes", contig_b,
         f"slots x max_len reserved up front "
         f"({slots} x {max_len} tokens of KV)")
    emit("paged", "paged-peak-cache-bytes", peak_b,
         f"{pt.peak_used}/{paged.n_pages - 1} pages at the high-water "
         f"mark ({peak_b / contig_b:.0%} of contiguous; shared prefix "
         f"pages counted once)")


def adapters_bench():
    """Multi-tenant adapter serving: a mixed-adapter trace (two tenants
    + null-adapter requests, different adapter per slot in the SAME
    dispatch via the banked gather epilogue) vs the merged-single-
    adapter engine on the same trace — the per-request-exact reference
    that can only serve ONE tenant at a time.  The overhead row is the
    acceptance number: unmerged per-slot serving must stay within 25%
    of merged-base decode tok/s while actually multiplexing tenants."""
    import repro.configs as C
    from repro.launch.mesh import make_local_mesh
    from repro.models.lm import LM
    from repro.serving import AdapterStore, ContinuousEngine, make_trace

    # same notch-above-smoke geometry as the gqa engine row, so decode
    # steps are big enough that the epilogue cost is visible over
    # per-dispatch host overhead
    cfg = C.reduced("gemma3-1b", d_model=128, n_layers=4, d_ff=256,
                    n_heads=8, n_kv_heads=2)
    lm = LM(cfg)
    raw = lm.init(jax.random.PRNGKey(0))

    def bump(tree, mag, seed):
        cnt = [0]

        def f(path, x):
            if any(getattr(k, "key", None) == "ad" for k in path):
                cnt[0] += 1
                k = jax.random.fold_in(jax.random.PRNGKey(seed), cnt[0])
                return x + mag * jax.random.normal(k, x.shape, x.dtype)
            return x

        return jax.tree_util.tree_map_with_path(f, tree)

    store = AdapterStore(raw, capacity=4)
    store.register("alpha", bump(raw, 0.02, 1))
    store.register("beta", bump(raw, 0.03, 2))

    slots, prompt_len, max_len = 4, 4, 52
    trace = make_trace(12, cfg.vocab, seed=0, prompt_lens=(prompt_len,),
                       gen_lens=(48, 24, 32),
                       adapter_ids=("alpha", "beta", None), store=store)
    useful = sum(r.max_new_tokens for r in trace)

    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        def run(eng, with_adapters):
            eng.reset()
            for r in trace:
                eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid,
                           adapter_id=r.adapter_id if with_adapters else None)
            eng.run()
            return eng.stats

        mixed = ContinuousEngine(lm, store.base, n_slots=slots,
                                 max_len=max_len, prefill_chunk=prompt_len,
                                 decode_burst=16, adapters=store)
        merged_eng = ContinuousEngine(lm, store.merged("alpha"),
                                      n_slots=slots, max_len=max_len,
                                      prefill_chunk=prompt_len,
                                      decode_burst=16)
        run(mixed, True), run(merged_eng, False)   # warm (compile)
        st_mix = min((run(mixed, True) for _ in range(3)),
                     key=lambda s: s.seconds)
        st_mrg = min((run(merged_eng, False) for _ in range(3)),
                     key=lambda s: s.seconds)

    overhead = st_mrg.tok_per_s / max(st_mix.tok_per_s, 1e-9)
    n_tenants = store.n_adapters
    emit("adapters", "mixed-unmerged-tok_s", round(st_mix.tok_per_s, 1),
         f"{n_tenants} tenants + null requests multiplexed per-slot "
         f"({useful} useful tokens, occupancy {st_mix.occupancy:.0%}, "
         f"banked gather epilogue)")
    emit("adapters", "merged-single-tok_s", round(st_mrg.tok_per_s, 1),
         f"one merged tenant, same trace shape (occupancy "
         f"{st_mrg.occupancy:.0%}); can only serve ONE adapter")
    emit("adapters", "unmerged-overhead", round(overhead, 3),
         f"merged/unmerged tok_s at {n_tenants} concurrent adapters; "
         f"acceptance: <= 1.25")
    emit("adapters", "occupancy", round(st_mix.occupancy, 3),
         f"{st_mix.dispatches} dispatches, {st_mix.model_steps} model "
         f"steps on the mixed-adapter trace")


def _slo_run(lm, merged, trace, arrivals, *, slots, max_len, queue_cap,
             deadline_s, injector=None):
    """One live frontend run: replay ``trace`` at ``arrivals`` against a
    threaded ServingFrontend, drain, return its slo_summary dict."""
    from repro.serving import ServingFrontend, replay, slo_summary

    fe = ServingFrontend(lm, merged, n_slots=slots, max_len=max_len,
                         prefill_chunk=4, decode_burst=4,
                         queue_cap=queue_cap, default_deadline_s=deadline_s,
                         injector=injector)
    fe.start()
    replay(lambda r: fe.submit(r.prompt, r.max_new_tokens, eos_id=r.eos_id),
           trace, arrivals)
    fe.stop()
    return slo_summary(fe)


def slo_bench():
    """Latency-SLO harness: Poisson vs bursty open-loop arrivals (same
    mean rate) replayed live against the async ServingFrontend — bounded
    intake queue, per-request deadlines — both clean and fault-injected
    (a deterministic mid-run crash + random stragglers).  Rows are TTFT
    and TPOT p50/p95/p99, timeout/reject rates, goodput and recovery
    count per (arrival, mode) combination.  The offered rate is set to
    ~70% of capacity measured on this machine, so the clean Poisson rows
    are the healthy baseline and the bursty/faulty rows show the tails."""
    import math

    import repro.configs as C
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import merge_model
    from repro.models.lm import LM
    from repro.runtime import FaultInjector
    from repro.serving import (ServingFrontend, bursty_arrivals, make_trace,
                               poisson_arrivals, slo_summary)

    cfg = C.reduced("gemma3-1b")
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)
    slots, max_len, n_req = 4, 24, 32
    lens = dict(prompt_lens=(3, 5, 8), gen_lens=(4, 8, 12))
    trace = make_trace(n_req, cfg.vocab, seed=0, **lens)

    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        # two warm runs through the REAL threaded serve loop: the first
        # pays compilation (compiled jits are cached module-level, so
        # fresh frontends reuse them); the second measures request
        # capacity and end-to-end latency under full saturation — all
        # requests submitted at once — so rate and deadline are
        # calibrated to this machine instead of being magic constants
        for phase in range(2):
            warm = ServingFrontend(lm, merged, n_slots=slots,
                                   max_len=max_len, prefill_chunk=4,
                                   decode_burst=4, queue_cap=n_req)
            warm.start()
            for r in make_trace(2 * slots, cfg.vocab, seed=7, **lens):
                warm.submit(r.prompt, r.max_new_tokens, eos_id=r.eos_id)
            warm.stop()
        cap = slo_summary(warm)
        lat = [t.t_done - t.t_submit for t in warm.tickets.values()
               if t.t_done is not None]
        rate = 0.7 * cap["finished"] / max(warm.wall_s, 1e-9)
        # total deadline 3x the saturated end-to-end latency: clean
        # Poisson traffic at 70% load should make it; bursty tails and
        # crash recovery may not (that is the point)
        deadline = max(3.0 * max(lat), 0.1)

        for arr_name, arrivals in (
                ("poisson", poisson_arrivals(n_req, rate, seed=1)),
                ("bursty", bursty_arrivals(n_req, rate, burst=6, seed=1))):
            for mode in ("clean", "faulty"):
                inj = (FaultInjector(seed=2, crash_steps=(8,),
                                     p_straggle=0.05, straggle_s=0.01)
                       if mode == "faulty" else None)
                s = _slo_run(lm, merged, trace, arrivals, slots=slots,
                             max_len=max_len, queue_cap=2 * slots,
                             deadline_s=deadline, injector=inj)
                note = (f"{arr_name} arrivals @ {rate:.1f} req/s, {mode}; "
                        f"{s['finished']}/{s['n_requests']} finished, "
                        f"{s['recoveries']} recoveries, deadline "
                        f"{deadline * 1e3:.0f}ms, queue cap {2 * slots}")
                pre = f"{arr_name}-{mode}-"
                for key, label in (("ttft_p50_s", "ttft-p50-ms"),
                                   ("ttft_p95_s", "ttft-p95-ms"),
                                   ("ttft_p99_s", "ttft-p99-ms"),
                                   ("tpot_p50_s", "tpot-p50-ms"),
                                   ("tpot_p95_s", "tpot-p95-ms"),
                                   ("tpot_p99_s", "tpot-p99-ms")):
                    v = s[key]
                    # nan percentile = no finished requests in this combo
                    emit("slo", pre + label,
                         -1.0 if math.isnan(v) else round(v * 1e3, 2), note)
                emit("slo", pre + "timeout-rate", round(s["timeout_rate"], 3),
                     note)
                emit("slo", pre + "reject-rate", round(s["reject_rate"], 3),
                     note)
                emit("slo", pre + "goodput-tok_s",
                     round(s["goodput_tok_s"], 1), note)
                emit("slo", pre + "recoveries", int(s["recoveries"]), note)


def spec_bench():
    """Speculative decoding: quantized self-drafting + one-step ragged
    verify vs the non-speculative burst engine on the same mixed trace.
    The gated headline is ALGORITHMIC — accepted tokens per model step
    per busy slot, measured over the all-decoding steady phase, must
    beat 1.0 (a non-speculative engine is exactly 1.0: one token per
    target step per slot).  tok/s rows are reported honestly: on CPU
    interpret the drafter's k extra forwards are nearly as expensive as
    the target's one, so wall-clock speedup needs the memory-bound
    serving regime the technique targets; the per-step win transfers.
    An int2 drafter row shows the acceptance/realism tradeoff at the
    paper's lowest bit width (reported, not gated)."""
    import repro.configs as C
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import merge_model
    from repro.models.lm import LM
    from repro.serving import ContinuousEngine, make_trace

    cfg = C.reduced("gemma3-1b", d_model=128, n_layers=4, d_ff=256,
                    n_heads=8, n_kv_heads=2)
    lm = LM(cfg)
    merged = merge_model(lm.init(jax.random.PRNGKey(0)), cfg.quant)

    k, slots, prompt_len = 3, 4, 4
    gens = (24, 8, 16, 12)
    trace = make_trace(slots, cfg.vocab, seed=0, prompt_lens=(prompt_len,),
                       gen_lens=gens)
    useful = sum(r.max_new_tokens for r in trace)
    max_len = prompt_len + max(gens) + k   # +k: verify headroom

    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        def drain(eng):
            eng.reset()
            for r in trace:
                eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
            eng.run()
            return eng.stats

        def accepted_per_step(eng):
            # steady-state metric: skip the prefill ramp (mixed
            # prefill/decode dispatches), measure from the first
            # all-decoding dispatch to drain.  tokens_out counts
            # committed tokens, busy_slot_steps counts TARGET rows
            # consumed (k+1 per slot per spec dispatch), so
            # d_tok * (k+1) / d_busy = mean tokens committed per verify
            # step per busy slot — 1.0 is the non-speculative engine
            eng.reset()
            for r in trace:
                eng.submit(r.prompt, r.max_new_tokens, r.eos_id, rid=r.rid)
            while eng.sched.has_work and not eng.sched.all_decoding:
                eng.step_once()
            t0, b0 = eng.stats.tokens_out, eng.stats.busy_slot_steps
            while eng.sched.has_work:
                eng.step_once()
            d_tok = eng.stats.tokens_out - t0
            d_busy = eng.stats.busy_slot_steps - b0
            return d_tok * (eng.speculate + 1) / max(d_busy, 1)

        rows = []
        for name, policy in (("intq8", "*=intq8"), ("int2", "*=int2")):
            eng = ContinuousEngine(lm, merged, n_slots=slots,
                                   max_len=max_len,
                                   prefill_chunk=prompt_len,
                                   decode_burst=1, speculate=k,
                                   drafter=policy)
            drain(eng)                      # warm (compile)
            st = min((drain(eng) for _ in range(3)),
                     key=lambda s: s.seconds)
            rows.append((name, eng, st, accepted_per_step(eng)))
        burst = ContinuousEngine(lm, merged, n_slots=slots, max_len=max_len,
                                 prefill_chunk=prompt_len, decode_burst=8)
        drain(burst)                        # warm (compile)
        st_b = min((drain(burst) for _ in range(3)),
                   key=lambda s: s.seconds)

    emit("spec", "burst-baseline-tok_s", round(st_b.tok_per_s, 1),
         f"non-speculative decode_burst=8 on the same trace "
         f"({useful} useful tokens, occupancy {st_b.occupancy:.0%})")
    for name, eng, st, per_step in rows:
        note = (f"k={k} {name} self-drafter over the shared merged base; "
                f"{st.accepted_tokens}/{st.proposed_tokens} drafts "
                f"accepted, {st.dispatches} dispatches")
        emit("spec", f"{name}-accepted-per-step", round(per_step, 3),
             f"committed tokens per target model-step per busy slot, "
             f"all-decoding phase (1.0 = non-speculative); {note}")
        emit("spec", f"{name}-acceptance-rate",
             round(st.acceptance_rate, 3), note)
        emit("spec", f"{name}-tok_s", round(st.tok_per_s, 1),
             f"wall-clock incl. drafter forwards (CPU interpret; "
             f"see table note); {note}")
    headline = rows[0][3]
    assert headline > 1.0, (
        f"speculation must beat one token per model step per slot on the "
        f"intq8 self-draft trace, got {headline:.3f}")


def roofline_summary():
    path = "experiments/roofline.json"
    if not os.path.exists(path):
        emit("roofline", "missing", 0, "run repro.launch.dryrun + benchmarks.roofline_report first")
        return
    with open(path) as f:
        rows = json.load(f)
    for r in rows:
        emit("roofline", f"{r['arch']}-{r['cell']}",
             round(r["bound_s"], 4),
             f"bound={r['dominant'].replace('_s','')} useful={r['useful_ratio']:.2f}")


TABLES = {
    "table1": table1_mmlu_proxy,
    "table2": table2_efficiency,
    "table3": table3_commonsense_proxy,
    "table4": table4_other_families,
    "table5": table5_group_size,
    "table6": table6_datasets,
    "fig3": fig3_dataset_size,
    "ablation_rank": ablation_rank,
    "kernels": kernels_bench,
    "decode": decode_bench,
    "engine": engine_bench,
    "paged": paged_bench,
    "adapters": adapters_bench,
    "slo": slo_bench,
    "spec": spec_bench,
    "roofline": roofline_summary,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    picks = args.only.split(",") if args.only else list(TABLES)
    print("table,name,value,derived")
    t0 = time.time()
    for t in picks:
        TABLES[t]()
    os.makedirs("experiments", exist_ok=True)
    # merge into the existing artifact: a partial `--only` run must not
    # drop the other tables' recorded reference numbers
    path = "experiments/bench_results.json"
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
    for k, d in RESULTS.items():
        merged[k] = {n: list(v) for n, v in d.items()}
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)
    # REPRO_COMPILE_GUARD=1: every engine built above declared budgets
    # into the ambient guard; a retrace storm fails the bench run loudly
    # instead of silently skewing the timings it just printed
    from repro.runtime import compile_guard
    guard = compile_guard.current()
    if guard is not None:
        print(guard.summary())
        guard.check()
    print(f"# done in {time.time() - t0:.0f}s -> experiments/bench_results.json")


if __name__ == "__main__":
    main()
