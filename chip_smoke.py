"""Chip smoke test: the paper's whole path on a TPU, at starcoder2-7b's
published widths (all 32 layers), through the entry points a user calls.

  device  the first JAX device must be a TPU; there is no CPU fallback
  train   QA-LoRA fine-tune steps on the INT4 base
          (``launch.steps.make_train_step``, synthetic alpaca stream)
  merge   ``merge_tree`` into INT4 (the paper's claim (ii)): every
          quantized linear keeps its uint8 codes, and the merged model's
          logits agree with the adapter model's
  serve   ``ContinuousEngine`` (4 slots, 8 requests) against the static
          ``generate_scan`` path
  kernel  the engine's decode burst holds the Pallas GEMV (merged INT-N
          linears take it on TPU at decode rows, so the serve phase's
          engine tokens, checked against XLA's teacher-forced argmax, ran
          through it), and each kernel against ``kernels/ref.py`` at a
          full-width shape

Run from the repository root:

    python chip_smoke.py              # one chip: every phase above
    python chip_smoke.py --chips 4    # only the four-chip train phase:
                                      # a 2x2 mesh against one device

Weights are random, drawn from ``--seed``; the data stream is generated
from it.  Each phase prints one line.  The last line of a passing run is
one JSON object naming the device; any failure (no TPU, a phase that
fails) exits non-zero without it.  The persistent compile cache goes
where ``JAX_COMPILATION_CACHE_DIR`` says, else to ``<repo>/.jax_cache``.
Times printed here are one unrepeated run each, not benchmark results.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "starcoder2-7b"
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 2, 512
# AdamW moves each adapter entry by about lr per step, so after three
# steps at 2e-4 (the train driver's default) the adapters shift logits far
# above bf16 rounding noise: the merge check then has an effect to keep.
TRAIN_LR = 2e-4
MERGE_PROMPT = (2, 256)
N_SLOTS, MAX_LEN, PREFILL_CHUNK, DECODE_BURST = 4, 1024, 64, 4
# the static prefill's flash attention tiles prompts longer than its
# 256-row query chunk in whole chunks, so lengths past 256 are multiples
PROMPT_LENS = (64, 512, 128, 256, 512, 64, 256, 128)
GEN_LEN = 32

# Two bf16 paths that compute the same logits in another order (chunked
# ragged prefill against one whole prefill, Pallas against XLA) may pick
# different argmaxes only where the top two logits are closer than the
# paths' rounding difference.  The head emits bf16 logits: the top logits
# of this random-weight model sit near 4-8, where one bf16 step is 2^-5,
# and the residual stream feeding the head carries 32 layers of bf16
# rounding.  So a split at a top-2 margin under 0.25 logit (8 such steps)
# is a near-tie; above it, one of the paths is wrong.
NEAR_TIE = 0.25
# Kernels dequantize to bf16 and accumulate in f32; kernels/ref.py stays in
# f32.  Their outputs then differ by bf16 rounding of the weights (2^-9
# relative per element) and of the output: 1% of the output scale allows
# that and still catches a misplaced tile, which is off by O(1).
KERNEL_RTOL = 1e-2
# Four-chip loss against one chip: the same bf16 step with its reductions
# split over a 2x2 mesh.  The loss averages 1024 token losses, so
# reduction-order noise stays far below 1% of it.
LOSS_RTOL = 1e-2


class PhaseFailed(RuntimeError):
    pass


def _require(ok: bool, msg: str):
    if not ok:
        raise PhaseFailed(msg)


def _label(dev) -> str:
    return f"{dev.platform} {dev.device_kind}"


def _peak_gb(dev) -> float:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_phase():
    import jax

    devs = jax.devices()
    dev = devs[0]
    _require(dev.platform == "tpu",
             f"jax.devices()[0].platform is {dev.platform!r}, not 'tpu'")
    print(f"[device] ok: {len(devs)} x {_label(dev)}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def train_phase(lm, mesh, seed: int, steps: int = TRAIN_STEPS,
                batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ):
    """QA-LoRA steps through ``make_train_step`` on ``mesh``.  Returns the
    placed ``(trainable, frozen)`` and the per-step losses and grad
    norms; raises when a loss or grad norm is not finite."""
    import jax
    import jax.numpy as jnp

    from repro.data import make_stream
    from repro.launch import steps as S
    from repro.optim import AdamWConfig, adamw_init, split_params

    params = jax.jit(lm.init)(jax.random.PRNGKey(seed))
    with jax.set_mesh(mesh):
        trainable, frozen = split_params(params)
        opt = adamw_init(trainable)
        jit_for, specs = S.make_train_step(lm, mesh, AdamWConfig(lr=TRAIN_LR))
        trainable, frozen, opt = S.place(mesh, specs, (trainable, frozen, opt))
        del params  # on a larger mesh, the unplaced copy on one device
        stream = make_stream("alpaca", vocab=lm.cfg.vocab, seq_len=seq,
                             global_batch=batch, seed=seed)
        step, losses, gnorms = None, [], []
        for _ in range(steps):
            toks, labs = stream.next_batch()
            b = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}
            if step is None:
                step, _ = jit_for(jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), b))
            trainable, opt, metrics = step(trainable, frozen, opt, b)
            losses.append(float(metrics["loss"]))
            gnorms.append(float(metrics["grad_norm"]))
    _require(bool(np.all(np.isfinite(losses + gnorms))),
             f"non-finite training: losses {losses}, grad norms {gnorms}")
    return trainable, frozen, losses, gnorms


def _linears(tree):
    from repro.core.schemes import map_linears

    found = {}
    map_linears(tree, lambda path, lp: found.setdefault(path, lp))
    return found


def _without_adapters(tree):
    """The same tree with every QA-LoRA B zeroed: the bare INT4 base."""
    import jax.numpy as jnp

    from repro.core.schemes import map_linears

    def zero_b(path, lp):
        if lp.scheme != "qalora":
            return lp
        ad = lp.data["ad"]
        data = {**lp.data, "ad": type(ad)(a=ad.a, b=jnp.zeros_like(ad.b))}
        return dataclasses.replace(lp, data=data)

    return map_linears(tree, zero_b)


def merge_phase(lm, trained, seed: int, prompt_shape=MERGE_PROMPT):
    """Merge, check the storage stays INT-N, and compare logits with the
    adapter model.  Returns the merged tree and (error, adapter effect),
    both relative to the logit scale."""
    import jax
    import jax.numpy as jnp

    from repro.core.schemes import merge_tree
    from repro.launch.serve import MERGE_LOGIT_RTOL, merge_error

    merged = merge_tree(trained)
    before, after = _linears(trained), _linears(merged)
    n_quant = 0
    for path, lp in before.items():
        if lp.scheme != "qalora":
            continue
        mp = after[path]
        q0, q1 = lp.data["q"], mp.data["q"]
        n_quant += int(np.prod(q0.qweight.shape[:-2]))  # stacked layers
        _require(mp.scheme == "intq" and q1.qweight.dtype == jnp.uint8,
                 f"{path}: merged to {mp.scheme} with "
                 f"{q1.qweight.dtype} codes, not intq/uint8")
        _require(bool(jnp.array_equal(q0.qweight, q1.qweight))
                 and bool(jnp.array_equal(q0.scale, q1.scale)),
                 f"{path}: the merge changed the integer codes or scales")
    _require(n_quant > 0, "the trained tree holds no qalora linear")

    prompts = jnp.asarray(np.random.default_rng(seed).integers(
        4, lm.cfg.vocab, size=prompt_shape), jnp.int32)
    prefill = jax.jit(lm.prefill)
    logits = {name: prefill(tree, {"tokens": prompts})[0]
              for name, tree in (("adapter", trained), ("merged", merged),
                                 ("base", _without_adapters(trained)))}
    err = merge_error(logits["adapter"], logits["merged"])
    effect = merge_error(logits["adapter"], logits["base"])
    _require(err < MERGE_LOGIT_RTOL,
             f"merged logits off by {err:.3e} of the logit scale "
             f"(limit {MERGE_LOGIT_RTOL:.0e})")
    # the check above means something only if the adapters move the
    # logits far more than the merge does
    _require(effect > 10 * err,
             f"adapter effect {effect:.3e} is not above the merge error "
             f"{err:.3e}: the merge check cannot tell a dropped adapter")
    return merged, n_quant, err, effect


def _prompts(vocab: int, seed: int, lens=PROMPT_LENS):
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(4, vocab, size=n).astype(np.int32) for n in lens]


def _engine(lm, params, n_slots=N_SLOTS, max_len=MAX_LEN,
            prefill_chunk=PREFILL_CHUNK, decode_burst=DECODE_BURST):
    from repro.serving import ContinuousEngine

    return ContinuousEngine(lm, params, n_slots=n_slots, max_len=max_len,
                            prefill_chunk=prefill_chunk,
                            decode_burst=decode_burst)


def _serve_timed(eng, prompts, gen_len):
    """Drive the engine step by step; returns streams, seconds and the
    time to each request's first token."""
    rids = [eng.submit(p, gen_len) for p in prompts]
    ttft, t0 = {}, time.perf_counter()
    while eng.sched.has_work:
        eng.step_once()
        now = time.perf_counter()
        live = [s.req.rid for s in eng.sched.slots
                if s is not None and s.emitted]
        for rid in live + list(eng.sched.outputs):
            ttft.setdefault(rid, now - t0)
    secs = time.perf_counter() - t0
    out = eng.sched.outputs
    unfinished = [r for r in rids if len(out.get(r, ())) != gen_len]
    _require(not unfinished, f"unfinished requests: {unfinished}")
    return [list(out[r]) for r in rids], secs, [ttft[r] for r in rids]


def teacher_margins(lm, params, prompts, streams):
    """Teacher-forced top-2 logit margin and argmax at every generated
    position: ``margin[i][j]`` belongs to the logits that choose token j
    of request i after ``prompts[i] + streams[i][:j]``."""
    import jax
    import jax.numpy as jnp

    seqs = [np.concatenate([p, np.asarray(s[:-1], np.int32)])
            for p, s in zip(prompts, streams)]
    width = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    n_new = np.asarray([len(s) for s in seqs], np.int32)

    def fn(params, cache, toks, n_new):
        logits, _, _ = lm.verify_ragged(params, cache, toks, n_new)
        top2 = jax.lax.top_k(logits.astype(jnp.float32), 2)[0]
        return top2[..., 0] - top2[..., 1], jnp.argmax(logits, -1)

    cache = lm.init_cache(len(seqs), width, dtype=jnp.float32)
    margin, amax = jax.jit(fn)(params, cache, jnp.asarray(toks),
                               jnp.asarray(n_new))
    margin, amax = np.asarray(margin), np.asarray(amax)
    cols = [len(p) - 1 + np.arange(len(s)) for p, s in zip(prompts, streams)]
    return ([margin[i, c] for i, c in enumerate(cols)],
            [amax[i, c] for i, c in enumerate(cols)])


def first_split(got, want, margins, what: str):
    """Index of the first token where ``got`` leaves ``want`` (None when
    they agree), after checking that the split is a bf16 near-tie."""
    for j, (a, b) in enumerate(zip(got, want)):
        if a != b:
            _require(margins[j] < NEAR_TIE,
                     f"{what}: token {j} is {a}, not {b}, at a top-2 margin "
                     f"of {margins[j]:.3f} (near-tie limit {NEAR_TIE})")
            return j
    return None


def serve_phase(lm, merged, mesh, seed: int, gen_len: int = GEN_LEN,
                prompt_lens=PROMPT_LENS, **engine_kw):
    import jax

    from repro.launch.serve import generate_scan
    from repro.runtime.compile_guard import CompileGuard

    prompts = _prompts(lm.cfg.vocab, seed, prompt_lens)
    max_len = engine_kw.get("max_len", MAX_LEN)
    with CompileGuard("chip-smoke serve") as guard:
        eng = _engine(lm, merged, **engine_kw)
        first, _, _ = _serve_timed(eng, prompts, gen_len)  # compiles
        eng.reset()
        streams, secs, ttft = _serve_timed(eng, prompts, gen_len)
        guard.check()
    _require(streams == first, "a second run of the same requests on the "
                               "warm engine gave other tokens")
    tok_s = eng.stats.tokens_out / secs

    # the static reference: one prefill + scan-decode batch per length
    ref = [None] * len(prompts)
    with jax.set_mesh(mesh):
        for n in sorted(set(prompt_lens)):
            idx = [i for i, p in enumerate(prompts) if len(p) == n]
            toks, _ = generate_scan(lm, mesh, merged,
                                    np.stack([prompts[i] for i in idx]),
                                    gen_len, max_len)
            for row, i in enumerate(idx):
                ref[i] = [int(t) for t in toks[row]]

    margins, argmax = teacher_margins(lm, merged, prompts, streams)
    splits = [first_split(s, r, m, f"request {i} engine vs generate_scan")
              for i, (s, r, m) in enumerate(zip(streams, ref, margins))]
    for i, (s, a, m) in enumerate(zip(streams, argmax, margins)):
        bad = [j for j in range(len(s)) if s[j] != a[j] and m[j] >= NEAR_TIE]
        _require(not bad, f"request {i}: engine tokens at {bad} are not the "
                          f"teacher-forced argmax and not near-ties")
    return dict(prompts=prompts, streams=streams, margins=margins,
                splits=splits, tok_s=tok_s, ttft=ttft,
                compiles=guard.counts())


def kernel_refs(seed: int, k: int, n: int, rank: int, bits: int = 4,
                group_size: int = 32):
    """Each quantized kernel once against kernels/ref.py at one [k, n];
    returns {kernel: error relative to the output scale}."""
    import jax
    import jax.numpy as jnp

    from repro.core.qalora import QALoRAParams, bank_adapter_delta
    from repro.core.quant import quantize
    from repro.kernels import (qalora_matmul, qalora_matmul_ref,
                               qalora_slot_matmul, qmatmul, qmatmul_ref)

    bf16 = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    qt = quantize(jax.random.normal(ks[0], (k, n)) / np.sqrt(k), bits,
                  group_size, scale_dtype=bf16)
    g = k // group_size
    p = QALoRAParams(
        a=(jax.random.normal(ks[1], (g, rank)) / np.sqrt(g)).astype(bf16),
        b=(jax.random.normal(ks[2], (rank, n)) * 0.01).astype(bf16))
    # four banked adapters, row i = i * A (row 0 is the null adapter);
    # slot i uses adapter i
    ids = jnp.arange(4, dtype=jnp.int32)
    a_bank = p.a[None] * ids[:, None, None].astype(bf16)
    b_bank = jnp.broadcast_to(p.b, (4,) + p.b.shape)
    x = lambda m: jax.random.normal(ks[3], (m, k)).astype(bf16)  # noqa: E731

    def rel(y, yr):
        y, yr = np.asarray(y, np.float32), np.asarray(yr, np.float32)
        return float(np.abs(y - yr).max() / np.abs(yr).max())

    got = {
        "qmatvec m=1": qmatmul(x(1), qt),
        "qalora_matvec m=4": qalora_matmul(x(4), qt, p, s=2.0),
        "qalora_slot_matvec m=4": qalora_slot_matmul(x(4), qt, a_bank, b_bank,
                                                     ids, s=2.0),
        "qmatmul m=256": qmatmul(x(256), qt),
        "qalora_matmul m=256": qalora_matmul(x(256), qt, p, s=2.0),
    }
    # the references in f32 at full precision (the TPU default would round
    # f32 matmul inputs to bf16); the kernels keep their own bf16 dots,
    # which Mosaic refuses at fp32 contract precision
    with jax.default_matmul_precision("highest"):
        want = {
            "qmatvec m=1": qmatmul_ref(x(1), qt),
            "qalora_matvec m=4": qalora_matmul_ref(x(4), qt, p, 2.0),
            "qalora_slot_matvec m=4": (
                qmatmul_ref(x(4), qt, jnp.float32)
                + bank_adapter_delta(x(4).astype(jnp.float32),
                                     a_bank.astype(jnp.float32),
                                     b_bank.astype(jnp.float32), ids, 2.0,
                                     group_size)),
            "qmatmul m=256": qmatmul_ref(x(256), qt),
            "qalora_matmul m=256": qalora_matmul_ref(x(256), qt, p, 2.0),
        }
    return {name: rel(got[name], want[name]) for name in got}


def kernel_phase(lm, merged, seed: int, **engine_kw):
    import jax.numpy as jnp

    from repro.serving import engine as engine_mod

    eng = _engine(lm, merged, **engine_kw)
    zi = jnp.zeros((eng.n_slots,), jnp.int32)
    hlo = engine_mod._JIT_BURST.lower(
        lm, merged, eng.aux, eng.cache, zi, zi, zi,
        k_steps=1).compile().as_text()
    _require("tpu_custom_call" in hlo,
             "the decode burst's HLO has no tpu_custom_call: the merged "
             "INT-N linears did not take the Pallas GEMV")
    cfg = lm.cfg
    errs = kernel_refs(seed, cfg.d_model, cfg.d_ff, cfg.quant.default.rank,
                       cfg.quant.default.bits, cfg.quant.default.group_size)
    bad = {name: e for name, e in errs.items() if not e < KERNEL_RTOL}
    _require(not bad, f"kernels off their reference by more than "
                      f"{KERNEL_RTOL:.0e} of the output scale: {bad}")
    return errs


def four_chip_phase(lm, seed: int, **train_kw):
    """The train step on a 2x2 mesh against the same step on
    jax.devices()[0]; the base and the adapters must be spread over all
    four devices."""
    import jax

    from repro.launch.mesh import make_local_mesh

    devs = jax.devices()
    _require(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    _, _, one, _ = train_phase(lm, make_local_mesh(1, 1), seed, **train_kw)
    trainable, frozen, four, _ = train_phase(lm, make_local_mesh(2, 2), seed,
                                             **train_kw)
    shares = {}
    for name, tree in (("base", frozen), ("adapters", trainable)):
        per_dev = dict.fromkeys(devs[:4], 0)
        leaves = jax.tree.leaves(tree)
        for x in leaves:
            for shard in x.addressable_shards:
                per_dev[shard.device] += shard.data.nbytes
        total = sum(x.nbytes for x in leaves)
        shares[name] = [per_dev[d] / total for d in devs[:4]]
        _require(all(s > 0 for s in shares[name]),
                 f"{name}: some device holds none of it: {shares[name]}")
        # sharded over "model", so no device holds all of it
        _require(max(shares[name]) < 0.75,
                 f"{name} is not spread: per-device shares {shares[name]}")
    rel = [abs(a - b) / abs(b) for a, b in zip(four, one)]
    _require(max(rel) < LOSS_RTOL,
             f"2x2-mesh losses {four} differ from one device's {one} by "
             f"up to {max(rel):.2e} (limit {LOSS_RTOL:.0e})")
    return one, four, rel, shares


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_one_chip(seed: int):
    import jax

    import repro.configs as C
    from repro.launch.mesh import make_local_mesh
    from repro.models.lm import LM

    dev = jax.devices()[0]
    on = f"on {_label(dev)}"
    lm = LM(C.get(ARCH))
    mesh = make_local_mesh()

    t0 = time.perf_counter()
    trainable, frozen, losses, gnorms = train_phase(lm, mesh, seed)
    from repro.optim import merge_params
    trained = merge_params(trainable, frozen)
    print(f"[train] ok {on}: {ARCH} QA-LoRA int4 g32, {TRAIN_STEPS} steps x "
          f"{TRAIN_BATCH}x{TRAIN_SEQ}, loss {losses}, grad norm {gnorms}, "
          f"peak {_peak_gb(dev):.2f} GB, {time.perf_counter() - t0:.0f}s "
          f"incl. compile", flush=True)
    del trainable, frozen

    merged, n_quant, err, effect = merge_phase(lm, trained, seed)
    del trained
    print(f"[merge] ok {on}: {n_quant} qalora matrices -> intq with their "
          f"uint8 codes and scales unchanged; logits max|adapter - merged| "
          f"= {err:.3e} of the logit scale (adapter effect {effect:.3e})",
          flush=True)

    serve = serve_phase(lm, merged, mesh, seed)
    diverged = {i: j for i, j in enumerate(serve["splits"]) if j is not None}
    print(f"[serve] ok {on}: {len(PROMPT_LENS)} requests (prompts "
          f"{min(PROMPT_LENS)}-{max(PROMPT_LENS)}) x {GEN_LEN} tokens on "
          f"{N_SLOTS} slots all finished; vs generate_scan: "
          f"{len(PROMPT_LENS) - len(diverged)} identical, near-tie splits "
          f"{diverged}; unclaimed single run: {serve['tok_s']:.1f} tok/s, "
          f"TTFT {min(serve['ttft']) * 1e3:.0f}-{max(serve['ttft']) * 1e3:.0f}"
          f" ms; compiles {serve['compiles']}", flush=True)

    errs = kernel_phase(lm, merged, seed)
    print(f"[kernel] ok {on}: decode burst HLO holds tpu_custom_call; vs "
          f"kernels/ref.py at {lm.cfg.d_model}->{lm.cfg.d_ff}: "
          + ", ".join(f"{k} {v:.1e}" for k, v in errs.items()), flush=True)
    print(f"[memory] peak {_peak_gb(dev):.2f} GB {on}", flush=True)


def run_four_chips(seed: int):
    import jax

    import repro.configs as C
    from repro.models.lm import LM

    one, four, rel, shares = four_chip_phase(LM(C.get(ARCH)), seed)
    devs = jax.devices()[:4]
    print(f"[four-chip] ok on 4 x {_label(devs[0])}: {ARCH} QA-LoRA losses "
          f"2x2 mesh {four} vs one device {one} (rel diff {max(rel):.1e}); "
          f"per-device shares base {['%.2f' % s for s in shares['base']]}, "
          f"adapters {['%.2f' % s for s in shares['adapters']]}; peak "
          f"{['%.2f GB' % _peak_gb(d) for d in devs]}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip train phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.runtime.compile_cache import enable_compile_cache

        print(f"[cache] {enable_compile_cache()}", flush=True)
        device = device_phase()
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    except Exception:
        traceback.print_exc()
        print("[chip_smoke] FAILED", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
