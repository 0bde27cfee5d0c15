"""End-to-end QA-LoRA fine-tuning driver.

Wires every substrate together: config -> model -> quantized init ->
adapter-only AdamW -> sharded train step -> data stream -> async
checkpointing -> fault-tolerant restartable loop (straggler detection,
preemption-safe save, O(1) data skip-ahead).

CPU-runnable with reduced configs:
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --reduced \
      --steps 100 --seq-len 64 --global-batch 8 --ckpt-dir /tmp/ckpt

On a real pod the same driver runs with the production mesh
(--mesh pod|multipod) and the full config; --mesh local (default) is a
1x1 mesh over this host's first device — one TPU chip, or the CPU.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama7b-proxy")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--dataset", default="alpaca")
    ap.add_argument("--lr", type=float, default=2e-4)
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--group-size", type=int, default=0, help="0 = config default")
    ap.add_argument("--mode", default="qalora",
                    choices=["qalora", "qlora", "lora", "fp"])
    ap.add_argument("--policy", default="",
                    help='per-layer policy rules overriding --mode, e.g. '
                         '"*=int4,*/attn/wo=int8,lm_head=fp"')
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="local",
                    choices=["local", "pod", "multipod"])
    ap.add_argument("--sync-every", type=int, default=0,
                    help="cross-pod int8 adapter sync cadence (multipod)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    import dataclasses
    import repro.configs as C
    from repro.models.lm import LM
    from repro.optim import AdamWConfig, adamw_init, split_params, count_params
    from repro.data import make_stream
    from repro.checkpoint import CheckpointManager
    from repro.runtime import RestartableLoop, PreemptionGuard
    from repro.launch import steps as S
    from repro.launch.mesh import make_production_mesh, make_local_mesh

    cfg = C.reduced(args.arch) if args.reduced else C.get(args.arch)
    q = dataclasses.replace(cfg.quant.default, mode=args.mode, bits=args.bits,
                            **({"group_size": args.group_size} if args.group_size else {}))
    if args.policy:
        from repro.core.schemes import PolicyTree
        q = PolicyTree.parse(args.policy, base=q)
    cfg = cfg.scaled(quant=q)
    lm = LM(cfg)

    mesh = (make_local_mesh() if args.mesh == "local"
            else make_production_mesh(multi_pod=(args.mesh == "multipod")))

    opt_cfg = AdamWConfig(lr=args.lr, schedule="constant")
    with jax.set_mesh(mesh):
        params = lm.init(jax.random.PRNGKey(0))
        trainable, frozen = split_params(params)
        opt_state = adamw_init(trainable)
        print(f"[train] arch={cfg.name} mode={q.mode} bits={q.bits} "
              f"trainable={count_params(trainable):,} "
              f"frozen={count_params(frozen):,}")

        jit_for, (tspec, fspec, ospec) = S.make_train_step(lm, mesh, opt_cfg)

        stream = make_stream(args.dataset, vocab=cfg.vocab,
                             seq_len=args.seq_len,
                             global_batch=args.global_batch)

        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if ckpt and ckpt.latest_step() is not None:
            start = ckpt.latest_step()
            state = ckpt.restore(start, {"t": trainable, "o": opt_state})
            trainable, opt_state = state["t"], state["o"]
            stream.skip_to(start)
            print(f"[train] resumed from step {start}")
        if ckpt:
            ckpt.save_base(frozen)
        trainable, frozen, opt_state = S.place(
            mesh, (tspec, fspec, ospec), (trainable, frozen, opt_state))

        sync = (S.make_sync_step(mesh, tspec)
                if args.sync_every and "pod" in mesh.shape else None)

        jitted = None
        state = {"t": trainable, "o": opt_state}

        def save_cb(step):
            if ckpt:
                ckpt.save(step, state)

        def body(step):
            nonlocal jitted, state
            toks, labs = stream.next_batch()
            batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}
            if cfg.frontend == "vision":
                f = jnp.zeros((toks.shape[0], cfg.frontend_len, cfg.d_model),
                              q.dtype)
                batch = {"tokens": batch["tokens"][:, cfg.frontend_len:],
                         "labels": batch["labels"][:, cfg.frontend_len:],
                         "frontend": f}
            if cfg.family == "encdec":
                half = toks.shape[1] // 2
                batch = {"tokens": batch["tokens"][:, :half],
                         "labels": batch["labels"][:, :half],
                         "src": jnp.zeros((toks.shape[0], half, cfg.d_model),
                                          q.dtype)}
            if jitted is None:
                jitted, _ = jit_for(jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch))
            t, o, metrics = jitted(state["t"], frozen, state["o"], batch)
            state = {"t": t, "o": o}
            if args.sync_every and sync and (step + 1) % args.sync_every == 0:
                state["t"] = sync(state["t"])
            if step % args.log_every == 0:
                print(f"[train] step={step} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f}")
            return {"loss": float(metrics["loss"])}

        with PreemptionGuard() as guard:
            loop = RestartableLoop(args.steps, args.ckpt_every, save_cb,
                                   start_step=start, guard=guard)
            t0 = time.time()
            end = loop.run(body)
            dt = time.time() - t0
        if ckpt:
            ckpt.wait()
            ckpt.close()
        print(f"[train] finished at step {end} "
              f"({dt / max(end - start, 1):.3f}s/step, "
              f"{len(loop.stragglers)} straggler steps)")


if __name__ == "__main__":
    main()
