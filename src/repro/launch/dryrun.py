import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh) cell
lowers AND compiles under the production sharding config, and extract the
artifacts the roofline analysis reads (memory_analysis, cost_analysis,
HLO text with collectives).

The two lines above MUST stay first: JAX locks the device count at first
backend init, and the production meshes need 512 host-platform devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --cell train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod

Artifacts land in experiments/dryrun/<arch>__<cell>__<mesh>.json (+ .hlo
when --save-hlo).  Existing artifacts are skipped unless --force.
"""

import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp


def build_step(arch: str, cell_name: str, mesh, gen_len: int = 0,
               policy: str = "", reduced: bool = False):
    """Returns (lower_fn, abstract_args) for the cell's step function.

    ``gen_len > 0`` builds decode cells as the serve scan-generate program
    (`steps.make_generate_step`) instead of a single decode step — the
    same whole-generation program `launch.serve` runs, proved to lower
    and compile under the production shardings.  ``policy`` applies
    per-layer PolicyTree rules (e.g. ``"*=int4,*/attn/wo=int8,lm_head=fp"``)
    so mixed-precision deployments compile-check like uniform ones.
    """
    import repro.configs as C
    from repro.configs.base import SHAPES
    from repro.configs.shapes import input_specs
    from repro.models.lm import LM
    from repro.launch import steps as S

    cfg = C.reduced(arch) if reduced else C.get(arch)
    if policy:
        from repro.core.schemes import PolicyTree
        cfg = cfg.scaled(quant=PolicyTree.parse(policy, base=cfg.quant.default))
    cell = SHAPES[cell_name]
    lm = LM(cfg)
    kind, kw = input_specs(cfg, cell)

    if kind == "train":
        jit_for, (tspec, fspec, ospec) = S.make_train_step(lm, mesh)
        trainable, frozen, opt = S.abstract_train_state(lm)
        jitted, bspec = jit_for(kw["batch"])
        args = (trainable, frozen, opt, kw["batch"])
    elif kind == "prefill":
        jit_for, pspec = S.make_prefill_step(lm, mesh)
        params = S.abstract_params(lm)
        jitted, bspec = jit_for(kw["batch"])
        args = (params, kw["batch"])
    elif gen_len:  # decode, whole scan-generation program
        jit_for, pspec = S.make_generate_step(lm, mesh, gen_len)
        params = S.abstract_params(lm)
        jitted, cspec = jit_for(kw["cache"])
        b = kw["tokens"].shape[0]
        logits = jax.ShapeDtypeStruct((b, cfg.vocab), jnp.float32)
        args = (params, kw["cache"], logits)
    else:  # decode, single step
        jit_for, pspec = S.make_decode_step(lm, mesh)
        params = S.abstract_params(lm)
        jitted, cspec = jit_for(kw["cache"])
        args = (params, kw["cache"], kw["tokens"])
    return jitted, args


COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")


def run_cell(arch: str, cell_name: str, mesh_kind: str, outdir: str,
             save_hlo: bool = False, force: bool = False,
             gen_len: int = 0, policy: str = "", reduced: bool = False) -> dict:
    from repro.configs.base import SHAPES
    from repro.launch.mesh import make_production_mesh

    if SHAPES[cell_name].kind != "decode":
        gen_len = 0  # only decode cells have a generation program
    tag = f"{arch}__{cell_name}__{mesh_kind}"
    if gen_len:
        tag += f"__gen{gen_len}"
    if reduced:
        tag += "__reduced"
    if policy:
        import hashlib
        digest = hashlib.sha1(policy.encode()).hexdigest()[:8]
        tag += "__pol" + re.sub(r"[^A-Za-z0-9]+", "-", policy)[:40] + "-" + digest
    path = os.path.join(outdir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    with jax.set_mesh(mesh):
        jitted, args = build_step(arch, cell_name, mesh, gen_len=gen_len,
                                  policy=policy, reduced=reduced)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    rec = {
        "arch": arch, "cell": cell_name, "mesh": mesh_kind,
        "n_devices": int(len(mesh.devices.flat)),
        "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_size_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_size_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_size_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_size_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "cost": {k: float(cost.get(k, 0.0)) for k in
                 ("flops", "bytes accessed", "transcendentals")},
        "collective_ops_toplevel": len(COLLECTIVE_RE.findall(hlo)),
    }
    os.makedirs(outdir, exist_ok=True)
    if save_hlo:
        with open(os.path.join(outdir, tag + ".hlo"), "w") as f:
            f.write(hlo)
        rec["hlo_path"] = os.path.join(outdir, tag + ".hlo")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dryrun] OK  {tag}  lower={t_lower:.1f}s compile={t_compile:.1f}s "
          f"args={rec['memory']['argument_size_bytes']/2**30:.2f}GiB(total) "
          f"temp={rec['memory']['temp_size_bytes']/2**30:.2f}GiB "
          f"flops={rec['cost']['flops']:.3e}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--cell")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--gen-len", type=int, default=0,
                    help="decode cells: compile the whole scan-generation "
                         "program (serve path) instead of one decode step")
    ap.add_argument("--policy", default="",
                    help='per-layer policy rules, e.g. '
                         '"*=int4,*/attn/wo=int8,lm_head=fp"')
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (CPU smoke) config sizes")
    args = ap.parse_args(argv)

    import repro.configs as C
    from repro.configs.base import cells_for

    if args.all:
        jobs = [(a, c.name) for a in C.ASSIGNED for c in cells_for(a)]
    else:
        assert args.arch and args.cell, "--arch/--cell or --all"
        jobs = [(args.arch, args.cell)]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch, cell in jobs:
        for mk in meshes:
            try:
                run_cell(arch, cell, mk, args.outdir,
                         save_hlo=args.save_hlo, force=args.force,
                         gen_len=args.gen_len, policy=args.policy,
                         reduced=args.reduced)
            except Exception as e:
                failures.append((arch, cell, mk, repr(e)))
                print(f"[dryrun] FAIL {arch}__{cell}__{mk}: {e}")
                traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES")
        sys.exit(1)
    print("[dryrun] all cells passed")


if __name__ == "__main__":
    main()
