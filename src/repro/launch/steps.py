"""jit-compiled train / prefill / serve steps with production shardings.

``make_train_step``: QA-LoRA fine-tuning — grads flow ONLY to adapter
params (the quantized base is frozen; no gradient buffers, no optimizer
state for it).  AdamW + grad clip per the paper's recipe.

All functions also serve the dry-run: they accept abstract
(ShapeDtypeStruct) inputs for ``.lower().compile()``.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.lm import LM
from repro.runtime import tracing
from repro.optim import (AdamWConfig, adamw_init, adamw_update, split_params,
                         merge_params, compressed_mean)
from repro.sharding import (param_specs, batch_spec_tree, cache_spec_tree,
                            spec_to_sharding)


def abstract_params(lm: LM):
    # the key is made inside eval_shape too: no array is created, so this
    # also runs under a mesh of described (compile-only) devices
    return jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))


def abstract_train_state(lm: LM):
    params = abstract_params(lm)
    trainable, frozen = split_params(params)
    opt = jax.eval_shape(adamw_init, trainable)
    return trainable, frozen, opt


def train_state_specs(lm: LM, mesh: Mesh):
    trainable, frozen, opt = abstract_train_state(lm)
    tspec = param_specs(trainable, mesh)
    fspec = param_specs(frozen, mesh)
    ospec = {"mu": param_specs(opt["mu"], mesh),
             "nu": param_specs(opt["nu"], mesh), "step": P()}
    return tspec, fspec, ospec


def place(mesh: Mesh, specs, tree):
    """``device_put`` ``tree`` onto ``mesh`` by the matching spec tree
    (e.g. ``(tspec, fspec, ospec)`` from :func:`make_train_step`): the
    jitted steps refuse arguments committed to a different sharding."""
    return jax.device_put(tree, spec_to_sharding(specs, mesh))


def make_train_fn(lm: LM, opt_cfg: AdamWConfig):
    def train_step(trainable, frozen, opt_state, batch):
        def loss_fn(tr):
            params = merge_params(tr, frozen)
            loss, metrics = lm.loss(params, batch)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(trainable)
        with tracing.scope(tracing.OPTIMIZER):
            new_tr, new_opt, om = adamw_update(opt_cfg, grads, opt_state,
                                               trainable)
        return new_tr, new_opt, {"loss": loss, **metrics, **om}

    return train_step


def make_train_step(lm: LM, mesh: Mesh, opt_cfg: Optional[AdamWConfig] = None,
                    donate: bool = True):
    """Returns (jitted_step, (tspec, fspec, ospec, bspec))."""
    opt_cfg = opt_cfg or AdamWConfig()
    tspec, fspec, ospec = train_state_specs(lm, mesh)
    cell_batch = None  # batch specs are computed per-call shape

    fn = make_train_fn(lm, opt_cfg)

    def jit_for(batch_abstract):
        bspec = batch_spec_tree(batch_abstract, mesh)
        sh = lambda t: spec_to_sharding(t, mesh)
        return jax.jit(
            fn,
            in_shardings=(sh(tspec), sh(fspec), sh(ospec), sh(bspec)),
            out_shardings=(sh(tspec), sh(ospec), None),
            donate_argnums=(0, 2) if donate else (),
        ), bspec

    return jit_for, (tspec, fspec, ospec)


def make_prefill_step(lm: LM, mesh: Mesh, params_abstract=None):
    """``params_abstract`` overrides the default (adapter-bearing) param
    tree — pass the merged tree when serving a deployed model."""
    pspec = param_specs(params_abstract or abstract_params(lm), mesh)
    sh = lambda t: spec_to_sharding(t, mesh)

    def jit_for(batch_abstract):
        bspec = batch_spec_tree(batch_abstract, mesh)
        return jax.jit(lm.prefill,
                       in_shardings=(sh(pspec), sh(bspec))), bspec

    return jit_for, pspec


def make_generate_step(lm: LM, mesh: Mesh, gen_len: int, donate: bool = True,
                       params_abstract=None):
    """Whole-generation step: ``lax.scan`` over ``lm.decode_step``.

    One compiled program emits ``gen_len`` greedy tokens from the prefill
    logits — no per-token dispatch or host sync.  Serve and dryrun both
    build their decode path through this factory.  ``params_abstract``
    overrides the default (adapter-bearing) param tree — pass the merged
    tree when serving a deployed model.
    """
    pspec = param_specs(params_abstract or abstract_params(lm), mesh)
    sh = lambda t: spec_to_sharding(t, mesh)

    def generate(params, cache, logits):
        return lm.generate(params, cache, logits, gen_len)

    def jit_for(cache_abstract):
        cspec = cache_spec_tree(cache_abstract, mesh)
        return jax.jit(
            generate,
            in_shardings=(sh(pspec), sh(cspec), None),
            out_shardings=(None, sh(cspec)),
            donate_argnums=(1,) if donate else (),
        ), cspec

    return jit_for, pspec


def make_decode_step(lm: LM, mesh: Mesh, donate: bool = True):
    pspec = param_specs(abstract_params(lm), mesh)
    sh = lambda t: spec_to_sharding(t, mesh)

    def jit_for(cache_abstract):
        cspec = cache_spec_tree(cache_abstract, mesh)
        # tokens [B,1]: replicated (tiny); the cache batch dim carries DP
        return jax.jit(
            lm.decode_step,
            in_shardings=(sh(pspec), sh(cspec), None),
            out_shardings=(None, sh(cspec)),
            donate_argnums=(1,) if donate else (),
        ), cspec

    return jit_for, pspec


def make_sync_step(mesh: Mesh, tspec):
    """Periodic cross-pod adapter averaging with int8 compression
    (local-SGD style; DESIGN.md §6). Only meaningful on multi-pod meshes."""
    if "pod" not in mesh.shape:
        return None
    sh = lambda t: spec_to_sharding(t, mesh)

    def sync(trainable):
        def inner(tr):
            return compressed_mean(tr, "pod")
        return jax.shard_map(inner, mesh=mesh, in_specs=(tspec,),
                             out_specs=tspec, check_vma=False)(trainable)

    return jax.jit(sync, in_shardings=(sh(tspec),), out_shardings=sh(tspec))
