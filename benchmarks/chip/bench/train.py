"""Fine-tune cells: QA-LoRA steps on the frozen INT4 base.

Set-up builds one object, the jitted step of ``launch.steps.make_train_step``
with its placed state, and drives it through its first ``ref_steps`` steps
on the seed's packed documents, reading what the check compares: each
step's loss, each adapter's first gradient as AdamW received it (its first
moment after one step, over 1 - beta1), and how far each adapter has moved.
The window then runs the same object on for whole steps, each on new rows.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import manifest as M
from . import spans as S
from . import traffic as T
from . import weights as W
from . import work


def _adapters(tree) -> dict:
    """{projection: {"a", "b"}} of a tree of QA-LoRA linears."""
    from repro.core.schemes import adapter_params, map_linears

    out = {}

    def grab(path, lp):
        if lp.scheme == "qalora":
            ad = adapter_params(lp)
            out[path.rsplit("/", 1)[-1]] = {"a": ad.a, "b": ad.b}
        return lp

    map_linears(tree, grab)
    return out


def slice_norms(leaves: dict) -> dict:
    """Norm of every layer's slice of every adapter leaf: {"wq.a": [L]}."""
    return {f"{n}.{k}": np.asarray(jnp.sqrt(jnp.sum(
        jnp.square(v.astype(jnp.float32)), axis=tuple(range(1, v.ndim)))))
        for n, ab in leaves.items() for k, v in ab.items()}


def _diff(a: dict, b: dict) -> dict:
    return {n: {k: a[n][k].astype(jnp.float32) - b[n][k].astype(jnp.float32)
                for k in a[n]} for n in a}


class Trainer:
    def __init__(self, c: dict, seed: int):
        from repro.launch import steps as St
        from repro.launch.mesh import make_local_mesh
        from repro.models.lm import LM
        from repro.optim import AdamWConfig, adamw_init, split_params

        self.seed = seed
        self.cfg, self.mix, self.cell = c["config"], c["traffic"], c["cell"]
        opt = self.cell["optimizer"]
        self.opt_cfg = AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                                   eps=opt["eps"],
                                   max_grad_norm=opt["max_grad_norm"],
                                   schedule="constant")
        self.lm = LM(W.arch_config(self.cfg))
        self.mesh = make_local_mesh()
        self.docs = T.Documents(self.mix, self.cfg["vocab"], seed)
        with jax.set_mesh(self.mesh):
            trainable, frozen = split_params(
                W.make_program(self.lm, self.cfg, seed))
            opt_state = adamw_init(trainable)
            jit_for, specs = St.make_train_step(self.lm, self.mesh,
                                                self.opt_cfg)
            self.state = St.place(self.mesh, specs,
                                  (trainable, frozen, opt_state))
            b, s = self.mix["batch"], self.mix["seq"]
            shape = jax.ShapeDtypeStruct((b, s), jnp.int32)
            self.step_fn, _ = jit_for({"tokens": shape, "labels": shape})
        self.tokens_per_step = self.mix["batch"] * self.mix["seq"]

    def step(self):
        toks, labs = self.docs.next_batch()
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}
        trainable, frozen, opt = self.state
        with jax.set_mesh(self.mesh):
            trainable, opt, m = self.step_fn(trainable, frozen, opt, batch)
        self.state = (trainable, frozen, opt)
        return float(m["loss"])

    def first_steps(self) -> dict:
        """The steps the check compares, run through the window's own
        call and feed before the window opens."""
        n = self.cell["ref_steps"]
        p0 = jax.tree.map(jnp.copy, _adapters(self.state[0]))
        losses, grads = [], None
        for i in range(n):
            losses.append(self.step())
            if i == 0:
                mu = _adapters(self.state[2]["mu"])
                grads = slice_norms({k: {j: v / (1 - self.opt_cfg.b1)
                                         for j, v in ab.items()}
                                     for k, ab in mu.items()})
        change = slice_norms(_diff(_adapters(self.state[0]), p0))
        return {"losses": losses, "grads": grads, "change": change}

    def free(self):
        self.state = self.step_fn = None
        gc.collect()


def train(trainer: Trainer, seconds: float, tracer) -> dict:
    tracer.start()
    losses, ends = [], []
    with S.span(S.WINDOW):
        t_open = time.perf_counter()
        while not ends or ends[-1] - t_open < seconds:
            with S.span(S.TRAIN):
                losses.append(trainer.step())
            ends.append(time.perf_counter())
    tracer.stop()
    return {"t_open": t_open, "t_close": ends[-1], "losses": losses}


def end_to_end(run: dict, trainer: Trainer) -> dict:
    window = run["t_close"] - run["t_open"]
    n = len(run["losses"])
    return {"steps": n, "window_s": window,
            "train_tok_s": n * trainer.tokens_per_step / window}


def layer_context(run: dict, trainer: Trainer, peak: dict) -> dict:
    f, b = M.family(trainer.cfg).train_step(
        trainer.cfg, trainer.mix["batch"], trainer.mix["seq"])
    window = run["t_close"] - run["t_open"]
    return {"window_s": window,
            "least_s": len(run["losses"]) * work.least_seconds(f, b, peak)}


# ---------------------------------------------------------------------------
# correctness: the first steps against the plain reference
# ---------------------------------------------------------------------------


def reference_steps(ref, cfg: dict, mix: dict, cell: dict, seed: int,
                    lower: bool = False) -> dict:
    """The plain reference's losses, first gradients and moves over the
    same rows: float32 at full precision (``lower``: the float8 control),
    AdamW with global-norm clipping as the configuration states, the
    adapters stored in their bf16 after every update."""
    opt = cell["optimizer"]
    raw = W.make_raw(cfg, seed)
    ad = M.family(cfg).adapters(raw)
    p0 = ad
    mu = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), ad)
    nu = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), ad)
    docs = T.Documents(mix, cfg["vocab"], seed)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda a, raw, t, l: ref.loss_sum(a, raw, cfg, t, l, lower=lower),
        has_aux=True))
    losses, first = [], None
    for step in range(1, cell["ref_steps"] + 1):
        toks, labs = docs.next_batch()
        tot, cnt, g = 0.0, 0.0, None
        ad32 = jax.tree.map(lambda x: x.astype(jnp.float32), ad)
        for row in range(toks.shape[0]):
            (s, c), gr = grad_fn(ad32, raw, jnp.asarray(toks[row]),
                                 jnp.asarray(labs[row]))
            tot, cnt = tot + s, cnt + c
            g = gr if g is None else jax.tree.map(jnp.add, g, gr)
        g = jax.tree.map(lambda x: x / cnt, g)
        losses.append(float(tot / cnt))
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(norm, 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        if step == 1:
            first = slice_norms(g)
        b1, b2 = opt["b1"], opt["b2"]
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)

        def upd(p, m, v):
            d = (m / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step))
                                          + opt["eps"])
            return (p.astype(jnp.float32) - opt["lr"] * d).astype(p.dtype)
        ad = jax.tree.map(upd, ad, mu, nu)
    return {"losses": losses, "grads": first,
            "change": slice_norms(_diff(ad, p0))}


def leaf_gap(prog: dict, refs: dict, keep: dict) -> float:
    """Worst relative gap of two sets of per-slice norms: |program -
    reference| over the larger of the reference's norm of that slice and
    the median slice's, over the slices ``keep`` marks."""
    r = np.concatenate([refs[k] for k in sorted(refs)])
    p = np.concatenate([prog[k] for k in sorted(refs)])
    m = np.concatenate([keep[k] for k in sorted(refs)])
    med = np.median(r[m])
    return float(np.max(np.abs(p - r)[m] / np.maximum(r[m], med)))


def compare(prog: dict, refs: dict, limits: dict) -> dict:
    """The three numbers compared.  Slices whose reference gradient is
    under a thousandth of the median slice's move under AdamW by
    round-off alone and are left out of the move."""
    gr = np.concatenate([refs["grads"][k] for k in sorted(refs["grads"])])
    floor = 1e-3 * np.median(gr)
    keep = {k: v >= floor for k, v in refs["grads"].items()}
    every = {k: np.ones_like(v, bool) for k, v in refs["grads"].items()}
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   refs["losses"]))
    return {
        "loss_gap": {"value": float(loss), "limit": limits["loss_gap"]},
        "grad_gap": {"value": leaf_gap(prog["grads"], refs["grads"], every),
                     "limit": limits["grad_gap"]},
        "move_gap": {"value": leaf_gap(prog["change"], refs["change"], keep),
                     "limit": limits["move_gap"]},
    }


def passed(checks: dict) -> bool:
    return all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in checks.values())
