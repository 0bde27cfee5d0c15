"""Faults planted under the timed path, for the tests and the study that
show the check catches them.  The benchmark's own runs plant none."""

import jax
import numpy as np


def altered_commits(sched_cls, vocab: int) -> dict:
    """``Scheduler.commit`` and ``commit_burst`` with tokens replaced
    where a dispatch's tokens are committed: every slot's token of a
    ragged step, and one token of every burst."""
    commit, commit_burst = sched_cls.commit, sched_cls.commit_burst

    def shift(t):
        return (t + vocab // 2) % vocab

    def altered(self, next_tokens):
        return commit(self, shift(np.asarray(next_tokens)))

    def altered_burst(self, emitted, tok, remaining):
        emitted = np.array(emitted)
        hit = np.argwhere(emitted >= 0)
        if len(hit):
            i, j = hit[len(hit) // 2]
            emitted[i, j] = shift(emitted[i, j])
        return commit_burst(self, emitted, tok, remaining)
    return {"commit": altered, "commit_burst": altered_burst}


def broken_train_fn(make_train_fn, kind: str):
    """``launch.steps.make_train_fn`` whose step returns its state
    unchanged (``frozen``) or leaves out half of the batch and takes the
    mean over the rest (``half_batch``)."""

    def make(lm, opt_cfg):
        step = make_train_fn(lm, opt_cfg)

        def broken(trainable, frozen, opt, batch):
            if kind == "half_batch":
                half = batch["tokens"].shape[0] // 2
                batch = jax.tree.map(lambda x: x[:half], batch)
            new_tr, new_opt, m = step(trainable, frozen, opt, batch)
            if kind == "frozen":
                return trainable, opt, m
            return new_tr, new_opt, m
        return broken
    return make
