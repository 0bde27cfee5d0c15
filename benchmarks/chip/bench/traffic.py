"""Seeded traffic: request lengths, arrival times and training documents.

One generator serves every mix; a mix is a data file under ``traffic/``:

  {"kind": "closed", "clients": 16, "prompt": {...}, "output": {...}}
  {"kind": "poisson", "rate": 3.0, "prompt": {...}, "output": {...}}
  {"kind": "train", "batch": 4, "seq": 2048, "doc": {...}}

where a length is ``{"median": m, "sigma": s, "min": lo, "max": hi}``: a
lognormal of that median and log-space sigma, clipped to [lo, hi].

Every seed gets the same work.  Lengths are the lognormal's quantiles at
``pool`` evenly spaced probabilities and Poisson gaps the exponential's,
each block of ``pool`` requests holding every quantile once, so two seeds
differ only in the order of the same sizes and gaps and in the token ids.
A mix sets ``pool`` near the number of requests its window sees, and may
fix the order too (``"order": "fixed"``), so that every seed replays one
schedule of lengths and arrivals with its own token ids.  That keeps a run's amount of work, and so its timing,
from depending on which seed it drew.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import List, Optional, Sequence

import numpy as np

POOL = 256          # quantiles per distribution, unless a mix sets "pool"
FIRST_TOKEN = 4     # ids 0..3 are left out, as the program's own traces do
SEPARATOR = 1       # between packed training documents


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one seed (seeds may exceed
    64 bits of signedness: numpy takes any non-negative integer)."""
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


def lognormal_quantiles(spec: dict, n: int = POOL) -> np.ndarray:
    """``n`` lengths at probabilities (i + 1/2) / n of the clipped
    lognormal ``spec``, as ints, ascending."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * np.asarray(z))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_quantiles(mean: float, n: int = POOL) -> np.ndarray:
    p = (np.arange(n) + 0.5) / n
    return -mean * np.log1p(-p)


def _draw(table: np.ndarray, seed: int, stream: int, i: int):
    """Entry ``i`` of an endless sequence of per-block permutations of
    ``table``: each block of ``len(table)`` draws holds every entry once."""
    block, j = divmod(i, len(table))
    return table[rng(seed, stream, block).permutation(len(table))[j]].item()


def order_seed(mix: dict, seed: int) -> int:
    """The seed that orders a mix's lengths and gaps: the run's own, or 0
    where the mix replays one fixed schedule (``"order": "fixed"``) and
    only the token ids and weights follow the seed."""
    return 0 if mix.get("order") == "fixed" else seed


class Requests:
    """The seed's request sequence: request ``i`` has a prompt of random
    ids and an output length, both fixed by ``(seed, i)`` alone."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.seed, self.vocab = seed, vocab
        self.order = order_seed(mix, seed)
        n = mix.get("pool", POOL)
        self.prompt_lens = lognormal_quantiles(mix["prompt"], n)
        self.output_lens = lognormal_quantiles(mix["output"], n)

    def __call__(self, i: int):
        n = _draw(self.prompt_lens, self.order, 1, i)
        out = _draw(self.output_lens, self.order, 2, i)
        prompt = rng(self.seed, 3, i).integers(FIRST_TOKEN, self.vocab, n)
        return prompt.astype(np.int32), out


def stratified_poisson_arrivals(n: int, rate: float, seed: int,
                                pool: int = POOL) -> np.ndarray:
    """Arrival offsets (seconds from t=0) of an open-loop Poisson process
    at ``rate`` requests/second, as the program's ``serving/trace.py``
    draws them (exponential gaps, summed), except that the gaps are the
    exponential's quantiles in a seeded order: the same gaps for every
    seed, so every seed offers the same load over the same time."""
    gaps = exponential_quantiles(1.0 / rate, pool)
    return np.cumsum([_draw(gaps, seed, 4, i) for i in range(n)])


def replay_due(arrivals: Sequence[float], t0: float, now: float,
               start: int) -> int:
    """Index one past the last request due by ``now`` (offsets from
    ``t0``), scanning from ``start``: the open-loop generator submits
    ``[start, returned)`` at this point of the serve loop."""
    i = start
    while i < len(arrivals) and t0 + arrivals[i] <= now:
        i += 1
    return i


class Documents:
    """Packed training rows: documents of lognormal lengths, each of
    random ids, joined by a separator and cut into rows of ``seq + 1``;
    a row's first ``seq`` ids are the tokens and its last ``seq`` the
    labels.  Rows never repeat: the stream runs on through the seed's
    documents."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, vocab, seed
        self.lens = lognormal_quantiles(mix["doc"])
        self._buf: List[np.ndarray] = []
        self._have = 0
        self._doc = 0

    def _fill(self, need: int):
        while self._have < need:
            n = _draw(self.lens, self.seed, 5, self._doc)
            ids = rng(self.seed, 6, self._doc).integers(
                FIRST_TOKEN, self.vocab, n).astype(np.int32)
            self._buf.append(np.append(ids, np.int32(SEPARATOR)))
            self._have += n + 1
            self._doc += 1

    def next_batch(self):
        b, s = self.mix["batch"], self.mix["seq"]
        need = b * (s + 1)
        self._fill(need)
        flat = np.concatenate(self._buf)
        rows, rest = flat[:need].reshape(b, s + 1), flat[need:]
        self._buf, self._have = [rest], len(rest)
        return rows[:, :-1].copy(), rows[:, 1:].copy()


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q``-quantile (0..1) of ``values`` by the nearest rank on the
    sorted values, counting every value: ``inf`` stands for a request
    that never got its answer, and sorts above every finite time."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    k = max(0, math.ceil(q * len(xs)) - 1)
    return float(xs[k])


def lateness(due: Sequence[float], sent: Sequence[float]) -> Optional[dict]:
    """How late the generator submitted requests: median and max of
    submit time minus due time, in seconds."""
    if not due:
        return None
    late = np.asarray(sent) - np.asarray(due)
    return {"median_s": float(np.median(late)), "max_s": float(late.max()),
            "n": len(late)}
