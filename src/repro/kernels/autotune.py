"""Measure-and-cache block-shape autotuner for the quantized kernels.

`ops.pick_blocks` used to be a pure heuristic (largest MXU-aligned
divisor under a VMEM cap).  That is still the no-measure fallback, but
block shapes are now resolved in three steps:

  1. cache hit  — `experiments/autotune_cache.json`, keyed on
     ``(m, k, n, bits, group_size, rank, backend)``;
  2. measure    — when enabled, time every legal candidate on the live
     backend (interpret on CPU, Mosaic on TPU) and persist the winner
     (raises, recording nothing, when every candidate fails);
  3. heuristic  — the original static rule.

Measurement is opt-in because it runs real kernels: set
``REPRO_AUTOTUNE=1`` (or pass ``measure=True`` / call :func:`warm`) to
populate the cache.  Entries are plain JSON ``key -> [bm, bn, bk]`` so
the cache is human-diffable and deleting the file resets everything.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Dict, List, Optional, Tuple

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
MEASURE_ENV = "REPRO_AUTOTUNE"
DEFAULT_CACHE_PATH = os.path.join("experiments", "autotune_cache.json")

_cache: Optional[Dict[str, List[int]]] = None
_cache_path_loaded: Optional[str] = None


def cache_path() -> str:
    return os.environ.get(CACHE_ENV, DEFAULT_CACHE_PATH)


def measure_enabled() -> bool:
    return os.environ.get(MEASURE_ENV, "") not in ("", "0", "false")


def cache_key(m: int, k: int, n: int, bits: int, group_size: int,
              rank: int, backend: str) -> str:
    return f"m{m}_k{k}_n{n}_b{bits}_g{group_size}_r{rank}_{backend}"


def _load() -> Dict[str, List[int]]:
    global _cache, _cache_path_loaded
    path = cache_path()
    if _cache is None or _cache_path_loaded != path:
        _cache_path_loaded = path
        try:
            with open(path) as f:
                _cache = {k: list(v) for k, v in json.load(f).items()}
        except (OSError, ValueError):
            _cache = {}
    return _cache


def _save() -> None:
    path = cache_path()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(_cache or {}, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def clear_cache(persist: bool = True) -> None:
    """Drop all entries (and the on-disk file unless ``persist=False``)."""
    global _cache
    _cache = {}
    if persist:
        try:
            os.remove(cache_path())
        except OSError:
            pass


def lookup(key: str) -> Optional[Tuple[int, int, int]]:
    v = _load().get(key)
    return tuple(v) if v else None


def record(key: str, blocks: Tuple[int, int, int]) -> None:
    _load()[key] = list(blocks)
    _save()


# ---------------------------------------------------------------------------
# candidate enumeration
# ---------------------------------------------------------------------------


# A TPU block's last two dims must be multiples of (8, 128) or span the
# whole array dim.  Blocks over K cut five arrays: x [m, K] and the GEMV's
# code planes of x [cpb, m, K/cpb] (lanes), packed codes [K/cpb, N],
# scale/zero [K/g, N] and the adapter A [K/g, r] (sublanes).  So a K block
# that does not span K is a multiple of 128 * cpb, of 8 * g and of 8 * cpb;
# an N block that does not span N, of 128.


def _legal_sizes(dim: int, mult: int, cap: int) -> List[int]:
    """Block sizes along ``dim``, largest first: the multiples of ``mult``
    that divide ``dim``, up to ``max(cap, mult)`` — or the whole ``dim``
    when none does (a block spanning the array is always legal)."""
    out = [d for d in range(mult, min(max(cap, mult), dim) + 1, mult)
           if dim % d == 0]
    return sorted(out, reverse=True) or [dim]


def block_k_sizes(k: int, bits: int, group_size: int, cap: int) -> List[int]:
    from repro.core.quant import codes_per_byte

    cpb = codes_per_byte(bits)
    mult = math.lcm(128 * cpb, 8 * group_size, 8 * cpb)
    return _legal_sizes(k, mult, cap)


def block_n_sizes(n: int, cap: int) -> List[int]:
    return _legal_sizes(n, 128, cap)


def candidates(m: int, k: int, n: int, bits: int, group_size: int,
               max_bk: int = 4, max_bn: int = 4) -> List[Tuple[int, int, int]]:
    """Legal (bm, bn, bk) triples under the TPU tiling rule above,
    VMEM-bounded.  Bounded to the ``max_bk`` largest K blocks x
    ``max_bn`` largest N blocks so measurement samples across BOTH axes
    rather than exhausting bn under a single bk."""
    bks = block_k_sizes(k, bits, group_size, 2048)
    bns = block_n_sizes(n, 512)
    bm = min(128, m)
    out = []
    for bk in bks[:max_bk]:
        for bn in bns[:max_bn]:
            # x + unpacked w tile + f32 acc, 4B elements, keep under ~4MB
            vmem = 4 * (bm * bk + bk * bn + bm * bn)
            if vmem > 4 * 2**20:
                continue
            out.append((bm, bn, bk))
    return out or [(bm, bns[-1], bks[-1])]


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def _time_call(fn, reps: int = 3) -> float:
    import jax

    jax.block_until_ready(fn())  # compile / warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


def measure_qmatmul(m: int, k: int, n: int, bits: int, group_size: int,
                    rank: int = 0, s: float = 1.0,
                    interpret: Optional[bool] = None,
                    reps: int = 3) -> Tuple[int, int, int]:
    """Time every candidate for the (fused when rank>0) kernel; return and
    persist the fastest block triple."""
    import jax
    import jax.numpy as jnp

    from repro.core.quant import quantize
    from .qmatmul import qmatmul_pallas
    from .qalora_fused import qalora_matmul_pallas
    from .qmatvec import GEMV_MAX_M, qmatvec_pallas, qalora_matvec_pallas

    backend = jax.default_backend()
    if interpret is None:
        interpret = backend != "tpu"
    key0 = jax.random.PRNGKey(0)
    x = jax.random.normal(key0, (m, k), jnp.float32)
    qt = quantize(jax.random.normal(key0, (k, n)), bits, group_size)
    a = b = None
    if rank:
        a = jax.random.normal(key0, (k // group_size, rank)) * 0.1
        b = jax.random.normal(key0, (rank, n)) * 0.1

    best, best_t, first_err = None, float("inf"), None
    for bm, bn, bk in candidates(m, k, n, bits, group_size):
        try:
            if m <= GEMV_MAX_M:
                if rank:
                    fn = lambda: qalora_matvec_pallas(
                        x, qt.qweight, qt.scale, qt.zero, a, b, s=s,
                        bits=bits, group_size=group_size, block_n=bn,
                        block_k=bk, interpret=interpret)
                else:
                    fn = lambda: qmatvec_pallas(
                        x, qt.qweight, qt.scale, qt.zero, bits=bits,
                        group_size=group_size, block_n=bn, block_k=bk,
                        interpret=interpret)
            elif rank:
                fn = lambda: qalora_matmul_pallas(
                    x, qt.qweight, qt.scale, qt.zero, a, b, s=s, bits=bits,
                    group_size=group_size, block_m=bm, block_n=bn,
                    block_k=bk, interpret=interpret)
            else:
                fn = lambda: qmatmul_pallas(
                    x, qt.qweight, qt.scale, qt.zero, bits=bits,
                    group_size=group_size, block_m=bm, block_n=bn,
                    block_k=bk, interpret=interpret)
            t = _time_call(fn, reps)
        except Exception as e:  # refused by this backend: try the next one
            first_err = first_err or e
            continue
        if t < best_t:
            best, best_t = (bm, bn, bk), t
    if best is None:
        raise RuntimeError(
            f"autotune: every block candidate failed for m={m} k={k} n={n} "
            f"bits={bits} g={group_size} rank={rank} on {backend}; nothing "
            f"recorded") from first_err
    record(cache_key(m, k, n, bits, group_size, rank, backend), best)
    return best


def warm(shapes, bits: int = 4, group_size: int = 32, rank: int = 0) -> None:
    """Pre-populate the cache for an iterable of (m, k, n) shapes."""
    for m, k, n in shapes:
        measure_qmatmul(m, k, n, bits, group_size, rank)
