"""Continuous-batching serving engine on top of the scan decode path.

The engine drives :meth:`repro.models.lm.LM.step_ragged` — one compiled
ragged step that lets every cache slot advance by its own number of
tokens — with the host-side :class:`~repro.serving.scheduler.Scheduler`
deciding what each slot consumes:

  * admission: queued requests enter free slots mid-flight; the slot's
    length is reset to 0 and its stale KV is never read (all masks are
    bounded by the slot's own length);
  * chunked prefill: prompts stream in ``prefill_chunk``-token chunks
    while decode slots ride along in the same batch (in-flight batching);
  * per-request termination: slots stop at EOS or ``max_new_tokens`` and
    are evicted + refilled immediately;
  * decode bursts: when every active slot is decoding, ``decode_burst``
    steps run as ONE fused ``lax.scan`` program with per-slot stop masks
    (finished slots idle on-device until the burst returns), amortizing
    the per-step dispatch that made the legacy loop slow (PR 1).

Per-slot decode state is the family-agnostic ``SlotState`` pytree
(``repro.models.slot_state``): slotted KV / compressed-KV for the
attention families, running Mamba2/RWKV6 recurrences for the recurrent
families (eviction reinitializes them via ``SlotState.reset``), and a
frozen per-slot cross cache for encdec (encoded once at admission).
For deterministic-routing families (gqa, mla_moe's MLA layers,
mamba_hybrid, rwkv, encdec), token streams are identical for any
``prefill_chunk`` / ``decode_burst`` setting and identical to running
each request alone through the static ``generate_scan`` path
(tests/test_serving_engine.py, tests/test_serving_mla.py,
tests/test_serving_recurrent.py, tests/test_serving_encdec.py).  For MoE
layers (gqa_moe, and deepseek-v3's routed layers) the engine runs, but
finite expert capacity makes routing depend on batch composition —
co-resident slots (and idle rows) compete for capacity, so per-request
streams are NOT reproducible across batch mixes.  This is inherent to
capacity-routed MoE under any batched serving (the static path has the
same scan-vs-loop caveat, PR 1); treat MoE serving as approximate.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import weakref
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import schemes
from ..runtime import compile_guard, tracing
from .paging import PageTable, pages_for
from .scheduler import Request, Scheduler
from .speculative import accept_drafts, rollback_counts


def _ragged_step(lm, params, aux, cache, tokens, n_new):
    # argmax in-graph: the host only needs next tokens, not [B, vocab]
    # logits (at real vocab sizes that transfer dominates the step).
    # `ok` is the in-graph health bit: non-finite logits (NaN/inf from
    # corrupted state) trip it BEFORE any token is committed host-side —
    # the fault-detection contract ServingFrontend recovery relies on.
    # Idle/fully-masked slots produce garbage-but-FINITE logits (pinned
    # by the masked-row finiteness tests), so the all-reduce over the
    # whole batch does not false-positive on idle rows.
    logits, cache = lm.step_ragged(params, cache, tokens, n_new, aux=aux)
    ok = jnp.isfinite(logits).all()
    return jnp.argmax(logits, -1).astype(jnp.int32), ok, cache


def _burst_steps(lm, params, aux, cache, tok, remaining, eos, *,
                 k_steps: int):
    """lax.scan of masked single-token ragged steps.  A slot whose
    remaining count hits 0 (max-len or EOS) stops consuming (n_new=0) so
    its cache and length freeze until the host evicts it."""

    def body(carry, _):
        cache, tok, remaining = carry
        active = remaining > 0
        logits, cache = lm.step_ragged(params, cache, tok[:, None],
                                       active.astype(jnp.int32), aux=aux)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, tok)
        emit = jnp.where(active, nxt, -1)
        stop = active & ((remaining <= 1) | (nxt == eos))
        remaining = jnp.where(stop, 0, jnp.where(active, remaining - 1, 0))
        return (cache, nxt, remaining), (emit, jnp.isfinite(logits).all())

    (cache, tok, remaining), (emitted, oks) = jax.lax.scan(
        body, (cache, tok, remaining), None, length=k_steps)
    return cache, tok, remaining, emitted, oks.all()


def _draft_steps(lm, params, aux, cache, tok, active, *, k_steps):
    """Drafter-side lax.scan of ``k_steps`` masked single-token ragged
    steps (speculative decoding).  Step i inserts its input token and
    argmaxes the next draft, so the scan proposes d_1..d_{k_steps-1} AND
    leaves the drafter cache holding exactly the same rows the verify
    step writes on the target (t0, d_1, ..) — the final step inserts the
    last draft with its output discarded, which is what makes the
    post-accept rollback identical for both caches.  No EOS/remaining
    logic here: drafts are proposals, acceptance handles termination.
    Drafter health is deliberately unchecked — a NaN-poisoned drafter
    produces garbage proposals that verification simply rejects."""

    def body(carry, _):
        cache, tok = carry
        logits, cache = lm.step_ragged(params, cache, tok[:, None],
                                       active.astype(jnp.int32), aux=aux)
        nxt = jnp.where(active, jnp.argmax(logits, -1).astype(jnp.int32),
                        tok)
        return (cache, nxt), nxt

    (cache, _), drafts = jax.lax.scan(body, (cache, tok), None,
                                      length=k_steps)
    return cache, drafts


def _verify_step(lm, params, aux, cache, tokens, n_new):
    """Verify all k+1 speculative positions in ONE ragged step: returns
    per-position argmax [B, C] (column i = the target's next token after
    tokens[:, :i+1]), the health bit over the consumed rows only (rows
    past n_new are garbage by contract and must not false-trip it), and
    the cache advanced by the full n_new (the host rolls back the
    rejected tail by shrinking ``len``)."""
    logits, _, cache = lm.verify_ragged(params, cache, tokens, n_new,
                                        aux=aux)
    valid = jnp.arange(tokens.shape[1])[None, :] < n_new[:, None]
    ok = jnp.isfinite(jnp.where(valid[..., None], logits, 0.0)).all()
    return jnp.argmax(logits, -1).astype(jnp.int32), ok, cache


def _spec_step_mtp(lm, params, aux, cache, tokens, n_new):
    """MTP-drafted speculation, fused: one program both VERIFIES this
    dispatch's draft and DRAFTS the next one from the same hidden
    states.  ``tokens`` [B, 2] = [last committed token, held MTP draft];
    ``n_new`` is 2 when the slot holds a draft, 1 on bootstrap (fresh or
    invalidated slot — same compiled program either way, the ragged
    contract absorbs it), 0 when idle.  Returns (verify argmax [B, 2],
    next-draft argmax [B, 2] — the host picks column m-1, the one
    conditioned on exactly the committed stream —, ok, cache)."""
    logits, h, cache = lm.verify_ragged(params, cache, tokens, n_new,
                                        aux=aux)
    v = jnp.argmax(logits, -1).astype(jnp.int32)
    draft = jnp.argmax(lm.mtp_draft_logits(params, h, v), -1)
    valid = jnp.arange(tokens.shape[1])[None, :] < n_new[:, None]
    ok = jnp.isfinite(jnp.where(valid[..., None], logits, 0.0)).all()
    return v, draft.astype(jnp.int32), ok, cache


def _slot_reset(slot_state, cache, mask):
    # eviction is family-agnostic: SlotState zeroes the evicted slots'
    # lengths AND their snapshot state (recurrences, cross caches);
    # length-indexed KV rows stay in place, masked by the zeroed length
    return slot_state.reset(cache, mask)


def _encode_cross(lm, params, src, src_len):
    return lm.encode_cross(params, src, src_len=src_len)


# one shared compile cache across engine instances: `lm` (and its
# SlotState) is a hashable frozen dataclass, so jit memoizes per
# (lm, shapes) — building a second engine for the same model does not
# re-trace
_JIT_STEP = jax.jit(_ragged_step, static_argnums=0)
_JIT_BURST = jax.jit(_burst_steps, static_argnums=0,
                     static_argnames=("k_steps",))
_JIT_RESET = jax.jit(_slot_reset, static_argnums=0)
_JIT_ENCODE = jax.jit(_encode_cross, static_argnums=0)
_JIT_DRAFT = jax.jit(_draft_steps, static_argnums=0,
                     static_argnames=("k_steps",))
_JIT_VERIFY = jax.jit(_verify_step, static_argnums=0)
_JIT_SPEC_MTP = jax.jit(_spec_step_mtp, static_argnums=0)
_JITS = (_JIT_STEP, _JIT_BURST, _JIT_RESET, _JIT_ENCODE, _JIT_DRAFT,
         _JIT_VERIFY, _JIT_SPEC_MTP)


def _cached_programs() -> int:
    """Executables the engine's jits hold (CompileGuard's probe); grows
    by one for every compile or persistent-cache load."""
    return sum(f._cache_size() for f in _JITS)


def make_self_drafter(params, policy: str, base=None, key=None):
    """Build a reduced-bits SELF-SPECULATION drafter from the same
    merged weights: re-store every linear of ``params`` under the
    PolicyTree ``policy`` (e.g. ``"*=intq8"`` — bare re-quantization, no
    adapters; see ``repro.core.schemes.PolicyTree.parse``).  Zero extra
    training: the drafter IS the served model at lower precision, so its
    argmax agrees with the target's wherever quantization noise doesn't
    flip the top logit.  Returns a params tree for
    ``ContinuousEngine(..., speculate=k, drafter=<tree>)`` (the engine
    also accepts the policy string directly and calls this)."""
    return schemes.convert_tree(params, schemes.PolicyTree.parse(
        policy, base), key)


class EngineCorrupted(RuntimeError):
    """The in-graph health bit tripped: a step produced non-finite logits
    (corrupted decode state — e.g. an injected NaN fault, or a real
    numerical blow-up).  Raised BEFORE the step's tokens commit, so the
    scheduler's emitted streams stay trustworthy; the engine's device
    state must be considered poisoned (reset / rebuild to continue —
    ``ServingFrontend`` does this and replays in-flight requests)."""


@dataclasses.dataclass
class EngineStats:
    """Aggregates :meth:`ContinuousEngine.step_once` iterations (a
    :meth:`ContinuousEngine.run` or any external per-step driver —
    wall clock accrues per step, not per run).

    ``slot_steps`` / ``busy_slot_steps`` are counted in MODEL-STEP units
    on every path: each dispatch that runs C model rows per slot adds
    ``n_slots * C`` to ``slot_steps`` and the rows actually consumed
    (``n_new.sum()``; one per active slot per fused burst step) to
    ``busy_slot_steps`` — so ``occupancy`` is the fraction of computed
    model rows that did useful work, comparable across the ragged and
    burst paths (and against static batching's padded rows).

    Speculative decoding: ``model_steps``/``slot_steps`` count
    TARGET-model rows only — the drafter's compute is a throughput bet,
    not target work, so it is excluded from occupancy accounting (its
    cost shows up honestly in ``seconds``, i.e. in ``tok_per_s``) while
    ``dispatches`` counts every program launch including drafter ones.
    ``proposed_tokens`` / ``accepted_tokens`` track speculation quality:
    drafts offered to a verify step, and of those the longest-prefix
    matches that actually committed (the per-dispatch bonus/correction
    token is a plain greedy token, counted in ``tokens_out`` but never
    in ``accepted_tokens``).

    ``seconds`` = ``host_s`` + ``sync_s``: ``sync_s`` is the time the host
    waits for the device's results (the ``repro.engine.sync`` span),
    ``host_s`` the rest of each iteration (admission, plan, the dispatch
    call, commit, and the frontend's intake and harvest).
    ``forward_passes`` counts target-model passes (a burst of k is k, a
    ragged or verify dispatch 1); ``compiles`` the executables the
    engine's module-level jits added during its iterations (a compile or
    a persistent-cache load; the count is process-wide, so another
    engine compiling on another thread at the same time adds to it)."""

    model_steps: int = 0      # model rows computed per slot (C per dispatch)
    dispatches: int = 0       # host->device program launches
    tokens_out: int = 0       # useful generated tokens
    slot_steps: int = 0       # slots x model rows computed
    busy_slot_steps: int = 0  # of those, rows a slot actually consumed
    seconds: float = 0.0
    proposed_tokens: int = 0  # draft tokens offered to a verify step
    accepted_tokens: int = 0  # of those, committed (prefix-matched)
    host_s: float = 0.0       # host seconds outside the sync span
    sync_s: float = 0.0       # seconds waiting for device results
    forward_passes: int = 0   # target-model passes
    compiles: int = 0         # new executables of the engine's jits

    @property
    def occupancy(self) -> float:
        return self.busy_slot_steps / max(self.slot_steps, 1)

    @property
    def tok_per_s(self) -> float:
        return self.tokens_out / max(self.seconds, 1e-9)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens that committed (0.0 when
        nothing was ever proposed — non-speculative engines)."""
        return self.accepted_tokens / max(self.proposed_tokens, 1)


class ContinuousEngine:
    """Serve an LM with in-flight batching over unified per-slot state.

    ``n_slots`` concurrent requests share one decode-state pytree of
    per-slot capacity ``max_len`` (each request needs prompt + max_new
    <= max_len).  Family support is derived from the model itself
    (``lm.supports_ragged()`` — the same guard ``LM.step_ragged`` owns,
    so the engine can never silently desync from the model): gqa /
    gqa_moe (slotted per-head KV), mla_moe (DeepSeek-style compressed
    latent ``c`` + rope key ``kr``, attention absorbed into the rank
    space), mamba_hybrid / rwkv (per-slot running recurrences — eviction
    reinitializes them via ``SlotState.reset``; the hybrid family's
    shared-attention blocks ride the slotted-KV chunk path), and encdec
    (slotted self-KV plus a frozen per-slot cross cache of capacity
    ``max_src``, encoded once at admission from the request's ``src``
    frames; a src-less request serves with a zero cross context).

    For mla_moe the step-invariant absorbed weights (the dequantized
    effective W_uk/W_uv of every layer's ``kv_up``) are computed ONCE at
    construction and threaded through every jitted step as ``aux`` — the
    dequant of a rank-512 up-projection per step per layer is pure waste
    on the decode hot path.

    ``decode_burst`` is clamped DOWN to a power of two at construction:
    burst lengths follow the shortest active request rounded down to a
    power of two, so a non-power-of-two cap (e.g. 6) would compile an
    extra scan program alongside the k in {1, 2, 4} ladder it already
    needs — the clamp keeps the compile-bound invariant of
    O(log decode_burst) programs.

    ``page_size > 0`` switches the CACHE leaves to the paged pool layout
    (``repro.serving.paging``): ``n_pages`` pages (page 0 reserved null;
    default sizes the pool to the contiguous capacity, n_slots x
    ceil(max_len / page_size) + 1 — shrink it to oversubscribe) are
    allocated per request at admission and shared across slots, with
    hash-based prefix reuse skipping the prefill of full prompt pages an
    earlier request already wrote.  The page map rides inside the cache
    pytree as plain int32 values, so admission/eviction remaps never
    retrace the compiled steps; token streams are identical to the
    contiguous layout (pinned by tests/test_serving_paged.py).
    Recurrent STATE (and the encdec cross cache) stays per-slot; rwkv
    has no CACHE leaves to page and fails loudly at construction.
    """

    def __init__(self, lm, params, *, n_slots: int, max_len: int,
                 prefill_chunk: int = 8, decode_burst: int = 8,
                 cache_dtype=jnp.float32, max_src: int = 0,
                 step_hook=None, adapters=None, page_size: int = 0,
                 n_pages: Optional[int] = None, speculate: int = 0,
                 drafter=None):
        if not lm.supports_ragged():
            raise NotImplementedError(
                f"continuous engine: family {lm.cfg.family!r} has no "
                f"LM.step_ragged support (lm.supports_ragged() is False); "
                f"use --engine static")
        self.lm, self.params = lm, params
        self.n_slots, self.max_len = n_slots, max_len
        # multi-tenant serving: an AdapterStore supplies the params tree
        # (shared INT-N base + per-slot adapter indices riding inside the
        # pytree); `params` is then only the aux/encode base.  Remapping
        # slots to adapters swaps array values under an unchanged pytree
        # structure, so the compiled steps never retrace on a mix change.
        self.adapters = adapters
        self._adapter_key = None
        if adapters is not None:
            if lm.cfg.family == "encdec":
                raise NotImplementedError(
                    "adapter serving: the encdec encoder runs outside the "
                    "slotted step (batch 1 per admission), so per-slot "
                    "adapter indices do not apply; serve encdec merged")
            if lm.absorbed_weights(params) is not None:
                raise NotImplementedError(
                    f"adapter serving: family {lm.cfg.family!r} hoists "
                    f"absorbed weights out of the step from a FIXED params "
                    f"tree, which would ignore per-slot adapters on those "
                    f"projections; serve this family merged")
        self.prefill_chunk = prefill_chunk
        db = max(1, decode_burst)
        self.decode_burst = 1 << (db.bit_length() - 1)
        self.cache_dtype = cache_dtype
        # ---- speculative decoding (draft-and-verify) ----
        self.speculate = int(speculate)
        self._mtp_draft = False
        self.draft_params = self.draft_aux = None
        if self.speculate < 0:
            raise ValueError(f"speculate must be >= 0; got {speculate}")
        if self.speculate > 0:
            if self.decode_burst > 1:
                raise ValueError(
                    f"speculate={self.speculate} and decode_burst="
                    f"{decode_burst} are both multi-token decode paths "
                    f"and do not compose: a fused burst commits every "
                    f"step unconditionally while speculation commits "
                    f"accepted prefixes with rollback.  Pass "
                    f"decode_burst=1 when speculating (the verify step "
                    f"IS the multi-token dispatch).")
            if not lm.slot_state().supports_rollback():
                raise NotImplementedError(
                    f"speculative decoding needs reject-rollback by "
                    f"length arithmetic, but family {lm.cfg.family!r} "
                    f"mutates per-slot recurrent STATE inside every "
                    f"decode step (SlotState.supports_rollback() is "
                    f"False) — a rejected draft cannot be un-stepped; "
                    f"serve it with speculate=0")
            if lm.cfg.family == "encdec":
                raise NotImplementedError(
                    "speculative decoding: encdec drafters would need "
                    "their own per-slot cross caches encoded at "
                    "admission; serve encdec with speculate=0")
            if adapters is not None:
                raise NotImplementedError(
                    "speculative decoding with an AdapterStore would "
                    "need the drafter rebuilt per slot->adapter remap; "
                    "serve adapters with speculate=0")
            if drafter is None:
                raise ValueError(
                    "speculate > 0 needs a drafter: pass drafter='mtp' "
                    "(mla_moe with a trained MTP head, k=1), a "
                    "PolicyTree spec string (e.g. '*=intq8' — a "
                    "reduced-bits self-speculation view of the merged "
                    "base, built via make_self_drafter), or a prebuilt "
                    "drafter params tree")
            if isinstance(drafter, str) and drafter == "mtp":
                if lm.cfg.family != "mla_moe" or not lm.cfg.mtp \
                        or "mtp_block" not in params:
                    raise ValueError(
                        f"drafter='mtp' needs an mla_moe model trained "
                        f"with cfg.mtp=True (family {lm.cfg.family!r}, "
                        f"mtp={lm.cfg.mtp}, mtp_block "
                        f"{'present' if 'mtp_block' in params else 'absent'})")
                if self.speculate != 1:
                    raise ValueError(
                        f"the MTP head predicts exactly ONE token ahead; "
                        f"speculate must be 1 with drafter='mtp' (got "
                        f"{self.speculate})")
                self._mtp_draft = True
            elif isinstance(drafter, str):
                self.draft_params = make_self_drafter(
                    params, drafter, base=lm.cfg.quant)
            else:
                self.draft_params = drafter
            if self.draft_params is not None:
                self.draft_aux = lm.absorbed_weights(self.draft_params)
        self.page_size = page_size
        if page_size > 0:
            slot_pages = pages_for(max_len, page_size)
            if n_pages is None:
                n_pages = n_slots * slot_pages + 1   # +1: reserved null
            self.n_pages = n_pages
            # raises for rwkv (no CACHE leaves to page) and n_pages < 2
            self.slot_state = lm.slot_state(page_size, n_pages)
        else:
            self.n_pages = 0
            self.slot_state = lm.slot_state()
        # encdec: per-slot frozen cross-cache capacity (encoder frames)
        self.max_src = (max(1, max_src or int(max_len * lm.cfg.source_frac))
                        if lm.cfg.family == "encdec" else 0)
        # step-invariant per-layer absorbed weights (None for gqa):
        # dequantized once here, never inside the per-step jitted graph
        self.aux = lm.absorbed_weights(params)
        # called once per engine iteration, before admission/dispatch:
        # hook(engine).  May sleep (straggler injection), poison the
        # decode state (poison_cache) or raise (crash injection) — see
        # repro.runtime.fault.FaultInjector.  Survives reset().
        self.step_hook = step_hook
        self._declare_compile_budgets()
        self.reset()

    def _declare_compile_budgets(self):
        """Register this engine's compile budgets with the active
        :class:`~repro.runtime.compile_guard.CompileGuard` (no-op when
        none is active).  Budgets are per ENGINE on shared module-level
        jits — a second engine accumulates its own allowance onto the
        same program — and encode the documented invariants.  Programs
        consuming the cache pytree get x2 "placement" headroom: the
        host-built cache right after construction/``reset()`` keys one
        program, and the committed device output of the first jitted
        dispatch keys another (visible under a mesh context).  Both are
        one-time variants per shape family, not O(steps) growth.

          * ``_JIT_STEP``: one chunk-width ragged program, x2 placements.
          * ``_JIT_RESET``: one mask-shaped program, x2 placements.
          * ``_JIT_BURST``: the pow2 ladder k in {1, 2, .., decode_burst}
            -> bit_length(decode_burst) scan programs (bursts only ever
            see a post-dispatch cache, so no placement doubling).
          * ``_JIT_ENCODE`` (encdec only): pow2 src buckets capped at
            ``max_src`` -> bit_length(max_src), +1 when the cap itself
            is not a power of two (the capped top bucket is extra); the
            encoder takes host-fresh inputs every call, so no doubling.
        """
        g = compile_guard.current()
        if g is None:
            return
        # per-engine budget ledger: contributions are keyed by a token
        # unique to this engine and reclaimed when the engine is
        # garbage-collected, so a long-lived process churning engines no
        # longer accumulates unbounded allowance on the shared
        # module-level jits (PR 9 caveat).  The finalizer holds the
        # guard and the token, never the engine.
        owner = f"engine-{id(self)}"
        weakref.finalize(self, g.release_owner, owner)
        step_budget = 4
        if self.draft_params is not None:
            # the self-spec drafter's params pytree has its own treedef
            # (reduced-bits storage), so its ride-along/prefill steps key
            # their own _JIT_STEP programs: same chunk-width x placement
            # family as the target's -> one extra allowance of 4
            step_budget += 4
        g.declare_jit("engine._JIT_STEP", _JIT_STEP, step_budget,
                      owner=owner)
        g.declare_jit("engine._JIT_RESET", _JIT_RESET, 2, owner=owner)
        g.declare_jit("engine._JIT_BURST", _JIT_BURST,
                      self.decode_burst.bit_length(), owner=owner)
        if self.speculate:
            # one fixed-width program each (C = speculate + 1 / scan
            # length speculate + 1 / C = 2), x2 cache placements
            if self._mtp_draft:
                g.declare_jit("engine._JIT_SPEC_MTP", _JIT_SPEC_MTP, 2,
                              owner=owner)
            else:
                g.declare_jit("engine._JIT_DRAFT", _JIT_DRAFT, 2,
                              owner=owner)
                g.declare_jit("engine._JIT_VERIFY", _JIT_VERIFY, 2,
                              owner=owner)
        if self.max_src:
            budget = self.max_src.bit_length()
            if self.max_src & (self.max_src - 1):
                budget += 1
            g.declare_jit("engine._JIT_ENCODE", _JIT_ENCODE, budget,
                          owner=owner)

    def reset(self):
        """Drop all queued/in-flight state (compiled steps are shared
        module-wide and survive).  Paged engines also rebuild the page
        table — registered prefix hashes do not survive a reset (their
        device pages are reinitialized)."""
        pt = None
        if self.page_size > 0:
            pt = PageTable(self.n_pages, self.page_size,
                           self.slot_state.slot_pages(self.max_len))
        self.sched = Scheduler(self.n_slots, self.max_len,
                               self.prefill_chunk, page_table=pt,
                               headroom=self.speculate)
        self.cache = self.slot_state.init(
            self.n_slots, self.max_len, dtype=self.cache_dtype,
            src_cap=self.max_src or None)
        # self-speculation: the drafter mirrors the target's decode
        # state shape-for-shape (paged drafters own a SECOND pool
        # addressed by the same page rows, mirrored in _publish_pages),
        # so draft rows land at the same positions and the post-accept
        # rollback is one shared length subtraction
        self.draft_cache = None
        if self.draft_params is not None:
            self.draft_cache = self.slot_state.init(
                self.n_slots, self.max_len, dtype=self.cache_dtype,
                src_cap=self.max_src or None)
        self.stats = EngineStats()
        self._adapter_key = None
        self._refresh_adapters()

    @property
    def page_table(self) -> Optional[PageTable]:
        """The live page pool (None on contiguous engines)."""
        return self.sched.page_table

    # ---------------- public API ----------------

    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None,
               rid: Optional[int] = None, src=None,
               adapter_id=None) -> int:
        """Queue a request; returns its rid (key into run()'s results).
        Pass ``rid`` to keep a caller-side id (e.g. a trace's pinned
        rid); omitted rids auto-assign past any pinned ones.  ``src``
        (encdec only) carries the request's encoder frames [Ss, d].
        ``adapter_id`` (name or id of a registered AdapterStore entry;
        0/None = null adapter) binds the request to one adapter —
        unknown ids fail loudly HERE, not mid-serve."""
        aid = 0
        if adapter_id not in (None, 0):
            if self.adapters is None:
                raise ValueError(
                    f"request names adapter {adapter_id!r} but the engine "
                    f"has no AdapterStore (pass adapters= at construction)")
            aid = self.adapters.resolve(adapter_id)  # ValueError on unknown
            self.adapters.touch(aid)
        if src is not None:
            if self.lm.cfg.family != "encdec":
                raise ValueError(
                    f"src frames are an encdec request field; family is "
                    f"{self.lm.cfg.family!r}")
            src = np.asarray(src, np.float32)
            if src.ndim != 2 or src.shape[1] != self.lm.cfg.d_model:
                raise ValueError(
                    f"src must be [Ss, d_model={self.lm.cfg.d_model}]; "
                    f"got {src.shape}")
            if src.shape[0] == 0:
                raise ValueError(
                    "src has zero frames; pass src=None for a src-less "
                    "request (a [0, d] src would burn an encoder dispatch "
                    "at admission to pin nothing)")
            if src.shape[0] > self.max_src:
                raise ValueError(
                    f"request has {src.shape[0]} encoder frames but the "
                    f"engine's cross cache holds max_src={self.max_src}")
        req = Request(prompt=np.asarray(prompt, np.int32).reshape(-1),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      rid=-1 if rid is None else rid, src=src,
                      adapter_id=aid)
        return self.sched.submit(req)

    def run(self) -> Dict[int, List[int]]:
        """Serve until queue and slots drain; returns rid -> token list
        (stats in :attr:`stats` — wall clock accumulates per
        :meth:`step_once`, so externally-driven loops report it too)."""
        while self.sched.has_work:
            self.step_once()
        # republish the (now empty) live-id set: without this, the store
        # would keep refusing to evict the last batch's adapters after
        # the engine has fully drained
        self._refresh_adapters()
        return self.sched.outputs

    def evict_slot(self, i: int):
        """Evict slot ``i`` (cancellation / deadline expiry) ATOMICALLY:
        the scheduler frees the slot and releases its pages, and the
        live-adapter set is republished in the same call — so the
        AdapterStore can evict the dropped request's adapter (and the
        page pool can re-hand its pages) immediately, not at the next
        engine step.  Callers must use this, not
        ``sched.evict_slot``, whenever the engine serves adapters or a
        paged cache.  Returns the evicted Slot (or None if free)."""
        s = self.sched.evict_slot(i)
        self._refresh_adapters()
        return s

    def poison_cache(self):
        """Overwrite every floating-point leaf of the decode state with
        NaN (fault injection: simulates silent device-state corruption).
        Any slot whose LIVE state is subsequently read produces NaN
        logits and trips the in-graph health bit (:class:`EngineCorrupted`
        before commit); corrupted rows that are masked out or fully
        overwritten by fresh prefill are — by the engine's own masking
        contract — never read, so poisoning an all-fresh batch is
        vacuous."""
        self.cache = jax.tree.map(
            lambda x: (jnp.full_like(x, jnp.nan)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x),
            self.cache)

    # ---------------- one engine iteration ----------------

    def step_once(self):
        """One engine iteration: (step hook ->) admit + reset refilled
        slots -> one ragged/burst dispatch -> commit.  Raises
        :class:`EngineCorrupted` (before commit) if the dispatch produced
        non-finite logits, and propagates whatever the step hook raises
        (e.g. :class:`repro.runtime.fault.InjectedFault`).  Wall clock
        accrues to :attr:`stats` HERE (not in :meth:`run`), so
        ``tok_per_s`` is meaningful for any driver — including an
        external per-step loop like ``ServingFrontend`` — and even for a
        step that dies mid-dispatch."""
        st = self.stats
        sync0, n0 = st.sync_s, _cached_programs()
        t0 = time.perf_counter()
        try:
            self._step_once_inner()
        finally:
            dt = time.perf_counter() - t0
            st.seconds += dt
            st.host_s += dt - (st.sync_s - sync0)
            st.compiles += _cached_programs() - n0
        guard = compile_guard.current()
        if guard is not None:
            # after the step, not inside the finally: a budget violation
            # must not mask a real dispatch failure mid-step
            guard.check()

    def _step_once_inner(self):
        if self.step_hook is not None:
            self.step_hook(self)
        with tracing.span(tracing.ADMIT):
            self._admit()
        if self.sched.all_decoding:
            if self.speculate:
                self._run_spec()
            else:
                self._run_burst()
        else:
            self._run_ragged()

    def _admit(self):
        filled = self.sched.admit()
        if filled:
            # evict + refill, family-agnostic: one batched SlotState.reset
            # zeroes the refilled slots' lengths and snapshot state
            # (recurrences, cross caches); stale KV rows beyond the zeroed
            # lengths are masked out by construction
            mask = np.zeros((self.n_slots,), bool)
            mask[filled] = True
            self.cache = _JIT_RESET(self.slot_state, self.cache,
                                    jnp.asarray(mask))
            if self.draft_cache is not None:
                # same program, same shapes: a compile-cache hit
                self.draft_cache = _JIT_RESET(self.slot_state,
                                              self.draft_cache,
                                              jnp.asarray(mask))
            self._publish_pages(filled)
            self._pin_cross(filled)
        self._refresh_adapters()

    @contextlib.contextmanager
    def _sync(self):
        """The host waits for the device here and nowhere else: the
        ``repro.engine.sync`` span, timed into ``stats.sync_s``."""
        t0 = time.perf_counter()
        try:
            with tracing.span(tracing.SYNC):
                yield
        finally:
            self.stats.sync_s += time.perf_counter() - t0

    @contextlib.contextmanager
    def host_part(self, name: str):
        """Host work a caller does around :meth:`step_once` for this
        engine (the frontend's intake and harvest): the span ``name``,
        timed into ``stats.host_s`` and ``stats.seconds``."""
        t0 = time.perf_counter()
        try:
            with tracing.span(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.stats.host_s += dt
            self.stats.seconds += dt

    def _publish_pages(self, filled):
        """Paged admission: write each refilled slot's page row — and seed
        its length with the prefix tokens already served from shared
        pages — into the cache pytree.  Pure value updates on unchanged
        shapes: the compiled steps never retrace as the page map churns.
        Runs AFTER the refill reset (which nulls the rows it is about to
        write) and must complete before the next dispatch reads them."""
        if self.page_size == 0:
            return
        pt = self.sched.page_table
        idx = jnp.asarray(filled)
        rows = np.stack([pt.page_row(i) for i in filled])
        lens = np.asarray([self.sched.slots[i].pp for i in filled], np.int32)
        self.cache["pages"] = self.cache["pages"].at[idx].set(
            jnp.asarray(rows))
        self.cache["len"] = self.cache["len"].at[idx].set(jnp.asarray(lens))
        if self.draft_cache is not None:
            # the drafter pool mirrors the page rows 1:1 — a prefix hit
            # is valid for the drafter too, because the drafter wrote
            # its own pool at these same page indices when the original
            # request prefilled (ride-along in _run_ragged)
            self.draft_cache["pages"] = self.draft_cache["pages"].at[
                idx].set(jnp.asarray(rows))
            self.draft_cache["len"] = self.draft_cache["len"].at[idx].set(
                jnp.asarray(lens))

    def _refresh_adapters(self):
        """Rebind ``self.params`` to the store's serving tree for the
        CURRENT slot->adapter mapping.  The rebuild is a host-side tree
        walk sharing every bank/base array by reference, and it only
        runs when the mapping or the store's contents changed (the
        version counter covers register/evict).  Also publishes the
        live-id set so the store's LRU never evicts an adapter that a
        queued or in-flight request still needs."""
        if self.adapters is None:
            return
        self.adapters.set_live(self.sched.live_adapter_ids())
        ids = self.sched.slot_adapter_ids()
        key = (tuple(ids.tolist()), self.adapters.version)
        if key != self._adapter_key:
            self._adapter_key = key
            self.params = self.adapters.with_slot_ids(ids)

    def _pin_cross(self, filled):
        """encdec admission: encode each refilled slot's ``src`` frames
        once and pin the per-layer cross K/V into the slot's frozen cross
        cache.  Src lengths are BUCKETED: frames are zero-padded up to
        the next power of two (capped at ``max_src``) and the true length
        rides into the encoder as a traced ``src_len`` key mask, so at
        most O(log max_src) encoder programs ever compile under live
        traffic with arbitrary lengths.  Masked keys hit exp(NEG_INF) ==
        0 exactly, so the pinned rows match encoding the unpadded source
        to a few ulps (not bit-identical: XLA reduces over the padded
        axis in another order).  Only the first ``ss`` rows (and
        the true length) are pinned; padded rows' garbage K/V never
        enters the cache.  Src-less requests keep the zeroed cross cache
        (cross len 0: a zero context, like the static token-only path)."""
        if self.lm.cfg.family != "encdec":
            return
        cross = self.cache["layers"]["cross"]
        for i in filled:
            src = self.sched.slots[i].req.src
            if src is None:
                continue
            ss = src.shape[0]
            bs = min(self.max_src, 1 << max(ss - 1, 0).bit_length())
            pad = np.zeros((bs, src.shape[1]), np.float32)
            pad[:ss] = src
            ks, vs = _JIT_ENCODE(self.lm, self.params,
                                 jnp.asarray(pad)[None],
                                 jnp.asarray([ss], jnp.int32))
            cross = {
                "k": cross["k"].at[:, i, :ss].set(
                    ks[:, 0, :ss].astype(cross["k"].dtype)),
                "v": cross["v"].at[:, i, :ss].set(
                    vs[:, 0, :ss].astype(cross["v"].dtype)),
                "len": cross["len"].at[i].set(ss),
            }
        self.cache["layers"]["cross"] = cross

    def _run_ragged(self):
        """One mixed prefill/decode ragged step."""
        with tracing.span(tracing.PLAN):
            tokens, n_new = self.sched.plan()
            tokens_d, n_new_d = jnp.asarray(tokens), jnp.asarray(n_new)
        with tracing.span(tracing.DISPATCH):
            nxt, ok, self.cache = _JIT_STEP(self.lm, self.params, self.aux,
                                            self.cache, tokens_d, n_new_d)
            if self.draft_cache is not None:
                # self-speculation ride-along: the drafter consumes the
                # SAME plan so its cache rows stay in lockstep with the
                # target's (prompt chunks and plain decode tokens alike);
                # its output tokens are discarded, its health deliberately
                # unchecked (garbage drafts are rejected by verification,
                # never committed).  Same chunk-width program family as
                # the target's step, keyed by the drafter's own params
                # treedef.
                _, _, self.draft_cache = _JIT_STEP(
                    self.lm, self.draft_params, self.draft_aux,
                    self.draft_cache, tokens_d, n_new_d)
                self.stats.dispatches += 1
        with self._sync():
            if not bool(ok):
                raise EngineCorrupted(
                    "non-finite logits in ragged step (decode state is "
                    "poisoned); tokens NOT committed")
            nxt = np.asarray(nxt)
        with tracing.span(tracing.COMMIT):
            # slots past their prompt after this plan emit one token each;
            # mid-prompt slots consume rows but emit nothing yet
            emitting = sum(1 for i, s in enumerate(self.sched.slots)
                           if s is not None and n_new[i] > 0
                           and not s.prefilling)
            self.sched.commit(nxt)
            st = self.stats
            c = int(tokens.shape[1])
            st.dispatches += 1
            st.forward_passes += 1
            st.model_steps += c
            # model-step units: this dispatch computed C rows for every
            # slot, of which each slot consumed n_new (same units as
            # _run_burst)
            st.slot_steps += self.n_slots * c
            st.busy_slot_steps += int(n_new.sum())
            st.tokens_out += emitting

    def _run_burst(self):
        """K fused decode steps in one program (per-slot stop masks)."""
        with tracing.span(tracing.PLAN):
            tok, remaining, eos = self.sched.burst_state()
            # follow the SHORTEST active request so finished slots are
            # evicted and refilled promptly (occupancy), rounding DOWN to
            # a power of two: never overshoots the shortest request, and
            # only O(log(decode_burst)) scan programs ever compile.  An
            # EOS-stopped slot still idles on-device until the burst
            # returns.
            k_min = int(remaining[remaining > 0].min())
            k = int(min(self.decode_burst, 1 << (k_min.bit_length() - 1)))
            args = (jnp.asarray(tok), jnp.asarray(remaining),
                    jnp.asarray(eos))
        with tracing.span(tracing.DISPATCH):
            self.cache, tok_d, rem_d, emitted, ok = _JIT_BURST(
                self.lm, self.params, self.aux, self.cache, *args, k_steps=k)
        with self._sync():
            if not bool(ok):
                raise EngineCorrupted(
                    "non-finite logits in decode burst (decode state is "
                    "poisoned); tokens NOT committed")
            emitted = np.asarray(emitted)
            tok_d, rem_d = np.asarray(tok_d), np.asarray(rem_d)
        with tracing.span(tracing.COMMIT):
            self.sched.commit_burst(emitted, tok_d, rem_d)
            st = self.stats
            st.dispatches += 1
            st.forward_passes += k
            st.model_steps += k
            st.slot_steps += self.n_slots * k
            st.busy_slot_steps += int((emitted >= 0).sum())
            st.tokens_out += int((emitted >= 0).sum())

    def _run_spec(self):
        """One draft-and-verify speculative dispatch (all slots
        decoding).  Draft k candidates per active slot — the reduced-bits
        self-speculation model, or the in-graph MTP head — then verify
        all k+1 positions in ONE ragged step and commit each slot's
        accepted greedy prefix plus its bonus/correction token
        (:mod:`repro.serving.speculative`: token-identical to plain
        greedy by construction).  The verify step advanced every active
        slot by the full k+1 rows; the rejected tail rolls back by a
        plain per-slot length subtraction — a values-only update, like
        the page map, so no compiled program ever retraces — on the
        target AND (self-spec) drafter caches, restoring the invariant
        that the cache holds the committed stream minus its last token.

        On :class:`EngineCorrupted` the drafter cache may already have
        advanced for the failed dispatch — irrelevant, because the
        corruption contract already requires a full ``reset()`` before
        serving continues (``ServingFrontend`` rebuilds and replays)."""
        st = self.stats
        if self._mtp_draft:
            with tracing.span(tracing.PLAN):
                tok, remaining, eos = self.sched.burst_state()
                active = remaining > 0
                held = self.sched.spec_drafts()
                have = active & (held >= 0)
                tokens = np.stack([tok, np.maximum(held, 0)], axis=1)
                n_new = np.where(active, np.where(have, 2, 1), 0)
                n_new = n_new.astype(np.int32)
                args = (jnp.asarray(tokens), jnp.asarray(n_new))
            with tracing.span(tracing.DISPATCH):
                v, mtp_d, ok, self.cache = _JIT_SPEC_MTP(
                    self.lm, self.params, self.aux, self.cache, *args)
            st.dispatches += 1
            with self._sync():
                if not bool(ok):
                    raise EngineCorrupted(
                        "non-finite logits in speculative verify (decode "
                        "state is poisoned); tokens NOT committed")
                v, mtp_d = np.asarray(v), np.asarray(mtp_d)
            drafts = np.where(have, held, -1)[:, None]
            proposed = int(have.sum())
        else:
            k = self.speculate
            with tracing.span(tracing.PLAN):
                tok, remaining, eos = self.sched.burst_state()
                active = remaining > 0
                args = (jnp.asarray(tok), jnp.asarray(active))
            with tracing.span(tracing.DISPATCH):
                self.draft_cache, d = _JIT_DRAFT(
                    self.lm, self.draft_params, self.draft_aux,
                    self.draft_cache, *args, k_steps=k + 1)
            with self._sync():
                drafts = np.asarray(d)[:k].T          # [B, k] = d_1..d_k
            with tracing.span(tracing.PLAN):
                tokens = np.concatenate([tok[:, None], drafts], axis=1)
                n_new = np.where(active, k + 1, 0).astype(np.int32)
                args = (jnp.asarray(tokens), jnp.asarray(n_new))
            with tracing.span(tracing.DISPATCH):
                v, ok, self.cache = _JIT_VERIFY(
                    self.lm, self.params, self.aux, self.cache, *args)
            st.dispatches += 2
            with self._sync():
                if not bool(ok):
                    raise EngineCorrupted(
                        "non-finite logits in speculative verify (decode "
                        "state is poisoned); tokens NOT committed")
                v = np.asarray(v)
            proposed = k * int(active.sum())
        with tracing.span(tracing.COMMIT):
            emitted, m = accept_drafts(drafts, v, n_new, remaining, eos)
            # un-advance the rejected tail on every cache that stepped:
            # values-only length updates (the compiled programs never see
            # a new shape), sound because every read mask is bounded by
            # the slot's own len (SlotState.supports_rollback, checked at
            # construction) — identical for contiguous and paged layouts
            rb = rollback_counts(n_new, m)
            dec = jnp.asarray(rb.astype(np.int32))
            self.cache["len"] = self.cache["len"] - dec
            if self.draft_cache is not None:
                self.draft_cache["len"] = self.draft_cache["len"] - dec
            if self._mtp_draft:
                # the next-dispatch draft: column m-1 is the MTP
                # prediction conditioned on exactly the committed stream
                # (rows 0..m-1 plus the new last token v[m-1])
                nd = mtp_d[np.arange(self.n_slots), np.maximum(m - 1, 0)]
                self.sched.set_spec_drafts(np.where(m > 0, nd, -1))
            self.sched.commit_spec(emitted, m)
            c = int(tokens.shape[1])
            st.forward_passes += 1
            st.model_steps += c
            st.slot_steps += self.n_slots * c
            st.busy_slot_steps += int(n_new.sum())
            st.tokens_out += int(m.sum())
            st.proposed_tokens += proposed
            st.accepted_tokens += int(np.maximum(m - 1, 0).sum())
