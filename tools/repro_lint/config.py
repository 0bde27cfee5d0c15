"""Rule configuration + per-file waivers for repro-lint.

``CONFIG`` declares each rule's scope (which files it watches, which
names it treats as host-static, ...).  ``WAIVERS`` is the ONLY way to
ship a violation: one entry per (rule, file), carrying a justification
string that the runner refuses to accept empty — and refuses to keep if
it no longer matches any violation (stale waivers fail the run, so the
list can only shrink as violations are fixed).
"""

CONFIG = {
    # Host-purity: these modules are the unit-testable scheduling brain;
    # importing jax there couples slot bookkeeping to device tracing.
    "RL001": {
        "pure_host_modules": (
            "src/repro/serving/scheduler.py",
            "src/repro/serving/paging.py",
            "src/repro/serving/trace.py",
            "src/repro/serving/speculative.py",
        ),
        "forbidden_roots": ("jax", "jaxlib"),
    },
    # Key-sniffing: the scheme-discriminating storage keys the pre-PR-2
    # dispatch style probed for.  `sniff_keys` covers membership tests
    # (`"q" in p`); `data_subscript_keys` covers raw `<x>.data["ad"]`
    # access to a LinearParams payload.  core/schemes.py is the single
    # owner of storage layouts and is exempt.
    "RL002": {
        "owner": "src/repro/core/schemes.py",
        "sniff_keys": ("q", "ad", "nf4"),
        "data_subscript_keys": ("q", "ad", "nf4", "w"),
    },
    # Compile discipline: jax.jit at module level only; pallas_call only
    # inside the kernels layer.  Scoped to src/ — tests may jit inline
    # (each test process is one trace cache, and inline jits there are
    # often the point of the test).
    "RL003": {
        "paths": ("src",),
        "kernel_prefix": "src/repro/kernels/",
    },
    # Traced-value control flow, checked in functions reachable from
    # module-level jit roots.  `static_params` is the declared contract:
    # parameters with these names carry host-static values (configs,
    # hashable model objects, compile-time shape/flag knobs) and may
    # drive Python branches; everything else entering a jitted call tree
    # is assumed traced.
    "RL004": {
        "paths": ("src",),
        "static_params": (
            "self", "cls", "lm", "cfg", "pol", "policy", "scheme",
            "slot_state", "mesh", "quantizer", "opt_cfg",
            # compile-time knobs threaded as static_argnames
            "causal", "window", "interpret", "bits", "group_size", "s",
            "out_dtype", "dtype", "scale_dtype", "block", "k_steps",
            "gen_len", "axis", "eps", "scale", "n_heads", "n_kv", "rank",
            "page_size", "src_cap", "training",
        ),
        # attribute reads that are static metadata even on traced values:
        # array metadata, QuantizedLinear's shape-derived properties and
        # static=True dataclass fields, LinearParams' registry metadata
        "static_attrs": ("shape", "ndim", "dtype", "size", "at",
                         "aval", "sharding",
                         "d_in", "d_out", "n_groups", "bits", "group_size",
                         "scheme", "policy", "exempt"),
        # calls whose result is host-static regardless of argument taint
        # (set/sorted over a params dict read its KEYS — static pytree
        # structure)
        "static_calls": ("len", "isinstance", "hasattr", "callable",
                         "type", "range", "enumerate", "id", "repr",
                         "set", "sorted"),
    },
    # Frontend lock discipline: writes to the declared cross-thread state
    # must sit under `with self._lock`.  Everything else in the frontend
    # is serve-loop-thread-only by the module's documented threading
    # contract and stays out of the declared set.
    "RL005": {
        "files": {
            "src/repro/serving/frontend.py": {
                "lock_attr": "_lock",
                "shared": ("tickets", "_intake", "_cancels", "_draining",
                           "_drain_cancel", "_stopped", "_next_rid",
                           "_seq", "fatal"),
            },
        },
    },
    # Deterministic serving: these modules promise byte-identical replay
    # (crash recovery, trace reproduction); ambient clocks / unseeded
    # rngs there make "deterministic recovery" a lie.  Injectable-clock
    # DEFAULTS (``clock=time.monotonic``) are references, not calls, and
    # do not flag.
    "RL006": {
        "files": (
            "src/repro/serving/scheduler.py",
            "src/repro/serving/paging.py",
            "src/repro/serving/trace.py",
            "src/repro/serving/speculative.py",
            "src/repro/serving/frontend.py",
            "src/repro/serving/engine.py",
        ),
        "clock_calls": ("time.time", "time.monotonic", "time.perf_counter",
                        "datetime.now", "datetime.utcnow"),
        "random_roots": ("random",),   # the stdlib global-state rng
    },
}

# ---------------------------------------------------------------------------
# Waivers: {"rule", "path", "reason"} — path is repo-relative, reason is
# MANDATORY and non-empty.  A waiver suppresses every violation of that
# rule in that file; the runner fails on waivers that suppress nothing.
# ---------------------------------------------------------------------------

WAIVERS = [
    {
        "rule": "RL003",
        "path": "src/repro/launch/steps.py",
        "reason": (
            "step factories (make_train_step / make_prefill_step / ...) "
            "close over per-mesh in_shardings/out_shardings, so their jits "
            "cannot be module-level; each factory is invoked once per "
            "launch and returns the jitted step for the caller to reuse — "
            "the retrace hazard RL003 guards against (a fresh jit per "
            "call of the HOT path) does not apply."),
    },
    {
        "rule": "RL006",
        "path": "src/repro/serving/engine.py",
        "reason": (
            "time.perf_counter() in the engine feeds only EngineStats' "
            "seconds, host_s and sync_s (reporting); token state, "
            "scheduling decisions and recovery replay never read the "
            "clock, so determinism is unaffected.  The frontend's "
            "deadline clock is injectable and is the one "
            "determinism-sensitive timer."),
    },
    {
        "rule": "RL004",
        "path": "src/repro/core/schemes.py",
        "reason": (
            "trainable_mask's `if sel and not jax.tree.leaves(v)` tests "
            "pytree STRUCTURE emptiness (leaf count is static under "
            "trace); the taint model cannot separate a list container's "
            "truthiness from its traced contents, and rewriting the check "
            "to appease it would obscure the intent."),
    },
    {
        "rule": "RL002",
        "path": "tests/test_schemes.py",
        "reason": (
            "the scheme-equivalence suite deliberately reimplements the "
            "pre-refactor key-sniffing dispatch as the bit-equivalence "
            "reference, and builds misnamed-key trees to test the loud "
            "failure paths — reproducing exactly what RL002 bans in "
            "production code is this file's job."),
    },
]
