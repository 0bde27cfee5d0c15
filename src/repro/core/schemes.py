"""First-class linear-scheme API: registry + tagged params + per-layer policy.

This module is the ONLY place allowed to reason about how a linear layer's
parameters are stored.  Everything else in the repo goes through four
entry points — :func:`linear_init`, :func:`linear_apply`,
:func:`merge_linear` / :func:`merge_tree`, :func:`dense_view` — and the
partition / conversion helpers built on them.

Schemes
-------
A scheme is a registered :class:`LinearScheme` describing one storage +
compute strategy for ``y = x @ W_eff``:

  fp       plain dense weight (pretraining / accuracy reference)
  lora     fp base + unconstrained LoRA                    (baseline)
  qlora    NF4 base + unconstrained LoRA                   (baseline)
  qalora   INT-N group-wise base + group-pooled adapter    (the paper)
  intq     bare INT-N group-wise linear (merged QA-LoRA / PTQ output)

Each linear's params live in a :class:`LinearParams` container whose
*static* fields carry the scheme tag and the resolved :class:`QuantPolicy`
— so forward/merge/partition dispatch is tag-driven, never by sniffing
dict keys, and kernel routing lives inside the quantized schemes only:
``use_kernel`` selects the Pallas ``qalora_matmul`` for qalora (and the
banked variant for qalora_slot); intq picks the decode GEMV from the
backend and the row count alone (:func:`repro.kernels.intq_matmul`).

Registering a new scheme is ~50 lines::

    @register_scheme("ternary")
    class TernaryScheme(LinearScheme):
        trainable = ("ad",)
        def init(self, key, d_in, d_out, pol): ...
        def apply(self, data, x, pol): ...
        def merge(self, data, pol): ...
        ...

Per-layer policies
------------------
:class:`PolicyTree` maps glob patterns over parameter paths to
:class:`QuantPolicy` records, e.g.::

    PolicyTree.parse("*=int4,*/attn/wo=int8,lm_head=fp", base=cfg.quant)

Resolution is last-match-wins over the rule list; the bare catch-all
``"*"`` never applies to ``lm_head`` (the output projection stays fp
unless a rule names it explicitly — the standard exemption in the
quantization literature).  Unmatched paths fall back to fp.
"""

from __future__ import annotations

import dataclasses
import fnmatch
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import lora as lora_lib
from . import nf4 as nf4_lib
from . import qalora as qalora_lib
from . import quant as quant_lib
from ..runtime import tracing

__all__ = [
    "QuantPolicy", "FP", "PolicyTree", "resolve_policy", "resolve_path",
    "LinearScheme", "LinearParams", "register_scheme", "get_scheme",
    "registered_schemes", "is_linear", "dense_linear", "quantized_base",
    "adapter_params", "from_dense_linear",
    "linear_init", "linear_apply", "merge_linear", "dense_view",
    "map_linears", "merge_tree", "convert_tree", "trainable_mask",
    "tree_flops_bytes",
]


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Per-linear quantization/adaptation policy (one resolved record)."""

    mode: str = "qalora"  # a registered scheme name
    bits: int = 4
    group_size: int = 32
    rank: int = 16
    s: float = 2.0
    use_kernel: bool = False  # qalora/qalora_slot: route through Pallas
    dtype: Any = jnp.float32  # compute/adapter dtype
    scale_dtype: Any = jnp.float32  # quantization scale/zero storage dtype

    # -- uniform policies are trivially "scoped": every path resolves to self
    def at(self, *names: str) -> "QuantPolicy":
        return self

    def resolve(self) -> "QuantPolicy":
        return self

    @property
    def default(self) -> "QuantPolicy":
        return self


FP = QuantPolicy(mode="fp")
_POLICY_FIELDS = frozenset(f.name for f in dataclasses.fields(QuantPolicy))

# the head is exempt from catch-all quantization rules unless named
_HEAD_PATHS = ("lm_head", "head")
_CATCH_ALL = "*"


def _norm_head(path: str) -> str:
    return "lm_head" if path in _HEAD_PATHS else path


@dataclasses.dataclass(frozen=True)
class PolicyTree:
    """Glob-pattern -> :class:`QuantPolicy` rules with scoped resolution.

    ``rules`` are matched (fnmatch) against slash-joined parameter paths,
    e.g. ``blocks/attn/wo``; the LAST matching rule wins.  ``prefix``
    tracks the current scope while the model threads the tree through its
    inits (``pol.at("attn").at("wq")``).
    """

    rules: Tuple[Tuple[str, QuantPolicy], ...]
    prefix: str = ""

    def at(self, *names: str) -> "PolicyTree":
        pre = "/".join((self.prefix,) + names) if self.prefix else "/".join(names)
        return dataclasses.replace(self, prefix=pre)

    def resolve(self) -> QuantPolicy:
        path = _norm_head(self.prefix)
        hit = None
        for pat, pol in self.rules:
            if path == "lm_head" and pat == _CATCH_ALL:
                continue  # lm_head exemption: catch-all never quantizes it
            if fnmatch.fnmatchcase(path, _norm_head(pat)):
                hit = pol
        if hit is None:
            return dataclasses.replace(self.default, mode="fp")
        return hit

    @property
    def default(self) -> QuantPolicy:
        # mirror resolution order (last match wins) for field delegation
        for pat, pol in reversed(self.rules):
            if pat == _CATCH_ALL:
                return pol
        return self.rules[-1][1] if self.rules else FP

    def __getattr__(self, name):
        # delegate QuantPolicy field reads (drivers do ``cfg.quant.dtype``)
        # to the default rule; complete by construction as fields evolve
        if name in _POLICY_FIELDS:
            return getattr(self.default, name)
        raise AttributeError(name)

    # -- construction -------------------------------------------------------

    @classmethod
    def of(cls, mapping, base: Optional[QuantPolicy] = None) -> "PolicyTree":
        """Build from ``{pattern: QuantPolicy | spec-string}`` (insertion
        order = precedence order, last match wins)."""
        base = base or QuantPolicy()
        rules = []
        for pat, val in mapping.items():
            pol = val if isinstance(val, QuantPolicy) else _parse_value(val, base)
            rules.append((pat, pol))
        return cls(rules=tuple(rules))

    @classmethod
    def parse(cls, spec: str, base: Optional[QuantPolicy] = None) -> "PolicyTree":
        """Parse a CLI policy string: ``"*=int4,*/attn/wo=int8,lm_head=fp"``.

        Values: ``fp`` | ``lora`` | ``qlora`` | ``int<N>`` (QA-LoRA at N
        bits) | ``intq<N>`` (bare quantized, no adapter), with optional
        ``:g<M>`` (group size) / ``:r<R>`` (rank) suffixes, e.g.
        ``int4:g64:r8``.
        """
        base = base or QuantPolicy()
        rules = []
        for item in spec.split(","):
            item = item.strip()
            if not item:
                continue
            if "=" not in item:
                raise ValueError(f"policy item {item!r}: expected pattern=value")
            pat, val = item.split("=", 1)
            rules.append((pat.strip(), _parse_value(val.strip(), base)))
        return cls(rules=tuple(rules))


def _parse_value(val: str, base: QuantPolicy) -> QuantPolicy:
    tok, *opts = val.split(":")
    kw: Dict[str, Any] = {}
    if tok in ("fp", "lora", "qlora"):
        kw["mode"] = tok
    elif tok.startswith("intq"):
        kw["mode"] = "intq"
        if tok[4:]:
            kw["bits"] = int(tok[4:])
    elif tok.startswith("int"):
        kw["mode"] = "qalora"
        if tok[3:]:
            kw["bits"] = int(tok[3:])
    elif tok == "qalora":
        kw["mode"] = "qalora"
    else:
        raise ValueError(f"unknown policy value {tok!r}")
    for o in opts:
        if o.startswith("g"):
            kw["group_size"] = int(o[1:])
        elif o.startswith("r"):
            kw["rank"] = int(o[1:])
        else:
            raise ValueError(f"unknown policy option {o!r} in {val!r}")
    return dataclasses.replace(base, **kw)


def resolve_policy(pol) -> QuantPolicy:
    """Resolve a (possibly scoped) policy object to one QuantPolicy."""
    return pol.resolve()


def resolve_path(pol, path: str) -> QuantPolicy:
    """Resolve the policy for an explicit parameter path.

    For a plain :class:`QuantPolicy` the only special case is the head:
    uniform policies never quantize ``lm_head`` (same exemption as the
    PolicyTree catch-all)."""
    if isinstance(pol, PolicyTree):
        return dataclasses.replace(pol, prefix=path).resolve()
    if _norm_head(path) == "lm_head" and pol.mode != "fp":
        return dataclasses.replace(pol, mode="fp")
    return pol


# ---------------------------------------------------------------------------
# tagged container
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LinearParams:
    """One linear layer's parameters, tagged with its scheme + policy.

    ``data`` holds the scheme-defined arrays (e.g. ``{"q": QuantizedLinear,
    "ad": QALoRAParams}``); ``scheme`` / ``policy`` / ``exempt`` are static
    pytree metadata, so jit/scan/vmap carry them for free and forward
    dispatch needs no key sniffing.  ``exempt=True`` marks layers forced fp
    at init (routers, mtp_proj) that conversion must never quantize.
    """

    data: Dict[str, Any]
    scheme: str = dataclasses.field(metadata=dict(static=True), default="fp")
    policy: QuantPolicy = dataclasses.field(
        metadata=dict(static=True), default=FP)
    exempt: bool = dataclasses.field(metadata=dict(static=True), default=False)

    # dict-style read access keeps downstream code/tests ergonomic
    def __getitem__(self, k):
        return self.data[k]

    def __contains__(self, k):
        return k in self.data

    def get(self, k, default=None):
        return self.data.get(k, default)

    def keys(self):
        return self.data.keys()

    def items(self):
        return self.data.items()


def is_linear(p) -> bool:
    return isinstance(p, LinearParams)


def dense_linear(w, policy: Optional[QuantPolicy] = None) -> LinearParams:
    """Wrap an existing dense weight as an fp-scheme linear."""
    pol = policy or dataclasses.replace(FP, dtype=w.dtype)
    return LinearParams(data={"w": w}, scheme="fp",
                        policy=dataclasses.replace(pol, mode="fp"))


# schemes whose ``data`` carries a packed INT-N base under "q"
_QUANT_BASE_SCHEMES = ("intq", "qalora", "qalora_slot")


def quantized_base(lp: LinearParams):
    """The packed :class:`QuantizedLinear` base of a quantized-base
    scheme — the sanctioned accessor for code that must touch INT-N
    storage itself (adapter banking, slot serving) rather than a dense
    or forward view.  Keeps the storage-key layout private to this
    module."""
    if not is_linear(lp) or lp.scheme not in _QUANT_BASE_SCHEMES:
        got = lp.scheme if is_linear(lp) else type(lp).__name__
        raise ValueError(
            f"quantized_base: expected one of {_QUANT_BASE_SCHEMES}, "
            f"got {got!r}")
    return lp.data["q"]


def adapter_params(lp: LinearParams):
    """The trainable adapter payload (e.g. ``QALoRAParams``) of an
    adapter-bearing linear, located via the scheme's declared
    ``trainable_paths`` instead of a hard-coded storage key."""
    keys = get_scheme(lp.scheme).trainable_paths(lp.data)
    if len(keys) != 1:
        raise ValueError(
            f"adapter_params: scheme {lp.scheme!r} declares "
            f"{len(keys)} trainable keys {tuple(keys)}; expected exactly "
            f"one adapter payload")
    return lp.data[keys[0]]


# ---------------------------------------------------------------------------
# scheme protocol + registry
# ---------------------------------------------------------------------------


class LinearScheme:
    """Protocol for one linear storage/compute scheme.

    Subclasses implement ``init`` / ``apply`` / ``merge`` (+ optionally
    ``dense_view`` / ``from_dense`` / ``flops_bytes``) over the scheme's
    ``data`` dict.  All 2-D ``[D_in, D_out]``; leading stack dims are
    handled by the module-level wrappers (vmap / per-slice stacking).
    """

    name: str = "?"
    trainable: Tuple[str, ...] = ()  # data keys holding trainable leaves

    # -- required -----------------------------------------------------------

    def init(self, key, d_in: int, d_out: int, pol: QuantPolicy) -> dict:
        raise NotImplementedError

    def apply(self, data: dict, x, pol: QuantPolicy):
        raise NotImplementedError

    def merge(self, data: dict, pol: QuantPolicy) -> Tuple[str, dict]:
        """Fold adapters for deployment; returns (scheme_name, data)."""
        raise NotImplementedError

    # -- defaults -----------------------------------------------------------

    def dense_view(self, data: dict, pol: QuantPolicy, dtype=None):
        """Effective (adapter-included) dense weight ``[D_in, D_out]``."""
        name, merged = self.merge(data, pol)
        return get_scheme(name).dense_view(merged, pol, dtype)

    def trainable_paths(self, data: dict) -> Tuple[str, ...]:
        return self.trainable

    def from_dense(self, key, w, pol: QuantPolicy,
                   quantizer: Optional[Callable] = None) -> dict:
        """Build this scheme's storage from a pretrained dense weight."""
        raise NotImplementedError

    def stack_ndim(self, data: dict) -> int:
        """Leading stack dims (scanned layers / stacked experts)."""
        raise NotImplementedError

    def flops_bytes(self, data: dict, pol: QuantPolicy, m: int = 1):
        """(flops, weight bytes read) for an ``[m, D_in]`` activation."""
        raise NotImplementedError


_REGISTRY: Dict[str, LinearScheme] = {}


def register_scheme(name: str):
    def deco(cls):
        inst = cls()
        inst.name = name
        _REGISTRY[name] = inst
        return cls
    return deco


def get_scheme(name: str) -> LinearScheme:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown linear scheme {name!r}; registered: {sorted(_REGISTRY)}")


def registered_schemes() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _dsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


# ---------------------------------------------------------------------------
# built-in schemes
# ---------------------------------------------------------------------------


@register_scheme("fp")
class FPScheme(LinearScheme):
    """Plain dense linear."""

    def init(self, key, d_in, d_out, pol):
        w = jax.random.normal(key, (d_in, d_out), pol.dtype) \
            / jnp.sqrt(d_in).astype(pol.dtype)
        return {"w": w}

    def apply(self, data, x, pol):
        return x @ data["w"].astype(x.dtype)

    def merge(self, data, pol):
        return "fp", data

    def dense_view(self, data, pol, dtype=None):
        w = data["w"]
        return w.astype(dtype) if dtype is not None else w

    def from_dense(self, key, w, pol, quantizer=None):
        return {"w": w}

    def stack_ndim(self, data):
        return data["w"].ndim - 2

    def flops_bytes(self, data, pol, m=1):
        w = data["w"]
        k, n = w.shape[-2:]
        return 2 * m * k * n, k * n * _dsize(w.dtype)


@register_scheme("lora")
class LoRAScheme(LinearScheme):
    """fp base + unconstrained LoRA (Hu et al., 2021)."""

    trainable = ("ad",)

    def init(self, key, d_in, d_out, pol):
        k1, k2 = jax.random.split(key)
        w = jax.random.normal(k1, (d_in, d_out), jnp.float32) / jnp.sqrt(d_in)
        return {"w": w.astype(pol.dtype),
                "ad": lora_lib.init_lora(k2, d_in, pol.rank, d_out, pol.dtype)}

    def apply(self, data, x, pol):
        return lora_lib.lora_forward(x, data["w"].astype(x.dtype),
                                     data["ad"], pol.s)

    def merge(self, data, pol):
        return "fp", {"w": lora_lib.lora_merge(data["w"], data["ad"], pol.s)}

    def from_dense(self, key, w, pol, quantizer=None):
        d_in, d_out = w.shape
        return {"w": w.astype(pol.dtype),
                "ad": lora_lib.init_lora(key, d_in, pol.rank, d_out, pol.dtype)}

    def stack_ndim(self, data):
        return data["w"].ndim - 2

    def flops_bytes(self, data, pol, m=1):
        w, ad = data["w"], data["ad"]
        k, n = w.shape[-2:]
        r = ad.b.shape[-2]
        flops = 2 * m * k * n + 2 * m * r * (k + n)
        byts = (k * n) * _dsize(w.dtype) + r * (k + n) * _dsize(ad.b.dtype)
        return flops, byts


@register_scheme("qlora")
class QLoRAScheme(LinearScheme):
    """NF4 base + unconstrained LoRA (Dettmers et al., 2023).  Merge falls
    back to fp — the paper's '4+16' row — because the adapter delta is not
    group-constant."""

    trainable = ("ad",)

    def init(self, key, d_in, d_out, pol):
        k1, k2 = jax.random.split(key)
        w = jax.random.normal(k1, (d_in, d_out), jnp.float32) / jnp.sqrt(d_in)
        return {"nf4": nf4_lib.nf4_quantize(w),
                "ad": lora_lib.init_lora(k2, d_in, pol.rank, d_out, pol.dtype)}

    def apply(self, data, x, pol):
        return lora_lib.qlora_forward(x, data["nf4"], data["ad"], pol.s)

    def merge(self, data, pol):
        return "fp", {"w": lora_lib.qlora_merge_fp(data["nf4"], data["ad"], pol.s)}

    def from_dense(self, key, w, pol, quantizer=None):
        d_in, d_out = w.shape
        return {"nf4": nf4_lib.nf4_quantize(w.astype(jnp.float32)),
                "ad": lora_lib.init_lora(key, d_in, pol.rank, d_out, pol.dtype)}

    def stack_ndim(self, data):
        return data["nf4"].codes.ndim - 2

    def flops_bytes(self, data, pol, m=1):
        nf4, ad = data["nf4"], data["ad"]
        k, n = nf4.shape[-2:]
        r = ad.b.shape[-2]
        flops = 2 * m * k * n + 2 * m * r * (k + n)
        byts = k * n // 2 + nf4.absmax.shape[-1] * 4 \
            + r * (k + n) * _dsize(ad.b.dtype)
        return flops, byts


def _qt_bytes(qt) -> int:
    per_col = qt.qweight.shape[-2] + 2 * qt.n_groups * _dsize(qt.scale.dtype)
    return per_col * qt.d_out


@register_scheme("qalora")
class QALoRAScheme(LinearScheme):
    """The paper: frozen INT-N group-wise base + group-pooled adapter.
    Kernel routing lives HERE: ``pol.use_kernel`` selects the fused Pallas
    ``qalora_matmul`` (matmul or decode-GEMV by shape) over the jnp path."""

    trainable = ("ad",)

    def init(self, key, d_in, d_out, pol):
        k1, k2 = jax.random.split(key)
        w = jax.random.normal(k1, (d_in, d_out), jnp.float32) / jnp.sqrt(d_in)
        qt = quant_lib.quantize(w, pol.bits, pol.group_size,
                                scale_dtype=pol.scale_dtype)
        return {"q": qt,
                "ad": qalora_lib.init_qalora(k2, qt.n_groups, pol.rank,
                                             d_out, pol.dtype)}

    def apply(self, data, x, pol):
        if pol.use_kernel:
            from repro.kernels import qalora_matmul  # lazy: kernels optional
            return qalora_matmul(x, data["q"], data["ad"], s=pol.s)
        return qalora_lib.qalora_forward(x, data["q"], data["ad"], pol.s,
                                         compute_dtype=x.dtype)

    def merge(self, data, pol):
        """Exact merge (Appendix B): zeros update only, stays INT-N."""
        return "intq", {"q": qalora_lib.merge(data["q"], data["ad"], pol.s)}

    def from_dense(self, key, w, pol, quantizer=None):
        d_in, d_out = w.shape
        qfn = quantizer or (lambda w_: quant_lib.quantize(
            w_, pol.bits, pol.group_size, scale_dtype=pol.scale_dtype))
        qt = qfn(w.astype(jnp.float32))
        return {"q": qt,
                "ad": qalora_lib.init_qalora(key, d_in // pol.group_size,
                                             pol.rank, d_out, pol.dtype)}

    def stack_ndim(self, data):
        return data["q"].qweight.ndim - 2

    def flops_bytes(self, data, pol, m=1):
        qt, ad = data["q"], data["ad"]
        k, n = qt.d_in, qt.d_out
        g = qt.n_groups
        r = ad.b.shape[-2]
        flops = 2 * m * k * n + 2 * m * r * (g + n)
        byts = _qt_bytes(qt) + r * (g + n) * _dsize(ad.b.dtype)
        return flops, byts


@register_scheme("qalora_slot")
class QALoRASlotScheme(LinearScheme):
    """Multi-tenant serving scheme: one frozen INT-N base shared by a
    stacked bank of QA-LoRA adapters, with a per-row adapter index.

    ``data`` holds ``{"q": QuantizedLinear, "a": [N, L, r] bank,
    "b": [N, r, D_out] bank, "ids": [B] int32}`` (plus leading stack
    dims on all four when the linear is scanned/stacked — ``ids`` is
    broadcast across the stack so per-layer slicing works).  Row ``i``
    of the activation batch computes ``x_i @ dequant(q) + s *
    pool(x_i) @ A[ids_i] @ B[ids_i]``; bank row 0 is the reserved null
    adapter (zeros -> delta exactly 0).  Built ONLY by
    :class:`repro.serving.adapters.AdapterStore` (``with_slot_ids``) —
    the ids ride inside the params pytree, so changing the slot->adapter
    mapping swaps an array value without changing the pytree structure:
    the engine's compiled steps never retrace on an adapter-mix change.
    """

    def init(self, key, d_in, d_out, pol):
        raise NotImplementedError(
            "qalora_slot linears are not initialized directly; build them "
            "from a base tree via repro.serving.adapters.AdapterStore")

    def apply(self, data, x, pol):
        qt, ids = data["q"], data["ids"]
        if pol.use_kernel:
            from repro.kernels import qalora_slot_matmul  # lazy
            ids_full = jnp.broadcast_to(
                ids.reshape(ids.shape + (1,) * (x.ndim - ids.ndim)),
                x.shape[:-1])
            return qalora_slot_matmul(x, qt, data["a"], data["b"],
                                      ids_full, s=pol.s)
        base = x @ quant_lib.dequantize(qt, x.dtype)
        return base + qalora_lib.bank_adapter_delta(
            x, data["a"], data["b"], ids, pol.s, qt.group_size)

    def merge(self, data, pol):
        raise NotImplementedError(
            "a qalora_slot linear banks MANY adapters — there is no single "
            "merge target; use AdapterStore.merged(name) for the merged "
            "single-adapter reference tree")

    def stack_ndim(self, data):
        return data["q"].qweight.ndim - 2

    def flops_bytes(self, data, pol, m=1):
        qt = data["q"]
        k, n = qt.d_in, qt.d_out
        g, r = qt.n_groups, data["a"].shape[-1]
        # each row reads the shared base once plus ITS adapter's rows
        flops = 2 * m * k * n + 2 * m * r * (g + n)
        byts = _qt_bytes(qt) + m * r * (g + n) * _dsize(data["b"].dtype)
        return flops, byts


@register_scheme("intq")
class IntQScheme(LinearScheme):
    """Bare INT-N group-wise linear: merged QA-LoRA output or PTQ result.
    On TPU its decode-shaped calls run the fused dequant-GEMV, every other
    call ``x @ dequantize(q)``; ``use_kernel`` is not read."""

    def init(self, key, d_in, d_out, pol):
        w = jax.random.normal(key, (d_in, d_out), jnp.float32) / jnp.sqrt(d_in)
        return {"q": quant_lib.quantize(w, pol.bits, pol.group_size,
                                        scale_dtype=pol.scale_dtype)}

    def apply(self, data, x, pol):
        from repro.kernels import intq_matmul  # lazy: kernels optional
        return intq_matmul(x, data["q"])

    def merge(self, data, pol):
        return "intq", data

    def dense_view(self, data, pol, dtype=None):
        return quant_lib.dequantize(data["q"], dtype or jnp.float32)

    def from_dense(self, key, w, pol, quantizer=None):
        qfn = quantizer or (lambda w_: quant_lib.quantize(
            w_, pol.bits, pol.group_size, scale_dtype=pol.scale_dtype))
        return {"q": qfn(w.astype(jnp.float32))}

    def stack_ndim(self, data):
        return data["q"].qweight.ndim - 2

    def flops_bytes(self, data, pol, m=1):
        qt = data["q"]
        return 2 * m * qt.d_in * qt.d_out, _qt_bytes(qt)


# ---------------------------------------------------------------------------
# single-linear entry points
# ---------------------------------------------------------------------------


def linear_init(key, d_in: int, d_out: int, pol,
                quantize_policy: bool = True) -> LinearParams:
    """Init one projection under ``pol`` (a QuantPolicy or a scoped
    PolicyTree).  ``quantize_policy=False`` forces fp and tags the layer
    exempt (routers, small accuracy-critical matrices)."""
    rp = resolve_policy(pol)
    exempt = not quantize_policy
    if exempt:
        rp = dataclasses.replace(rp, mode="fp")
    scheme = get_scheme(rp.mode)
    return LinearParams(data=scheme.init(key, d_in, d_out, rp),
                        scheme=rp.mode, policy=rp, exempt=exempt)


def from_dense_linear(key, w, pol, quantizer=None,
                      exempt: bool = False) -> LinearParams:
    """Build a tagged linear from a pretrained dense weight (2-D or
    leading-stacked)."""
    rp = resolve_policy(pol)
    scheme = get_scheme(rp.mode)
    data = _from_dense_stacked(scheme, key, w, rp, quantizer)
    return LinearParams(data=data, scheme=rp.mode, policy=rp, exempt=exempt)


def _from_dense_stacked(scheme, key, w, pol, quantizer):
    lead = w.shape[:-2]
    if not lead:
        return scheme.from_dense(key, w, pol, quantizer)
    flat = w.reshape((-1,) + w.shape[-2:])
    fn = lambda w2: scheme.from_dense(key, w2, pol, quantizer)  # noqa: E731
    try:
        data = jax.vmap(fn)(flat)  # one traced program for the whole stack
    except Exception:
        # non-vmappable custom scheme/quantizer: quantize slice-wise (a
        # genuine from_dense bug re-raises here with a clean traceback)
        import warnings
        warnings.warn(
            f"scheme '{scheme.name}'.from_dense is not vmappable; "
            f"converting {flat.shape[0]} stacked slices sequentially")
        slices = [fn(flat[i]) for i in range(flat.shape[0])]
        data = jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
    return jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), data)


def _wrap_legacy(p, pol) -> LinearParams:
    """Adopt a pre-registry bare-dict linear (old checkpoints / tests).
    The ONLY dict-key sniffing in the codebase lives here."""
    has_ad = "ad" in p
    if "q" in p:
        mode = "qalora" if has_ad else "intq"
    elif "nf4" in p and has_ad:
        mode = "qlora"
    elif "w" in p:
        mode = "lora" if has_ad else "fp"
    else:
        raise ValueError(f"unrecognized legacy linear params: {sorted(p)}")
    if pol is None:
        if has_ad:
            # the adapter scale s (etc.) is not recoverable from a bare
            # dict; silently assuming defaults would mis-merge checkpoints
            # trained with a non-default policy
            raise ValueError(
                f"legacy untagged '{mode}' params need an explicit "
                f"QuantPolicy (adapter scale s, use_kernel); pass pol=...")
        rp = QuantPolicy()
    else:
        rp = resolve_policy(pol)
    return LinearParams(data=dict(p), scheme=mode,
                        policy=dataclasses.replace(rp, mode=mode))


def _as_linear(p, pol=None) -> LinearParams:
    return p if isinstance(p, LinearParams) else _wrap_legacy(p, pol)


def linear_apply(p, x, pol=None):
    """Tag-driven forward.  ``pol`` is only consulted for legacy bare-dict
    params; tagged params carry their own resolved policy."""
    lp = _as_linear(p, pol)
    with tracing.scope(tracing.LINEAR):
        return get_scheme(lp.scheme).apply(lp.data, x, lp.policy)


def merge_linear(p, pol=None) -> LinearParams:
    """Merge adapters for deployment.  QA-LoRA stays quantized (exact);
    QLoRA falls back to fp (the paper's Table-1 '4+16' row).  Idempotent."""
    lp = _as_linear(p, pol)
    name, data = get_scheme(lp.scheme).merge(lp.data, lp.policy)
    return LinearParams(data=data, scheme=name,
                        policy=dataclasses.replace(lp.policy, mode=name),
                        exempt=lp.exempt)


def dense_view(p, dtype=None, pol=None):
    """Effective (adapter-included) dense weight, in ``dtype`` (or the
    storage dtype).  Handles leading stack dims."""
    lp = _as_linear(p, pol)
    scheme = get_scheme(lp.scheme)
    n = scheme.stack_ndim(lp.data)
    fn = lambda d: scheme.dense_view(d, lp.policy, dtype)  # noqa: E731
    for _ in range(n):
        fn = jax.vmap(fn)
    return fn(lp.data)


# ---------------------------------------------------------------------------
# tree walkers
# ---------------------------------------------------------------------------


def _is_legacy_linear(p) -> bool:
    return isinstance(p, dict) and ("ad" in p or "q" in p or "nf4" in p)


def map_linears(tree, fn, pol=None):
    """Apply ``fn(path, LinearParams) -> node`` to every linear in a params
    pytree (tagged containers, plus legacy bare dicts which are adopted)."""
    def walk(p, path):
        if isinstance(p, LinearParams):
            return fn(path, p)
        if _is_legacy_linear(p):
            return fn(path, _wrap_legacy(p, pol))
        if isinstance(p, dict):
            return {k: walk(v, f"{path}/{k}" if path else k)
                    for k, v in p.items()}
        return p

    return walk(tree, "")


def merge_tree(params, pol=None):
    """Merge every adapter in the model into its base (tag-driven walk).
    Replaces the old key-sniffing ``serve.merge_model`` body; idempotent."""
    return map_linears(params, lambda path, lp: merge_linear(lp), pol=pol)


def convert_tree(params, pol, key=None, quantizer=None):
    """Re-store every linear under the (possibly per-layer) target policy:
    generic ``from_dense(dense_view(p))``.  Exempt layers (routers,
    mtp_proj) and group-indivisible matrices keep their fp storage.
    ``quantizer`` overrides RTN for quantized bases (e.g. a GPTQ closure).
    """
    key = jax.random.PRNGKey(0) if key is None else key
    counter = [0]

    def fresh_key():
        counter[0] += 1
        return jax.random.fold_in(key, counter[0])

    def one(path, lp: LinearParams):
        if lp.exempt or (path and path.split("/")[-1] in _LEGACY_SKIP):
            return lp
        tp = resolve_path(pol, path)
        if tp.mode == lp.scheme and tp == lp.policy:
            return lp
        w = dense_view(lp, dtype=jnp.float32)
        d_in = w.shape[-2]
        if tp.mode != "fp" and d_in % tp.group_size != 0:
            return dense_linear(w.astype(lp.policy.dtype), lp.policy)
        if tp.mode == "fp":
            return dense_linear(w.astype(tp.dtype), tp)
        return from_dense_linear(fresh_key(), w, tp, quantizer=quantizer,
                                 exempt=lp.exempt)

    def walk(p, path, parent=""):
        if isinstance(p, LinearParams):
            return one(path, p)
        if _is_legacy_linear(p):
            return one(path, _wrap_legacy(p, pol))
        if isinstance(p, dict):
            if set(p) == {"w"} and hasattr(p["w"], "ndim") and p["w"].ndim >= 2:
                # legacy fp linear: adopt it (skip rule via parent name)
                return one(path, _wrap_legacy(p, FP)) \
                    if parent not in _LEGACY_SKIP else p
            return {k: walk(v, f"{path}/{k}" if path else k, k)
                    for k, v in p.items()}
        return p

    return walk(params, "")


# name-based exemptions for legacy (untagged) trees only; tagged trees
# carry ``exempt`` in their static metadata instead.
_LEGACY_SKIP = {"router", "mtp_proj"}


def trainable_mask(params, pol=None):
    """Same-structure pytree of bools: True on trainable (adapter) leaves.

    Fails loudly when a scheme declares a trainable data key that is
    missing or empty for some layer — the failure mode the old ``"ad"``
    key heuristic hit silently (a misnamed pytree trained nothing).
    """
    def one(path, lp: LinearParams):
        tp = set(get_scheme(lp.scheme).trainable_paths(lp.data))
        missing = sorted(tp - set(lp.data))
        if missing:
            raise ValueError(
                f"scheme '{lp.scheme}' at '{path or '<root>'}' declares "
                f"trainable key(s) {missing} but the params only hold "
                f"{sorted(lp.data)} — nothing would train for this layer")
        data = {}
        for k, v in lp.data.items():
            sel = k in tp
            if sel and not jax.tree.leaves(v):
                raise ValueError(
                    f"scheme '{lp.scheme}' at '{path or '<root>'}': "
                    f"trainable key '{k}' selects zero leaves")
            data[k] = jax.tree.map(lambda _: sel, v)
        return data

    def walk(p, path):
        if isinstance(p, LinearParams):
            return LinearParams(data=one(path, p), scheme=p.scheme,
                                policy=p.policy, exempt=p.exempt)
        if _is_legacy_linear(p):
            # structure-only walk: the policy is irrelevant to the mask,
            # so default it rather than demand one for legacy dicts
            return one(path, _wrap_legacy(p, pol or QuantPolicy()))
        if isinstance(p, dict):
            return {k: walk(v, f"{path}/{k}" if path else k)
                    for k, v in p.items()}
        return jax.tree.map(lambda _: False, p)

    return walk(params, "")


def tree_flops_bytes(params, m: int = 1, pol=None):
    """Sum (flops, weight-bytes) over every linear for an ``[m, D_in]``
    activation per layer — the scheme-aware roofline numerator."""
    totals = [0, 0]

    def one(path, lp: LinearParams):
        scheme = get_scheme(lp.scheme)
        n = scheme.stack_ndim(lp.data)
        stack = 1
        if n:
            lead = jax.tree.leaves(lp.data)[0].shape[:n]
            for s_ in lead:
                stack *= int(s_)
            data2 = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[n:])[0],
                                 lp.data)
        else:
            data2 = lp.data
        f, b = scheme.flops_bytes(data2, lp.policy, m)
        totals[0] += f * stack
        totals[1] += b * stack
        return lp

    map_linears(params, one, pol=pol)
    return totals[0], totals[1]
