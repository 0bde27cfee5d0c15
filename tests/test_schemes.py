"""The LinearScheme registry + PolicyTree API.

1. Every registered scheme's apply/merge is BIT-identical to the
   pre-refactor dict-branching reference (kept verbatim below) across
   bits x group_size.
2. PolicyTree glob resolution: precedence (last match wins), the
   lm_head exemption, CLI parsing.
3. merge_tree is idempotent and matches the pre-refactor merge walker on
   the uniform-policy path.
4. A per-layer mixed policy (INT4 body + INT8 attn/wo + fp lm_head)
   round-trips init -> train step -> merge -> serve on gemma3-1b reduced.
5. The partition fails loudly when a trainable scheme selects no leaves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core import lora as lora_lib
from repro.core import qalora as qalora_lib
from repro.core import quant as quant_lib
from repro.core import schemes as S
from repro.core.schemes import FP, LinearParams, PolicyTree, QuantPolicy
from repro.models import LM


# ---------------------------------------------------------------------------
# pre-refactor reference (the old models/common.py mode-switch, verbatim)
# ---------------------------------------------------------------------------


def _ref_linear_apply(p, x, pol):
    if "w" in p and "ad" not in p:
        return x @ p["w"].astype(x.dtype)
    if "w" in p:
        return lora_lib.lora_forward(x, p["w"].astype(x.dtype), p["ad"], pol.s)
    if "nf4" in p:
        return lora_lib.qlora_forward(x, p["nf4"], p["ad"], pol.s)
    if "ad" not in p:
        return x @ quant_lib.dequantize(p["q"], x.dtype)
    return qalora_lib.qalora_forward(x, p["q"], p["ad"], pol.s,
                                     compute_dtype=x.dtype)


def _ref_merge_linear(p, pol):
    if "q" in p:
        return {"q": qalora_lib.merge(p["q"], p["ad"], pol.s)}
    if "nf4" in p:
        return {"w": lora_lib.qlora_merge_fp(p["nf4"], p["ad"], pol.s)}
    if "ad" in p:
        return {"w": lora_lib.lora_merge(p["w"], p["ad"], pol.s)}
    return p


def _ref_merge_model(params, pol):
    def walk(p):
        if isinstance(p, dict) and ("ad" in p or "q" in p or "nf4" in p):
            return _ref_merge_linear(p, pol)
        if isinstance(p, dict):
            return {k: walk(v) for k, v in p.items()}
        return p
    return walk(params)


def _bump_adapters(params, eps=0.01):
    """Give adapters non-trivial weights (a freshly-init B==0 adapter makes
    merge trivially exact)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: (x + eps if any(
            getattr(k, "key", None) == "ad" for k in path) else x), params)


# ---------------------------------------------------------------------------
# 1. scheme-by-scheme bit-equivalence with the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["fp", "lora", "qlora", "qalora", "intq"])
@pytest.mark.parametrize("bits", [2, 3, 4, 8])
@pytest.mark.parametrize("group", [32, 64])
def test_scheme_apply_merge_bit_identical_to_reference(mode, bits, group):
    if mode in ("fp", "lora", "qlora") and (bits, group) != (2, 32):
        pytest.skip("bits/group only affect the quantized bases")
    d_in, d_out = 128, 48
    pol = QuantPolicy(mode=mode, bits=bits, group_size=group, rank=4,
                      s=1.7, dtype=jnp.float32)
    p = S.linear_init(jax.random.PRNGKey(3), d_in, d_out, pol)
    p = LinearParams(data=_bump_adapters(p.data), scheme=p.scheme,
                     policy=p.policy, exempt=p.exempt)
    x = jax.random.normal(jax.random.PRNGKey(7), (5, d_in))

    y_new = S.linear_apply(p, x)
    y_ref = _ref_linear_apply(p.data, x, pol)
    np.testing.assert_array_equal(np.asarray(y_new), np.asarray(y_ref))

    if mode == "intq":
        # the old reference could not merge a bare quantized linear at all
        # (KeyError on 'ad') — covered by test_merge_idempotent_single
        return
    m_new = S.merge_linear(p)
    m_ref = _ref_merge_linear(p.data, pol)
    for k in m_ref:
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            m_new[k], m_ref[k])
    # merged apply matches too
    np.testing.assert_array_equal(
        np.asarray(S.linear_apply(m_new, x)),
        np.asarray(_ref_linear_apply(m_ref, x, pol)))


def test_merge_idempotent_single():
    pol = QuantPolicy(mode="qalora", bits=4, group_size=32, rank=4)
    p = S.linear_init(jax.random.PRNGKey(0), 64, 32, pol)
    m1 = S.merge_linear(p)
    m2 = S.merge_linear(m1)  # old merge_linear crashed here (KeyError 'ad')
    assert m1.scheme == m2.scheme == "intq"
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), m1.data, m2.data)


def test_legacy_dict_params_still_work():
    """Old untagged checkpoints are adopted transparently (the only
    key-sniffing left lives inside core/schemes.py)."""
    pol = QuantPolicy(mode="qalora", bits=4, group_size=16, rank=4, s=2.0)
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    qt = quant_lib.quantize(w, 4, 16)
    ad = qalora_lib.init_qalora(jax.random.PRNGKey(1), 4, 4, 32)
    legacy = {"q": qt, "ad": ad}
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 64))
    np.testing.assert_array_equal(
        np.asarray(S.linear_apply(legacy, x, pol)),
        np.asarray(_ref_linear_apply(legacy, x, pol)))
    assert S.merge_linear(legacy, pol).scheme == "intq"


# ---------------------------------------------------------------------------
# 2. PolicyTree resolution
# ---------------------------------------------------------------------------


def test_policytree_last_match_wins():
    pt = PolicyTree.of({
        "*": QuantPolicy(mode="qalora", bits=4),
        "*/attn/wo": QuantPolicy(mode="qalora", bits=8),
        "blocks/attn/wo": QuantPolicy(mode="fp"),
    })
    assert pt.at("blocks", "attn", "wq").resolve().bits == 4
    assert pt.at("dec_blocks", "attn", "wo").resolve().bits == 8
    # the most recently declared matching rule wins
    assert pt.at("blocks", "attn", "wo").resolve().mode == "fp"


def test_policytree_lm_head_exemption():
    pt = PolicyTree.of({"*": QuantPolicy(mode="qalora", bits=4)})
    assert pt.at("blocks", "mlp", "up").resolve().mode == "qalora"
    # catch-all does NOT quantize the head...
    assert pt.at("lm_head").resolve().mode == "fp"
    # ...but an explicit rule does
    pt2 = PolicyTree.of({"*": QuantPolicy(mode="qalora", bits=4),
                         "lm_head": QuantPolicy(mode="qalora", bits=8)})
    assert pt2.at("lm_head").resolve().bits == 8
    # uniform QuantPolicy behaves the same through resolve_path
    up = QuantPolicy(mode="qalora", bits=4)
    assert S.resolve_path(up, "lm_head").mode == "fp"
    assert S.resolve_path(up, "blocks/mlp/up").mode == "qalora"


def test_policytree_unmatched_falls_back_to_fp():
    pt = PolicyTree.of({"blocks/*": QuantPolicy(mode="qalora", bits=4)})
    assert pt.at("enc_blocks", "mlp", "up").resolve().mode == "fp"


def test_policytree_head_pattern_alias():
    """The head param lives at params['head']; rules may spell it either
    'head' or 'lm_head' and both match."""
    pt = PolicyTree.of({"*": QuantPolicy(mode="qalora", bits=4),
                        "head": QuantPolicy(mode="qalora", bits=8)})
    assert pt.at("lm_head").resolve().bits == 8
    assert pt.at("head").resolve().bits == 8


def test_policytree_default_is_last_catch_all():
    """Field delegation (cfg.quant.bits) agrees with last-match-wins."""
    pt = PolicyTree(rules=(("*", QuantPolicy(mode="qalora", bits=4)),
                           ("*", QuantPolicy(mode="qalora", bits=8))))
    assert pt.at("blocks", "mlp", "up").resolve().bits == 8
    assert pt.bits == 8 and pt.default.bits == 8


def test_legacy_adapter_dicts_require_policy():
    """Merging/applying an untagged adapter dict without a policy raises
    (the adapter scale s is not recoverable from bare arrays)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    qt = quant_lib.quantize(w, 4, 16)
    ad = qalora_lib.init_qalora(jax.random.PRNGKey(1), 4, 4, 32)
    legacy = {"q": qt, "ad": ad}
    with pytest.raises(ValueError, match="QuantPolicy"):
        S.merge_linear(legacy)
    with pytest.raises(ValueError, match="QuantPolicy"):
        S.merge_tree({"blocks": {"wq": legacy}})
    # adapter-free legacy dicts need no policy
    assert S.merge_linear({"q": qt}).scheme == "intq"
    # and the structure-only partition walk never needs one
    from repro.optim import split_params
    tr, _ = split_params({"wq": legacy})
    assert tr["wq"]["ad"] is not None


def test_policytree_parse():
    base = QuantPolicy(mode="qalora", bits=4, group_size=32, rank=16)
    pt = PolicyTree.parse("*=int4:g64,*/attn/wo=int8,lm_head=fp,*/mlp/up=intq3:r8",
                          base=base)
    r = pt.at("blocks", "attn", "wo").resolve()
    assert (r.mode, r.bits, r.group_size) == ("qalora", 8, 32)
    r = pt.at("blocks", "mlp", "down").resolve()
    assert (r.mode, r.bits, r.group_size) == ("qalora", 4, 64)
    r = pt.at("blocks", "mlp", "up").resolve()
    assert (r.mode, r.bits, r.rank) == ("intq", 3, 8)
    assert pt.at("lm_head").resolve().mode == "fp"
    with pytest.raises(ValueError):
        PolicyTree.parse("*=int4,oops")
    with pytest.raises(ValueError):
        PolicyTree.parse("*=float99")


# ---------------------------------------------------------------------------
# 3. tree-level merge: uniform path matches pre-refactor, idempotent
# ---------------------------------------------------------------------------


def _tagged_to_dicts(tree):
    """View a tagged params tree as the old bare-dict layout."""
    return S.map_linears(tree, lambda path, lp: dict(lp.data))


def test_merge_tree_matches_prerefactor_and_is_idempotent():
    cfg = C.reduced("gemma3-1b")
    lm = LM(cfg)
    params = _bump_adapters(lm.init(jax.random.PRNGKey(0)))

    merged = S.merge_tree(params)
    ref = _ref_merge_model(_tagged_to_dicts(params), cfg.quant)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), _tagged_to_dicts(merged), ref)

    merged2 = S.merge_tree(merged)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
        _tagged_to_dicts(merged2), _tagged_to_dicts(merged))


# ---------------------------------------------------------------------------
# 4. per-layer mixed policy end-to-end (init -> train -> merge -> serve)
# ---------------------------------------------------------------------------


def test_mixed_policy_roundtrip_gemma():
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import generate_scan, generate_loop_reference, merge_model
    from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                             split_params, merge_params, count_params)

    base = C.reduced("gemma3-1b").quant.default
    pt = PolicyTree.parse("*=int4,*/attn/wo=int8,lm_head=fp", base=base)
    cfg = C.reduced("gemma3-1b", quant=pt)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))

    blk = params["blocks"]
    assert blk["attn"]["wo"].policy.bits == 8
    assert blk["attn"]["wq"].policy.bits == 4
    assert blk["mlp"]["up"].policy.bits == 4
    assert params["head"].scheme == "fp"

    # one adapter-only train step
    trainable, frozen = split_params(params)
    assert count_params(trainable) > 0
    opt = adamw_init(trainable)
    batch = {"tokens": jnp.ones((2, 32), jnp.int32),
             "labels": jnp.ones((2, 32), jnp.int32)}

    @jax.jit
    def step(tr, o):
        loss, g = jax.value_and_grad(
            lambda t: lm.loss(merge_params(t, frozen), batch)[0])(tr)
        tr, o, _ = adamw_update(AdamWConfig(lr=1e-2), g, o, tr)
        return tr, o, loss

    trainable, opt, loss = step(trainable, opt)
    assert np.isfinite(float(loss))
    tuned = merge_params(trainable, frozen)

    # merge stays INT-N per layer, then serve: merged == adapter decoding
    merged = merge_model(tuned)
    mb = merged["blocks"]
    assert mb["attn"]["wo"].scheme == "intq" and mb["attn"]["wo"]["q"].bits == 8
    assert mb["mlp"]["up"].scheme == "intq" and mb["mlp"]["up"]["q"].bits == 4
    assert merged["head"].scheme == "fp"

    prompts = np.random.default_rng(0).integers(4, cfg.vocab, (2, 5)).astype(np.int32)
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        g_scan, _ = generate_scan(lm, mesh, merged, prompts, 4, 9)
        g_loop, _ = generate_loop_reference(lm, merged, prompts, 4, 9)
    np.testing.assert_array_equal(g_scan, g_loop)

    cache = lm.init_cache(2, 9, dtype=jnp.float32)
    step_d = jax.jit(lm.decode_step)
    la, _ = step_d(tuned, cache, jnp.asarray(prompts[:, :1]))
    lme, _ = step_d(merged, cache, jnp.asarray(prompts[:, :1]))
    assert float(jnp.max(jnp.abs(la - lme))) < 5e-2


def test_convert_tree_mixed_policy():
    """fp pretrain -> per-layer conversion (LQ-LoRA-style mixed precision)."""
    cfg_fp = C.reduced("llama7b-proxy", n_layers=2, vocab=64).scaled(
        quant=QuantPolicy(mode="fp", dtype=jnp.float32))
    lm = LM(cfg_fp)
    params = lm.init(jax.random.PRNGKey(0))
    base = QuantPolicy(mode="qalora", bits=4, group_size=16, rank=4,
                       dtype=jnp.float32)
    pt = PolicyTree.parse("*=int4,*/attn/wo=int8,lm_head=fp", base=base)
    out = S.convert_tree(params, pt, jax.random.PRNGKey(1))
    blk = out["blocks"]
    assert blk["attn"]["wo"]["q"].bits == 8
    assert blk["attn"]["wq"]["q"].bits == 4
    assert out["head"].scheme == "fp"
    # adapters start as identity -> converted loss ~= fp loss
    lmq = LM(cfg_fp.scaled(quant=pt))
    batch = {"tokens": jnp.ones((2, 32), jnp.int32),
             "labels": jnp.ones((2, 32), jnp.int32)}
    l_fp, _ = jax.jit(lm.loss)(params, batch)
    l_q, _ = jax.jit(lmq.loss)(out, batch)
    assert abs(float(l_fp) - float(l_q)) < 0.5


# ---------------------------------------------------------------------------
# 5. loud partition failures + misc API
# ---------------------------------------------------------------------------


def test_partition_raises_on_empty_trainable_scheme():
    from repro.optim import split_params
    pol = QuantPolicy(mode="qalora", bits=4, group_size=16, rank=4)
    p = S.linear_init(jax.random.PRNGKey(0), 64, 32, pol)
    broken = {"blocks": {"wq": LinearParams(
        data={"q": p.data["q"], "adapter": p.data["ad"]},  # misnamed key
        scheme="qalora", policy=p.policy)}}
    with pytest.raises(ValueError, match="blocks/wq"):
        split_params(broken)


def test_partition_legacy_dicts_and_fp_trees():
    from repro.optim import split_params, count_params
    # legacy adapter dict still partitions (adopted in schemes.py)
    legacy = {"wq": {"q": jnp.ones((4, 4)), "ad": {"a": jnp.zeros((2, 1))}},
              "embed": jnp.ones((8, 4))}
    tr, fr = split_params(legacy)
    assert tr["wq"]["ad"]["a"] is not None and tr["embed"] is None
    # an all-fp tree has zero trainables and that is fine (not an error)
    cfg = C.reduced("gemma3-1b", quant=QuantPolicy(mode="fp",
                                                   dtype=jnp.float32))
    params = LM(cfg).init(jax.random.PRNGKey(0))
    tr, fr = split_params(params)
    assert count_params(tr) == 0 and count_params(fr) > 0


def test_registry_contents_and_custom_registration():
    assert set(S.registered_schemes()) >= {"fp", "lora", "qlora", "qalora", "intq"}
    with pytest.raises(KeyError):
        S.get_scheme("nope")

    @S.register_scheme("testonly_double")
    class DoubleScheme(S.LinearScheme):
        def init(self, key, d_in, d_out, pol):
            return {"w": jnp.ones((d_in, d_out), pol.dtype)}

        def apply(self, data, x, pol):
            return 2.0 * (x @ data["w"].astype(x.dtype))

        def merge(self, data, pol):
            return "fp", {"w": 2.0 * data["w"]}

        def stack_ndim(self, data):
            return data["w"].ndim - 2

    try:
        pol = QuantPolicy(mode="testonly_double")
        p = S.linear_init(jax.random.PRNGKey(0), 8, 4, pol)
        x = jnp.ones((2, 8))
        np.testing.assert_allclose(np.asarray(S.linear_apply(p, x)),
                                   np.asarray(S.linear_apply(S.merge_linear(p), x)),
                                   rtol=1e-6)
    finally:
        S._REGISTRY.pop("testonly_double", None)


def test_flops_bytes_accounting():
    pol4 = QuantPolicy(mode="qalora", bits=4, group_size=32, rank=4)
    p4 = S.linear_init(jax.random.PRNGKey(0), 128, 64, pol4)
    pfp = S.linear_init(jax.random.PRNGKey(0), 128, 64, FP)
    f4, b4 = S.get_scheme("qalora").flops_bytes(p4.data, pol4, m=1)
    ffp, bfp = S.get_scheme("fp").flops_bytes(pfp.data, FP, m=1)
    assert f4 >= ffp  # adapter adds flops
    assert b4 < bfp  # INT4 reads ~8x fewer weight bytes than f32
    tf, tb = S.tree_flops_bytes({"a": p4, "b": pfp}, m=2)
    assert tf == 2 * (f4 + ffp) // 1 and tb == b4 + bfp


def test_tagged_tree_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import save_pytree, load_pytree
    pol = QuantPolicy(mode="qalora", bits=4, group_size=16, rank=4)
    tree = {"wq": S.linear_init(jax.random.PRNGKey(0), 64, 32, pol)}
    host = jax.tree.map(np.asarray, tree)
    save_pytree(host, str(tmp_path / "ck"))
    out = load_pytree(str(tmp_path / "ck"), tree)
    assert out["wq"].scheme == "qalora" and out["wq"].policy.bits == 4
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), out, host)


def test_serve_driver_mixed_policy_cli():
    """--policy threads through the serve driver and --verify holds."""
    from repro.launch.serve import main
    main(["--arch", "gemma3-1b", "--reduced", "--requests", "2",
          "--prompt-len", "4", "--gen-len", "2", "--verify",
          "--policy", "*=int4,*/attn/wo=int8,lm_head=fp"])


# ---------------------------------------------------------------------------
# 6. the merged intq linear picks its path from the backend and row count
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("rows", [1, 16, 32, 4096])
def test_intq_route_by_backend_and_rows(monkeypatch, backend, rows):
    """On TPU, decode-shaped rows run the fused dequant-GEMV (a
    pallas_call; interpret mode here) and 4096-row prefill steps stay on
    ``x @ dequantize(q)``; off TPU nothing takes the kernel.  Both paths
    give the dequantize-then-dot result, and ``use_kernel`` is not read."""
    from repro.kernels import INTQ_GEMV_MAX_M, ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: backend == "tpu")
    d_in, d_out = 128, 256
    pol = QuantPolicy(mode="intq", bits=4, group_size=32, use_kernel=False)
    p = S.linear_init(jax.random.PRNGKey(1), d_in, d_out, pol)
    lead = (rows // 128, 128) if rows > 128 else (rows, 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (*lead, d_in))

    jaxpr = str(jax.make_jaxpr(lambda x: S.linear_apply(p, x))(x))
    want_kernel = backend == "tpu" and rows <= INTQ_GEMV_MAX_M
    assert ("pallas_call" in jaxpr) == want_kernel
    assert rows in (1, 16, 32) or not want_kernel  # prefill stays on XLA
    y = S.linear_apply(p, x)
    want = x @ quant_lib.dequantize(p["q"], x.dtype)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
