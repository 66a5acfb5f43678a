"""Execution-policy dispatch: context gates, overrides, persistence, and the
phi-LM decode parity acceptance test.

The policy (``kernels/dispatch.py``) must pick ``fused`` on the plain
single-device path, fall back to ``coo`` inside pjit/shard_map SPMD regions
and under autodiff/vmap tracing, honor explicit overrides (demoting unsafe
ones in SPMD), and persist a config override across a checkpoint
save/restore round-trip. The acceptance test asserts phi-LM decode logits
are BIT-identical between a forced-``coo`` run and a policy-dispatched
(``fused``) run.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.patterns import PhiConfig, calibrate, pattern_weight_products
from repro.kernels import dispatch, ops


@pytest.fixture(autouse=True)
def _fresh_policy():
    dispatch.get_policy().reset()
    yield
    dispatch.get_policy().reset()


@pytest.fixture(scope="module")
def small_phi():
    rng = np.random.default_rng(0)
    protos = (rng.random((6, 64)) < 0.25).astype(np.float32)
    a = np.abs(protos[rng.integers(0, 6, 96)]
               - (rng.random((96, 64)) < 0.05)).astype(np.float32)
    w = rng.standard_normal((64, 128)).astype(np.float32)
    pats = calibrate(a, PhiConfig(k=16, q=16, iters=6))
    pwp = pattern_weight_products(jnp.asarray(pats), jnp.asarray(w))
    return jnp.asarray(a), jnp.asarray(w), jnp.asarray(pats), pwp


# ------------------------------------------------------------------- gates ---
def test_single_device_default_is_fused(small_phi):
    a, w, pats, pwp = small_phi
    pol = dispatch.get_policy()
    out = pol.matmul(a, w, pats, pwp, site="t.single")
    ref = ops.phi_matmul(a, w, pats, pwp, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)
    dec = pol.decisions()
    assert any(s == "t.single" and i == "fused" and "single_device" in r
               for (s, i, r) in dec)
    # fused decisions carry autotuned blocks
    d = pol.resolve(site="t.single2", m=96, k_dim=64, n=128, t=4, q=16)
    assert d.impl == "fused" and d.blocks is not None
    # runtime telemetry: the l2_nnz audit counters were streamed out
    jax.effects_barrier()
    rep = pol.report()
    budgets = {b.site: b for b in rep["packer_budgets"]}
    assert "t.single" in budgets and budgets["t.single"].l2_nnz_total > 0
    assert budgets["t.single"].nnz_budget_required > 0


def test_shard_map_body_resolves_local_fused(small_phi):
    """Inside a shard_map body the operands are per-shard local arrays, so
    the policy re-gates on the local shape and keeps the fused lowering
    (``spmd_local_*`` reason) instead of blanket-demoting to coo."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    a, w, pats, pwp = small_phi
    pol = dispatch.get_policy()
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    f = shard_map(lambda a_, w_: dispatch.phi_matmul(a_, w_, pats, pwp,
                                                     site="t.shmap"),
                  mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                  check_vma=False)
    out = f(a, w)
    ref = ops.phi_matmul(a, w, pats, pwp, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)
    dec = pol.decisions()
    assert any(s == "t.shmap" and i in ("fused", "fused_stream", "fused_prefetch")
               and r.startswith("spmd_local_") for (s, i, r) in dec), dec
    last = pol.last_decision("t.shmap")
    assert last is not None and last.shards == 1, last


def test_shard_map_body_honors_pallas_override(small_phi):
    """An explicit Pallas-impl override is honored inside the shard_map body
    (local operands — the old blanket demotion no longer applies there)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    a, w, pats, pwp = small_phi
    pol = dispatch.get_policy()
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    f = shard_map(lambda a_, w_: dispatch.phi_matmul(
                      a_, w_, pats, pwp, site="t.shmap_ov",
                      config_override="fused_stream"),
                  mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                  check_vma=False)
    out = f(a, w)
    ref = ops.phi_matmul(a, w, pats, pwp, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)
    assert ("t.shmap_ov", "fused_stream", "config_override") in pol.decisions()


def test_mesh_context_and_explicit_region_resolve_coo(small_phi):
    from jax.sharding import Mesh
    from repro.distributed import sharding as shd

    a, w, pats, pwp = small_phi
    pol = dispatch.get_policy()
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with shd.use_rules(shd.SERVE_RULES, mesh):
        dispatch.phi_matmul(a, w, pats, pwp, site="t.mesh")
    with dispatch.spmd_region():
        assert dispatch.in_spmd_region()
        dispatch.phi_matmul(a, w, pats, pwp, site="t.region")
    assert not dispatch.in_spmd_region()
    dec = pol.decisions()
    assert ("t.mesh", "coo", "spmd_region") in dec
    assert ("t.region", "coo", "spmd_region") in dec


def test_axis_env_probe_pinned_jax_contract():
    """Version-pins the private-jax surface the SPMD gate stands on:
    ``jax._src.core.get_axis_env`` must exist and report an empty axis env
    outside any shard_map/pmap. If a jax upgrade moves the symbol, THIS
    test fails in CI instead of the gate failing at user trace time."""
    from jax._src.core import get_axis_env

    assert hasattr(get_axis_env(), "axis_sizes")
    assert not get_axis_env().axis_sizes
    assert dispatch._axis_env_nonempty() is False
    assert dispatch._axis_env_shards() == 1
    assert not dispatch.in_spmd_region()


def test_in_spmd_region_inside_shard_map_body():
    """Inside a shard_map body the axis env is in scope: the policy sees an
    SPMD region and counts the cooperating devices."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    seen = {}

    def body(x):
        seen["spmd"] = dispatch.in_spmd_region()
        seen["shards"] = dispatch._axis_env_shards()
        return x

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P()))(
        jnp.ones((8, 128)))
    assert seen == {"spmd": True, "shards": 1}


@pytest.mark.parametrize("transform", ["vmap", "jvp", "grad"])
def test_under_transform_detects_vmap_and_autodiff(transform):
    """vmap, JVP and grad (linearize) tracers are transforms the Pallas
    kernels have no rule for — also when staged under ``jit``."""
    seen = []

    def f(x):
        seen.append(dispatch._under_transform(x))
        return (x * 2.0).sum()

    x = jnp.ones((4, 3))
    if transform == "vmap":
        jax.vmap(f)(x)
        jax.jit(jax.vmap(f))(x)
    elif transform == "jvp":
        jax.jvp(f, (x,), (x,))
        jax.jit(lambda v: jax.jvp(f, (v,), (v,)))(x)
    else:
        jax.grad(f)(x)
        jax.jit(jax.grad(f))(x)
    assert seen == [True, True]


def test_under_transform_false_when_staged_or_eager():
    """Plain ``jit`` / ``scan`` / ``shard_map`` staging and eager arrays are
    not transforms: the fused lowerings stay eligible."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    seen = []

    def f(x):
        seen.append(dispatch._under_transform(x))
        return x

    x = jnp.ones((4, 3))
    f(x)
    jax.jit(f)(x)
    jax.lax.scan(lambda c, r: (c, f(r)), 0.0, x)
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(x)
    assert seen == [False, False, False, False]


def test_autodiff_and_vmap_resolve_coo(small_phi):
    a, w, pats, pwp = small_phi
    pol = dispatch.get_policy()
    g = jax.grad(lambda w_: dispatch.phi_matmul(a, w_, pats, pwp,
                                                site="t.grad").sum())(w)
    assert np.isfinite(np.asarray(g)).all() and float(jnp.abs(g).sum()) > 0
    vout = jax.vmap(lambda a_: dispatch.phi_matmul(a_, w, pats, pwp,
                                                   site="t.vmap"))(
        a.reshape(4, 24, 64))
    ref = ops.phi_matmul(a, w, pats, pwp, impl="ref")
    np.testing.assert_allclose(np.asarray(vout).reshape(96, 128),
                               np.asarray(ref), rtol=1e-4, atol=1e-3)
    dec = pol.decisions()
    assert ("t.grad", "coo", "autodiff_or_vmap") in dec
    assert ("t.vmap", "coo", "autodiff_or_vmap") in dec


def test_vmap_over_patterns_only_resolves_coo(small_phi):
    """A vmap that batches ONLY the pattern bank (per-layer pattern sets)
    must be sniffed too: a/w/pwp are plain arrays, so only the ``patterns``
    operand carries the batching tracer — dispatching to a Pallas impl there
    would fail to compile (no batching rule)."""
    a, w, pats, pwp = small_phi
    pol = dispatch.get_policy()
    vout = jax.vmap(lambda p_: dispatch.phi_matmul(a, w, p_, pwp,
                                                   site="t.vmap_pats"))(
        jnp.stack([pats, pats]))
    ref = ops.phi_matmul(a, w, pats, pwp, impl="ref")
    for i in range(2):
        np.testing.assert_allclose(np.asarray(vout[i]), np.asarray(ref),
                                   rtol=1e-4, atol=1e-3)
    assert ("t.vmap_pats", "coo", "autodiff_or_vmap") in pol.decisions()


def test_vmem_shape_gate_resolves_fused_stream():
    """The VMEM gate is three-way: shapes whose all-resident blocks bust
    the budget stream their K axis (fused dataflow kept) instead of
    falling off to the pure-XLA "coo" path."""
    pol = dispatch.get_policy()
    # K so large that even the smallest all-resident config busts VMEM —
    # the shape class PR 2 demoted to "coo".
    assert ops.fused_shape_viable(256, 1 << 16, 512, 1 << 12, 128) == \
        "fused_stream"
    d = pol.resolve(site="t.vmem", m=256, k_dim=1 << 16, n=512,
                    t=1 << 12, q=128)
    assert d.impl == "fused_stream" and d.reason.startswith(
        "vmem_gate_k_stream")
    # blocks carry the K-group size: (block_m, block_n, group_t)
    assert d.blocks is not None and len(d.blocks) == 3
    bm, bn, gt = d.blocks
    assert (1 << 12) % gt == 0 and gt >= 1


def test_vmem_shape_gate_coo_only_when_streaming_busts_too():
    pol = dispatch.get_policy()
    # Pathological pattern count: even a single-partition group's PWP
    # stripe busts VMEM, so no fused lowering fits.
    assert ops.fused_shape_viable(256, 256, 512, 16, 1 << 16) == "coo"
    d = pol.resolve(site="t.vmem_coo", m=256, k_dim=256, n=512, t=16,
                    q=1 << 16)
    assert d.impl == "coo" and d.reason == "fused_vmem_gate"


# --------------------------------------------------------------- overrides ---
def test_overrides_honored_and_demoted_in_spmd(small_phi):
    a, w, pats, pwp = small_phi
    pol = dispatch.get_policy()
    out = pol.matmul(a, w, pats, pwp, site="t.ov", override="pallas",
                     nnz_budget=0.5)
    ref = ops.phi_matmul(a, w, pats, pwp, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)
    assert ("t.ov", "pallas", "call_override") in pol.decisions()
    # config-level override (PhiConfig.impl threaded by the model layer)
    d = pol.resolve(site="t.cfg", m=96, k_dim=64, n=128, t=4, q=16,
                    config_override="coo")
    assert d.impl == "coo" and d.reason == "config_override"
    # per-call beats config
    d = pol.resolve(site="t.prec", m=96, k_dim=64, n=128, t=4, q=16,
                    override="ref", config_override="coo")
    assert d.impl == "ref" and d.reason == "call_override"
    # policy-level override (PHI_IMPL env)
    env_pol = dispatch.PhiExecutionPolicy(override="ref")
    d = env_pol.resolve(site="t.pol", m=96, k_dim=64, n=128, t=4, q=16)
    assert d.impl == "ref" and d.reason == "policy_override"
    # Pallas-based override is demoted inside an SPMD region
    with dispatch.spmd_region():
        d = pol.resolve(site="t.demote", m=96, k_dim=64, n=128, t=4, q=16,
                        override="fused")
        assert d.impl == "coo" and "demotes_fused" in d.reason
        # "ref" is pure XLA: safe to honor even in SPMD
        d = pol.resolve(site="t.refok", m=96, k_dim=64, n=128, t=4, q=16,
                        override="ref")
        assert d.impl == "ref"
    # ... and under a differentiated trace (e.g. --phi-impl fused training)
    with dispatch.autodiff_region():
        d = pol.resolve(site="t.addem", m=96, k_dim=64, n=128, t=4, q=16,
                        override="fused")
        assert d.impl == "coo" and d.reason == "autodiff_demotes_fused"
    # ... a "fused" override where only streaming fits is streamed, not
    # demoted to coo (closest executable lowering to the operator's intent)
    d = pol.resolve(site="t.vmdem", m=256, k_dim=1 << 16, n=512, t=1 << 12,
                    q=128, override="fused")
    assert d.impl == "fused_stream" and d.reason == "vmem_gate_streams_fused"
    assert d.blocks is not None and len(d.blocks) == 3
    # ... a "fused_stream" override is honored wherever it can execute
    d = pol.resolve(site="t.sov", m=96, k_dim=64, n=128, t=4, q=16,
                    override="fused_stream")
    assert d.impl == "fused_stream" and d.reason == "call_override"
    # ... and where even streaming busts VMEM, both fused overrides demote
    d = pol.resolve(site="t.vmdem2", m=256, k_dim=256, n=512, t=16,
                    q=1 << 16, override="fused")
    assert d.impl == "coo" and d.reason == "vmem_gate_demotes_fused"
    d = pol.resolve(site="t.vmdem3", m=256, k_dim=256, n=512, t=16,
                    q=1 << 16, override="fused_stream")
    assert d.impl == "coo" and d.reason == "vmem_gate_demotes_fused_stream"
    with pytest.raises(ValueError, match="unknown Phi impl"):
        pol.resolve(site="t.bad", m=96, k_dim=64, n=128, t=4, q=16,
                    override="nope")
    with pytest.raises(ValueError, match="unknown Phi impl"):
        dispatch.PhiExecutionPolicy(override="nope")


def test_phi_config_validates_impl():
    with pytest.raises(AssertionError):
        PhiConfig(impl="bogus")
    assert PhiConfig(impl="fused").impl == "fused"


# ------------------------------------------------- checkpoint round-trip ----
def test_impl_override_survives_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config, phi_variant

    cfg = phi_variant(get_config("olmo_1b", smoke=True), timesteps=2, q=16)
    cfg = cfg.with_(phi=dataclasses.replace(cfg.phi, impl="coo"))
    extra = dispatch.checkpoint_extra(cfg)
    assert extra == {"phi_impl": "coo"}

    mgr = CheckpointManager(str(tmp_path), async_save=False)
    tree = {"x": jnp.arange(4.0)}
    mgr.save(3, tree, {"loader": {"step": 3}, **extra})
    assert mgr.latest_extra()["phi_impl"] == "coo"

    # restore onto a config with no live override -> checkpointed one applies
    fresh = phi_variant(get_config("olmo_1b", smoke=True), timesteps=2, q=16)
    restored = dispatch.apply_checkpoint_extra(fresh, mgr.latest_extra())
    assert restored.phi.impl == "coo"
    # a live override wins over the checkpointed one
    live = fresh.with_(phi=dataclasses.replace(fresh.phi, impl="pallas"))
    assert dispatch.apply_checkpoint_extra(
        live, mgr.latest_extra()).phi.impl == "pallas"
    # non-phi configs pass through untouched
    plain = get_config("olmo_1b", smoke=True)
    assert dispatch.apply_checkpoint_extra(plain, mgr.latest_extra()) is plain


# ------------------------------------------------------- phi_apply (SNN) ----
def _mlp_setup():
    from repro.snn import models
    cfg = models.SNNConfig(kind="mlp", widths=(32,), input_size=8,
                           timesteps=2, phi=PhiConfig(k=16, q=8, iters=4))
    params = models.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((12, 8, 8, 3)), jnp.float32)
    phi, _ = models.calibrate_model(params, cfg, x)
    return models, cfg, params, phi, x


def test_phi_apply_routes_through_policy():
    models, cfg, params, phi, x = _mlp_setup()
    pol = dispatch.get_policy()
    out_pol = models.phi_apply(params, cfg, phi, x)
    out_coo = models.phi_apply(params, cfg, phi, x, impl="coo")
    np.testing.assert_allclose(np.asarray(out_pol), np.asarray(out_coo),
                               rtol=1e-4, atol=1e-4)
    dec = pol.decisions()
    assert any(s.startswith("snn.") and i == "fused" for (s, i, _) in dec)
    assert any(s.startswith("snn.") and i == "coo" and r == "call_override"
               for (s, i, r) in dec)


def test_phi_apply_k_mismatch_raises_instead_of_truncating():
    models, cfg, params, phi, x = _mlp_setup()
    # PhiState calibrated for a different model: drop one K-tile of 'head'
    bad = models.PhiState(
        patterns={"head": phi.patterns["head"][:-1]},
        pwp={"head": phi.pwp["head"][:-1]},
    )
    with pytest.raises(ValueError, match="calibrated for K="):
        models.phi_apply(params, cfg, bad, x)


# --------------------------------------------------- spiking-Phi training ---
def test_phi_training_paths_dispatch_coo():
    """Spiking-Phi training end-to-end: the autodiff region keeps every
    spiking GEMM on the differentiable XLA lowering (scan-over-layers hides
    JVP tracers, so this exercises the explicit ``autodiff_region`` gate),
    and the Phi calibration state stays frozen (int8 patterns would
    otherwise make ``jax.grad`` fail)."""
    from repro.configs import get_config, phi_variant
    from repro.launch.train import train_loop
    from repro.train import optimizer as opt

    pol = dispatch.get_policy()
    cfg = phi_variant(get_config("olmo_1b", smoke=True), timesteps=2, q=16)
    ocfg = opt.OptConfig(lr=1e-3, warmup_steps=1, decay_steps=2)
    params, losses = train_loop(cfg, ocfg, steps=2, global_batch=2, seq=16,
                                log_every=0)
    assert np.isfinite(losses).all()
    assert any(s.startswith("lm.") and i == "coo" and r == "autodiff_or_vmap"
               for (s, i, r) in pol.decisions())
    # calibration state came through the step untouched (frozen)
    from repro.models import model
    _, phi_state = model.split_phi_state(params)
    assert phi_state, "phi state missing from trained params"


def test_phi_train_step_under_mesh_dispatches_coo():
    from jax.sharding import Mesh
    from repro.configs import get_config, phi_variant
    from repro.distributed import sharding as shd
    from repro.models import model
    from repro.train import optimizer as opt
    from repro.train import step as step_lib

    pol = dispatch.get_policy()
    cfg = phi_variant(get_config("olmo_1b", smoke=True), timesteps=2, q=16)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    ocfg = opt.OptConfig(lr=1e-3, warmup_steps=1, decay_steps=2)
    bundle, p_specs, o_specs, _ = step_lib.make_train_step(cfg, ocfg, mesh)
    params = shd.init_params(p_specs, jax.random.PRNGKey(0))
    batch = model.dummy_batch(cfg, 2, 16, with_labels=True)
    opt_state = opt.init(model.split_phi_state(params)[0], ocfg)
    new_params, _, loss = bundle.fn(params, opt_state, batch)
    assert np.isfinite(float(loss))
    assert jax.tree.structure(new_params) == jax.tree.structure(params)
    # inside the pjit body every phi GEMM resolved an SPMD-safe lowering
    lm_impls = {i for (s, i, _) in pol.decisions() if s.startswith("lm.")}
    assert lm_impls == {"coo"}, pol.decisions()


# ----------------------------------------- acceptance: phi-LM decode parity --
def test_phi_lm_decode_bit_identical_coo_vs_policy():
    """Acceptance: phi-LM decode logits are BIT-identical between a
    forced-``coo`` run and a policy-dispatched run (which resolves
    ``fused`` on this single-device path — asserted via telemetry).

    Bitwise equality across two genuinely different lowerings is only
    meaningful when the arithmetic itself is exact, so the params are
    snapped to a dyadic grid (multiples of 2^-10): every Phi partial
    product (one-hot PWP selections, ±1 residual × weight) is then exactly
    representable and every summation order yields the same floats — the
    paper's losslessness claim, transported to float hardware. The fused
    kernel's separate L1/L2 accumulators (matching the unfused out1+out2
    association) keep this exact for any dispatch mode.
    """
    from repro.configs import get_config, phi_variant
    from repro.distributed.sharding import init_params
    from repro.models import model

    cfg = phi_variant(get_config("olmo_1b", smoke=True), timesteps=2, q=16)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(1))
    params = jax.tree.map(lambda x: jnp.round(x * 1024) / 1024, params)
    batch = model.dummy_batch(cfg, 2, 8, with_labels=False,
                              key=jax.random.PRNGKey(2))
    cfg, params, _ = model.calibrate_lm_phi_budgeted(cfg, params, batch)

    def decode_run(c, steps=2):
        logits, caches = model.prefill(c, params, batch)
        caches = model.extend_caches(c, caches, 8 + steps + 1)
        outs = [np.asarray(logits)]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for t in range(steps):
            pos = jnp.full((2,), 8 + t, jnp.int32)
            logits, caches = model.decode_step(c, params, tok, pos, caches)
            outs.append(np.asarray(logits))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return outs

    pol = dispatch.get_policy()
    out_policy = decode_run(cfg)
    out_coo = decode_run(cfg.with_(phi=dataclasses.replace(cfg.phi,
                                                           impl="coo")))
    for got, want in zip(out_policy, out_coo):
        assert np.array_equal(got, want), \
            f"decode logits differ by {np.abs(got - want).max()}"

    dec = pol.decisions()
    # policy run executed the LM GEMMs via fused ...
    fused_sites = {s for (s, i, _) in dec
                   if i == "fused" and s.startswith("lm.")}
    assert fused_sites, dec
    # ... and the forced run via the coo config override
    assert any(i == "coo" and r == "config_override" and s.startswith("lm.")
               for (s, i, r) in dec), dec
    # runtime telemetry captured the packer budget of the served GEMMs
    jax.effects_barrier()
    budgets = {b.site for b in pol.report()["packer_budgets"]}
    assert budgets & fused_sites


# -------------------------------- acceptance: large-K streaming parity ------
def test_large_k_stream_bit_identical_vs_coo(monkeypatch):
    """Acceptance: a large-K shape that PR 2's policy demoted to ``coo``
    (K=16384, N=512 — ``fused_shape_viable`` was False) now resolves to
    ``fused_stream``, its output is BIT-identical to forced-``coo`` under
    dyadic-grid weights (same exactness argument as the decode-parity
    test: every Phi partial product is exactly representable, so summation
    order is irrelevant), and its modelled HBM bytes are ≤ the 3-kernel
    pipeline's for the same shape."""
    monkeypatch.setenv("PHI_CHUNK_ROWS", "64")  # keep the coo run small
    from repro.core.patterns import PhiConfig, calibrate, \
        pattern_weight_products

    rng = np.random.default_rng(7)
    M, K, N, q = 48, 16384, 512, 8
    T = K // 16
    a = jnp.asarray((rng.random((M, K)) < 0.08), jnp.float32)
    w = jnp.asarray(np.round(rng.standard_normal((K, N)) * 1024) / 1024,
                    jnp.float32)                 # dyadic 2^-10 grid
    pats = jnp.asarray(calibrate(np.asarray(a), PhiConfig(k=16, q=q,
                                                          iters=3)))
    pwp = pattern_weight_products(pats, w)       # sums of dyadics: exact

    assert ops.fused_shape_viable(M, K, N, T, q) == "fused_stream"
    pol = dispatch.get_policy()
    out_pol = pol.matmul(a, w, pats, pwp, site="t.largeK")
    out_coo = ops.phi_matmul(a, w, pats, pwp, impl="coo")
    assert np.array_equal(np.asarray(out_pol), np.asarray(out_coo)), \
        f"differ by {np.abs(np.asarray(out_pol) - np.asarray(out_coo)).max()}"
    dec = pol.decisions()
    assert any(s == "t.largeK" and i == "fused_stream"
               and r.startswith("vmem_gate_k_stream") for (s, i, r) in dec)
    # runtime telemetry carries the K-group size alongside the nnz counters
    jax.effects_barrier()
    with pol._lock:
        site = dict(pol._sites)["t.largeK"]
    assert site["group_t"] >= 1 and site["l2_nnz_total"] > 0
    # modelled HBM bytes: streaming keeps the fused round-trip savings
    from repro.core.perfmodel import GemmShape, phi_kernel_traffic
    tr = phi_kernel_traffic(GemmShape(M, K, N), k=16, q=q)
    assert tr["fused_stream"].total <= tr["three_kernel"].total


# -------------------------------------------- paged decode attention site --
# olmo_1b served on one v5e: 16 slots, 16 heads (16 KV heads) of 128,
# pages of 16 over a 2048 context, bf16 pools.
OLMO_PAGED = dict(batch=16, heads=16, kv_heads=16, head_dim=128,
                  page_size=16, logical_pages=128, dtype=jnp.bfloat16)


def test_paged_decode_resolves_kernel_on_tpu_at_olmo_shapes(monkeypatch):
    from repro.kernels import paged_attention

    monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
    d = dispatch.get_policy().resolve_paged_decode(
        site="lm.attn_decode_paged", **OLMO_PAGED)
    assert (d.impl, d.reason) == ("paged_kernel", "tpu_paged_kernel")
    assert d.shape == (16 * 16, 128, 2048, 16, 16)
    assert d.blocks == (paged_attention.block_pages(16, 128),)
    assert dispatch.get_policy().decisions() == {
        ("lm.attn_decode_paged", "paged_kernel", "tpu_paged_kernel"): 1}


def test_paged_decode_keeps_gather_on_cpu_and_in_spmd(monkeypatch):
    pol = dispatch.get_policy()
    d = pol.resolve_paged_decode(site="lm.attn_decode_paged", **OLMO_PAGED)
    assert (d.impl, d.reason, d.blocks) == ("gather", "cpu_keeps_gather",
                                            None)
    monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
    with dispatch.spmd_region():
        d = pol.resolve_paged_decode(site="lm.attn_decode_paged",
                                     **OLMO_PAGED)
    assert (d.impl, d.reason) == ("gather", "spmd_region_keeps_gather")


@pytest.mark.parametrize("change,reason", [
    (dict(dtype=jnp.float32), "pool_not_bf16_keeps_gather"),
    (dict(page_size=8, logical_pages=256), "page_not_bf16_tiles_keeps_gather"),
    (dict(head_dim=64), "head_dim_not_lanes_keeps_gather"),
], ids=["f32_pool", "page_8", "head_dim_64"])
def test_paged_decode_unsupported_shape_keeps_gather_on_tpu(
        monkeypatch, change, reason):
    monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
    d = dispatch.get_policy().resolve_paged_decode(
        site="lm.attn_decode_paged", **{**OLMO_PAGED, **change})
    assert (d.impl, d.reason) == ("gather", reason)


def test_paged_engine_records_its_decode_lowering():
    """A paged engine's decode step asks the policy at
    ``lm.attn_decode_paged``; on the CPU the answer is ``gather``."""
    from repro.configs import get_config
    from repro.distributed.sharding import init_params
    from repro.models import model
    from repro.serve.engine import Engine, Request

    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(0))
    eng = Engine(cfg, params, batch_slots=2, max_context=32, paged=True,
                 page_size=8)
    eng.submit(Request(rid=0, tokens=[5, 6, 7], max_new_tokens=2,
                       temperature=0.0))
    eng.run()
    d = dispatch.get_policy().last_decision("lm.attn_decode_paged")
    assert (d.impl, d.reason) == ("gather", "cpu_keeps_gather")
    assert d.shape == (2 * cfg.n_heads, cfg.d_model // cfg.n_heads, 32,
                       cfg.n_kv_heads, 8)
