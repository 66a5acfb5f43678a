"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, shape + finiteness asserts, prefill/decode consistency, Phi-LM mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, phi_variant
from repro.distributed.sharding import init_params
from repro.models import model


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch, rng):
    cfg = get_config(arch, smoke=True)
    params = init_params(model.lm_specs(cfg), rng)
    batch = model.dummy_batch(cfg, 2, 16, with_labels=True)
    logits = model.train_logits(cfg, params, batch)
    S = 16
    assert logits.shape == (2, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    loss, grads = jax.value_and_grad(lambda p: model.train_loss(cfg, p, batch))(params)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_full_forward(arch, rng):
    cfg = get_config(arch, smoke=True)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(1))
    B, S, extra = 2, 16, 3
    offs = cfg.frontend_positions if cfg.frontend == "patches" else 0
    batch = model.dummy_batch(cfg, B, S + extra + offs, with_labels=False,
                              key=jax.random.PRNGKey(2))
    full_logits = np.asarray(model.train_logits(cfg, params, batch))
    pre = {k: (v[:, :S] if k in ("tokens", "frame_embeds") else v) for k, v in batch.items()}
    lg, caches = model.prefill(cfg, params, pre)
    np.testing.assert_allclose(np.asarray(lg), full_logits[:, S - 1 + offs],
                               rtol=2e-2, atol=2e-2)
    caches = model.extend_caches(cfg, caches, S + extra + offs)
    for t in range(extra):
        pos = jnp.full((B,), S + t + offs, jnp.int32)
        tok = batch["tokens"][:, S + t] if "tokens" in batch else jnp.zeros((B,), jnp.int32)
        emb = batch["frame_embeds"][:, S + t] if cfg.frontend == "frames" else None
        lg, caches = model.decode_step(cfg, params, tok, pos, caches, embeds=emb)
        np.testing.assert_allclose(np.asarray(lg), full_logits[:, S + t + offs],
                                   rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("arch", ["olmo_1b", "mamba2_2p7b", "qwen1p5_4b"])
def test_phi_spiking_mode_lossless(arch):
    """Phi decomposition inside the spiking LM == spiking-dense, exactly the
    paper's losslessness claim transported to the LM integration."""
    cfg = phi_variant(get_config(arch, smoke=True), timesteps=2, q=16)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(1))
    batch = model.dummy_batch(cfg, 2, 8, with_labels=False, key=jax.random.PRNGKey(2))
    cfg, params, _ = model.calibrate_lm_phi_budgeted(cfg, params, batch)
    lg_phi = model.train_logits(cfg, params, batch)

    from repro.snn.lif import LIFConfig, lif_update
    lif = LIFConfig()

    def dense_mm(x, p, name):
        xf = x.astype(jnp.float32)

        def step(v, _):
            s, v2 = lif_update(v, xf, lif)
            return v2, s

        _, spikes = jax.lax.scan(step, jnp.zeros_like(xf), None, length=cfg.phi.timesteps)
        out = jnp.einsum("t...k,kn->t...n", spikes, p[name].astype(jnp.float32))
        return (out.mean(0) * 2.0).astype(x.dtype)

    x, _ = model._forward(cfg, params, batch, matmul=dense_mm)
    lg_dense = model._logits(cfg, params, x)
    np.testing.assert_allclose(np.asarray(lg_phi), np.asarray(lg_dense),
                               rtol=1e-3, atol=1e-3)


def test_full_configs_match_assignment():
    """The full configs carry the exact assigned hyper-parameters."""
    expect = {
        "mamba2_2p7b": dict(n_layers=64, d_model=2560, vocab=50280, ssm_state=128),
        "olmo_1b": dict(n_layers=16, d_model=2048, n_heads=16, n_kv_heads=16,
                        d_ff=8192, vocab=50304, norm="nonparam_ln"),
        "h2o_danube3_4b": dict(n_layers=24, d_model=3840, n_heads=32, n_kv_heads=8,
                               d_ff=10240, vocab=32000, attn_type="swa"),
        "yi_34b": dict(n_layers=60, d_model=7168, n_heads=56, n_kv_heads=8,
                       d_ff=20480, vocab=64000),
        "qwen1p5_4b": dict(n_layers=40, d_model=2560, n_heads=20, n_kv_heads=20,
                           d_ff=6912, vocab=151936, qkv_bias=True),
        "pixtral_12b": dict(n_layers=40, d_model=5120, n_heads=32, n_kv_heads=8,
                            d_ff=14336, vocab=131072, frontend="patches"),
        "llama4_maverick": dict(n_layers=48, d_model=5120, n_heads=40, n_kv_heads=8,
                                d_ff=8192, vocab=202048, n_experts=128, top_k=1),
        "arctic_480b": dict(n_layers=35, d_model=7168, n_heads=56, n_kv_heads=8,
                            d_ff=4864, vocab=32000, n_experts=128, top_k=2),
        "zamba2_1p2b": dict(n_layers=38, d_model=2048, n_heads=32, n_kv_heads=32,
                            d_ff=8192, vocab=32000, ssm_state=64),
        "musicgen_large": dict(n_layers=48, d_model=2048, n_heads=32, n_kv_heads=32,
                               d_ff=8192, vocab=2048, frontend="frames"),
    }
    for arch, kv in expect.items():
        cfg = get_config(arch)
        for k, v in kv.items():
            assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_param_counts_in_headline_band():
    """Logical parameter counts should be near the archs' headline sizes."""
    bands = {
        "mamba2_2p7b": (2.2e9, 3.2e9),
        "olmo_1b": (0.9e9, 1.5e9),
        "h2o_danube3_4b": (3.0e9, 5.0e9),
        "yi_34b": (30e9, 38e9),
        "qwen1p5_4b": (3.0e9, 5.5e9),
        "pixtral_12b": (10e9, 14e9),
        "llama4_maverick": (330e9, 480e9),
        "arctic_480b": (420e9, 520e9),
        "zamba2_1p2b": (0.9e9, 1.6e9),
        "musicgen_large": (2.0e9, 3.3e9),
    }
    for arch, (lo, hi) in bands.items():
        tot, act = get_config(arch).param_count()
        assert lo <= tot <= hi, (arch, tot / 1e9)
        assert act <= tot
