"""Distribution tests on 8 placeholder devices (subprocess so the XLA flag
doesn't leak into other tests' single-device world)."""
import os
import subprocess
import sys
import textwrap


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(script: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_moe_ep_matches_dense_oracle():
    run_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.config import ModelConfig
        from repro.models import moe
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh

        cfg = ModelConfig(name='t', family='moe', n_layers=1, d_model=32,
                          n_heads=4, n_kv_heads=4, d_ff=64, vocab=64,
                          n_experts=8, top_k=2, capacity_factor=8.0,
                          compute_dtype=jnp.float32)
        specs = moe.moe_specs(cfg)
        params = shd.init_params(specs, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)
        want = moe.moe_dense(cfg, params, x)

        mesh = make_mesh((2, 4), ('data', 'model'))
        with shd.use_rules(shd.TRAIN_RULES, mesh), mesh:
            got = jax.jit(lambda p, x: moe.moe_ep(cfg, p, x))(params, x)
        # capacity_factor 8 => nothing drops; EP must equal the oracle
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
        print('EP == dense OK')
    """)


def test_sharded_train_step_runs_and_matches_single_device():
    run_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import model
        from repro.train import optimizer as opt, step as step_lib

        cfg = get_config('olmo_1b', smoke=True).with_(tp=2)
        ocfg = opt.OptConfig(lr=1e-3, warmup_steps=0, decay_steps=10)
        mesh = make_mesh((4, 2), ('data', 'model'))
        bundle, p_specs, o_specs, _ = step_lib.make_train_step(cfg, ocfg, mesh)
        params = shd.init_params(p_specs, jax.random.PRNGKey(0))
        opt_state = opt.init(params, ocfg)
        batch = model.dummy_batch(cfg, 8, 32, with_labels=True)

        # single-device reference
        def ref_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: model.train_loss(cfg, p, batch))(params)
            p2, o2 = opt.apply_updates(params, grads, opt_state, ocfg)
            return p2, o2, loss
        rp, ro, rloss = jax.jit(ref_step)(params, opt_state, batch)

        p_sh = shd.specs_to_shardings(p_specs, mesh, shd.TRAIN_RULES)
        o_sh = shd.specs_to_shardings(o_specs, mesh, shd.TRAIN_RULES)
        with mesh:
            sp, so, sloss = jax.jit(bundle.fn, in_shardings=(p_sh, o_sh, None))(
                params, opt_state, batch)
        assert abs(float(rloss) - float(sloss)) < 1e-3, (float(rloss), float(sloss))
        d = max(float(jnp.abs(a - b).max())
                for a, b in zip(jax.tree.leaves(rp), jax.tree.leaves(sp)))
        assert d < 5e-3, d
        print('sharded step == single-device OK')
    """)


def test_grad_compression_error_feedback():
    run_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.train.grad_compress import pod_compressed_grads

        mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
        params = {'w': jnp.ones((4, 8)) * 0.5}
        batch = {'x': jax.random.normal(jax.random.PRNGKey(0), (8, 4))}

        def loss_fn(p, b):
            return jnp.mean((b['x'] @ p['w']) ** 2)

        ef = jax.tree.map(lambda p: jnp.zeros_like(p), params)
        with mesh:
            loss, grads, new_ef = jax.jit(
                lambda p, b, e: pod_compressed_grads(loss_fn, p, b, e, mesh)
            )(params, batch, ef)
        want = jax.grad(loss_fn)(params, batch)['w']
        got = grads['w']
        # int8 EF compression: close but not exact; error goes into new_ef
        rel = float(jnp.abs(got - want).max() / (jnp.abs(want).max() + 1e-9))
        assert rel < 0.05, rel
        assert float(jnp.abs(new_ef['w']).max()) > 0.0
        print('grad compression OK, rel err', rel)
    """)


def test_checkpoint_elastic_reshard():
    run_devices("""
        import numpy as np, jax, jax.numpy as jnp, tempfile, os
        from repro.checkpoint import CheckpointManager
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        tree = {'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d, keep=2, async_save=False)

        mesh1 = make_mesh((4, 2), ('data', 'model'))
        sh1 = {'w': NamedSharding(mesh1, P('data', 'model'))}
        t1 = jax.tree.map(lambda x, s: jax.device_put(x, s), tree, sh1)
        mgr.save(10, t1, {'loader': {'step': 7}})

        # elastic restart on a DIFFERENT mesh shape
        mesh2 = make_mesh((2, 4), ('data', 'model'))
        sh2 = {'w': NamedSharding(mesh2, P('model', 'data'))}
        step, t2, extra = mgr.restore_latest(tree, sh2)
        assert step == 10 and extra['loader']['step'] == 7
        np.testing.assert_array_equal(np.asarray(t2['w']), np.asarray(tree['w']))
        assert t2['w'].sharding == sh2['w']
        print('elastic reshard OK')
    """)


def test_phi_lm_sharded_decode_bit_identical_and_fused():
    """Mesh-aware dispatch acceptance: on an 8-device (2 data × 4 model)
    mesh, phi-LM decode logits under the policy (which resolves fused
    lowerings INSIDE the shard_map bodies — asserted via decisions) are
    BIT-identical to forced-coo under the dyadic 2^-10 weight grid, for
    both the column-parallel w1 site and the row-parallel psum w2 site."""
    run_devices("""
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config, phi_variant
        from repro.distributed import sharding as shd
        from repro.kernels import dispatch
        from repro.launch.mesh import make_mesh
        from repro.models import model

        cfg = phi_variant(get_config('olmo_1b', smoke=True), timesteps=2, q=16)
        params = shd.init_params(model.lm_specs(cfg), jax.random.PRNGKey(1))
        params = jax.tree.map(lambda x: jnp.round(x * 1024) / 1024, params)
        batch = model.dummy_batch(cfg, 2, 8, with_labels=False,
                                  key=jax.random.PRNGKey(2))
        cfg, params, _ = model.calibrate_lm_phi_budgeted(cfg, params, batch)

        mesh = make_mesh((2, 4), ('data', 'model'))

        def decode_run(c, steps=2):
            with shd.use_rules(shd.SERVE_RULES, mesh):
                logits, caches = model.prefill(c, params, batch)
                caches = model.extend_caches(c, caches, 8 + steps + 1)
                outs = [np.asarray(logits)]
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                for t in range(steps):
                    pos = jnp.full((2,), 8 + t, jnp.int32)
                    logits, caches = model.decode_step(c, params, tok, pos,
                                                       caches)
                    outs.append(np.asarray(logits))
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return outs

        pol = dispatch.get_policy()
        out_pol = decode_run(cfg)
        out_coo = decode_run(cfg.with_(phi=dataclasses.replace(cfg.phi,
                                                               impl='coo')))
        for got, want in zip(out_pol, out_coo):
            assert np.array_equal(got, want), \\
                f'sharded decode logits differ by {np.abs(got - want).max()}'

        dec = pol.decisions()
        fused_spmd = {s for (s, i, r) in dec
                      if i in ('fused', 'fused_stream', 'fused_prefetch')
                      and r.startswith('spmd_local_')}
        # column-parallel (w1: N on 'model') AND row-parallel psum
        # (w2: K on 'model') both kept the fused dataflow in-body
        assert 'lm.w1.spmd' in fused_spmd, dec
        assert 'lm.w2.spmd' in fused_spmd, dec
        # forced-coo run: the config override was honored inside the body
        assert any(s == 'lm.w2.spmd' and i == 'coo' and r == 'config_override'
                   for (s, i, r) in dec), dec
        # per-shard telemetry: the decision carries the mesh extent
        last = pol.last_decision('lm.w1.spmd')
        assert last is not None and last.shards == 8, last
        print('sharded phi decode parity OK:', sorted(fused_spmd))
    """)


def test_multipod_mesh_constructs():
    run_devices("""
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh(multi_pod=True)
        assert dict(m.shape) == {'pod': 2, 'data': 16, 'model': 16}
        m2 = make_production_mesh()
        assert dict(m2.shape) == {'data': 16, 'model': 16}
        print('mesh OK')
    """, n=512)
