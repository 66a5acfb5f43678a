"""Training driver: mesh + data + checkpoint/restore + watchdog in one loop.

CPU-runnable end-to-end with smoke configs:
  PYTHONPATH=src python -m repro.launch.train --arch olmo_1b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On resume (same or different mesh) the loop restores params/opt state AND
the data cursor, continuing bit-exactly (elastic restart path).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, phi_variant
from repro.data.pipeline import DataConfig, LoaderState, Prefetcher, ShardedLoader
from repro.distributed import sharding as shd
from repro.distributed.watchdog import StepWatchdog
from repro.kernels import dispatch
from repro import obs
from repro.models import model
from repro.train import optimizer as opt
from repro.train import step as step_lib
from repro.utils import StepTimer, enable_compile_cache, log


def train_loop(cfg, ocfg, *, steps: int, global_batch: int, seq: int,
               ckpt_dir: str | None = None, ckpt_every: int = 50,
               mesh=None, seed: int = 0, log_every: int = 10,
               metrics: obs.MetricsRegistry | None = None):
    rules = shd.TRAIN_RULES
    # Observability (repro.obs): step counters/histograms land in the
    # caller's registry; the process tracer (if installed via --trace-out)
    # gets one "train_step" span per step with the monotonic step counter.
    metrics = metrics if metrics is not None else obs.MetricsRegistry("train")
    m_steps = metrics.counter("steps", "optimizer steps completed")
    m_loss = metrics.gauge("last_loss", "most recent training loss")
    m_step_ms = metrics.histogram("step_ms", "wall time per training step")
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=global_batch, seed=seed)
    loader = ShardedLoader(dcfg)
    mgr = CheckpointManager(ckpt_dir, keep=3) if ckpt_dir else None
    if mgr is not None:
        # A persisted Phi impl override must be re-applied before the step
        # functions close over cfg (a live cfg.phi.impl wins over it).
        cfg = dispatch.apply_checkpoint_extra(cfg, mgr.latest_extra())

    if mesh is not None:
        bundle, p_specs, o_specs, _ = step_lib.make_train_step(cfg, ocfg, mesh, rules)
        p_sh = shd.specs_to_shardings(p_specs, mesh, rules)
        o_sh = shd.specs_to_shardings(o_specs, mesh, rules)
        step_fn = jax.jit(bundle.fn, in_shardings=(p_sh, o_sh, None),
                          donate_argnums=(0, 1))
    else:
        p_specs = model.lm_specs(cfg)
        p_sh = o_sh = None

        def step_fn_(params, opt_state, batch):
            # Phi calibration state is frozen: grads/optimizer see only the
            # trainable half (int8 patterns are non-differentiable).
            trainable, phi_state = model.split_phi_state(params)
            with dispatch.autodiff_region():
                loss, grads = jax.value_and_grad(
                    lambda tp: model.train_loss(
                        cfg, model.merge_phi_state(tp, phi_state), batch))(trainable)
            new_t, new_opt = opt.apply_updates(trainable, grads, opt_state, ocfg)
            return model.merge_phi_state(new_t, phi_state), new_opt, loss

        step_fn = jax.jit(step_fn_, donate_argnums=(0, 1))

    params = shd.init_params(p_specs, jax.random.PRNGKey(seed))
    if cfg.spiking and cfg.phi is not None:
        # Spiking-Phi training: fill the zero-initialised Phi state from real
        # spike statistics before the first step. Every spiking GEMM then
        # routes through the kernels.dispatch execution policy (the autodiff
        # gate keeps the backward pass on the differentiable XLA lowering).
        calib = model.dummy_batch(cfg, min(global_batch, 2), seq,
                                  with_labels=False)
        params, _ = model.calibrate_lm_phi(cfg, params, calib)
        log.info("phi calibrated; impl override: %s", cfg.phi.impl or "policy")
    opt_state = opt.init(model.split_phi_state(params)[0], ocfg)
    start_step = 0
    if mgr is not None:
        got = mgr.restore_latest({"params": params, "opt": opt_state},
                                 {"params": p_sh, "opt": o_sh} if p_sh else None,
                                 missing_ok=("usage",))
        if got[0] is not None:
            start_step, tree, extra = got
            params, opt_state = tree["params"], tree["opt"]
            loader.state = LoaderState.from_dict(extra.get("loader", {"step": 0}))
            log.info("restored checkpoint @ step %d", start_step)

    watchdog = StepWatchdog()
    losses = []
    it = iter(Prefetcher(iter(loader)))
    for step in range(start_step, steps):
        batch = next(it)
        with StepTimer() as t:
            params, opt_state, loss = step_fn(
                params, opt_state,
                {k: jnp.asarray(v) for k, v in batch.items()})
            loss = float(loss)
        losses.append(loss)
        m_steps.inc()
        m_loss.set(loss)
        step_s = t.history[-1] if t.history else 0.0
        m_step_ms.observe(step_s * 1e3)
        tracer = obs.get_tracer()
        if tracer is not None:
            tracer.emit("train_step", step=step + 1, loss=loss)
        verdict = watchdog.record(step_s)
        # NB: save the CONSUMED cursor (step+1), not loader.state — the
        # prefetcher runs ahead of consumption (caught by
        # tests/test_fault_tolerance.py).
        consumed = {"loader": {"step": step + 1}, **dispatch.checkpoint_extra(cfg)}
        if verdict == "escalate" and mgr is not None:
            mgr.save(step + 1, {"params": params, "opt": opt_state}, consumed)
        if log_every and (step + 1) % log_every == 0:
            log.info("step %d loss %.4f (median step %.3fs)", step + 1,
                     float(np.mean(losses[-log_every:])), watchdog.median)
        if mgr is not None and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_state}, consumed)
    if mgr is not None:
        mgr.save(steps, {"params": params, "opt": opt_state},
                 {"loader": {"step": steps}, **dispatch.checkpoint_extra(cfg)})
        mgr.wait()
    if cfg.spiking and cfg.phi is not None:
        dispatch.get_policy().log_report(prefix="train")
    return params, losses


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--phi", action="store_true",
                    help="train the spiking+Phi variant of --arch")
    ap.add_argument("--phi-impl", default=None, choices=dispatch.IMPLS,
                    help="force one Phi kernel lowering; default: the "
                         "execution policy picks per call")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write train_step + dispatch spans as JSONL")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the step metrics at exit (Prometheus text "
                         "for .prom/.txt paths, JSON otherwise)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.phi:
        import dataclasses
        cfg = phi_variant(cfg, timesteps=2, q=16)
        if args.phi_impl:
            cfg = cfg.with_(phi=dataclasses.replace(cfg.phi, impl=args.phi_impl))
    ocfg = opt.OptConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                         decay_steps=args.steps)
    tracer = None
    if args.trace_out:
        tracer = obs.Tracer(obs.JsonlSink(args.trace_out))
        obs.set_tracer(tracer)
    metrics = obs.MetricsRegistry("train")
    t0 = time.time()
    _, losses = train_loop(cfg, ocfg, steps=args.steps, global_batch=args.batch,
                           seq=args.seq, ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every, metrics=metrics)
    log.info("done: loss %.4f -> %.4f in %.1fs",
             losses[0], float(np.mean(losses[-10:])), time.time() - t0)
    if args.metrics_out:
        registries = [metrics]
        if args.phi:
            jax.effects_barrier()   # flush callback-fed dispatch counters
            registries.append(dispatch.get_policy().metrics)
        if args.metrics_out.endswith((".prom", ".txt")):
            body = obs.prometheus_many(registries)
        else:
            import json
            body = json.dumps(obs.snapshot_many(registries),
                              sort_keys=True, indent=2)
        with open(args.metrics_out, "w") as f:
            f.write(body)
        log.info("metrics written to %s", args.metrics_out)
    if tracer is not None:
        obs.set_tracer(None)
        tracer.close()


if __name__ == "__main__":
    main()
