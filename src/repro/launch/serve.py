"""Serving launcher: build a config (optionally spiking+Phi), load or init
params, and drive the continuous-batching engine over a synthetic request
stream, reporting throughput/latency/slot-utilisation.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo_1b [--smoke] \
        --requests 16 --slots 4 [--phi] [--ckpt-dir DIR] \
        [--host-devices 8 --mesh-model 4] \
        [--trace-out trace.jsonl --metrics-out metrics.prom --obs]

Observability (docs/observability.md): ``--trace-out`` streams the request
lifecycle + dispatch spans as deterministic JSONL, ``--metrics-out`` writes
the merged metric registries (Prometheus text for ``.prom``/``.txt``, JSON
otherwise), ``--obs`` adds wall-time sampling (engine tick histogram,
span durations) on top.

The model runs at its published widths unless ``--smoke`` picks the reduced
same-family config (the size for CPU runs).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time


def _early_host_devices() -> None:
    """--host-devices N forces N virtual CPU devices; the XLA flag must be
    set before jax initialises its backends, i.e. before the import below."""
    for i, a in enumerate(sys.argv):
        if a == "--host-devices" and i + 1 < len(sys.argv):
            n = sys.argv[i + 1]
        elif a.startswith("--host-devices="):
            n = a.split("=", 1)[1]
        else:
            continue
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = ((flags + " ") if flags else "") + \
            f"--xla_force_host_platform_device_count={int(n)}"
        return


_early_host_devices()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.configs import get_config, phi_variant  # noqa: E402
from repro.distributed.sharding import init_params  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import model  # noqa: E402
from repro import obs  # noqa: E402
from repro.serve.engine import Engine, Request  # noqa: E402
from repro.utils import enable_compile_cache, log  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="serve the reduced same-family config (CPU runs); "
                         "default: the published widths")
    ap.add_argument("--phi", action="store_true")
    ap.add_argument("--phi-impl", default=None, choices=dispatch.IMPLS,
                    help="force one Phi kernel lowering; default: the "
                         "execution policy picks per call (fused here)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-context", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--paged", action="store_true",
                    help="serve from a paged KV cache (fixed-size pages + "
                         "page-table indirection; bitwise-identical decode)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--pages", type=int, default=None,
                    help="physical page-pool size; undersizing it forces "
                         "scheduler preemption (default: worst case, "
                         "slots * max_context / page_size)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the request/dispatch span trace as JSONL "
                         "(deterministic: monotonic seq/tick counters, no "
                         "wall-clock unless --obs)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the merged metric registries at exit — "
                         "Prometheus text exposition for .prom/.txt paths, "
                         "JSON snapshot otherwise")
    ap.add_argument("--obs", action="store_true",
                    help="enable wall-time observation: engine tick "
                         "histogram (p50/p99 logged from the same code path "
                         "the bench reads) and wall_ms fields on trace spans")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N virtual CPU devices for off-TPU mesh "
                         "testing (consumed before jax init)")
    ap.add_argument("--mesh-model", type=int, default=0,
                    help="model-parallel ways: builds a (data, model) mesh "
                         "over the visible devices and serves the phi GEMMs "
                         "through shard_map (0 = single device)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.phi:
        cfg = phi_variant(cfg, timesteps=2, q=16)
        if args.phi_impl:
            cfg = cfg.with_(phi=dataclasses.replace(cfg.phi, impl=args.phi_impl))
    # Phi state comes from the checkpoint or from calibration below, which
    # builds it: a fresh run initialises the weights only.
    init_cfg = cfg if args.ckpt_dir else cfg.with_(phi=None)
    params = init_params(model.lm_specs(init_cfg), jax.random.PRNGKey(0))
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        # missing_ok: pre-PR-4 phi checkpoints lack the usage histograms —
        # zero-fill them (policy reads all-zero as "no histogram").
        step, tree, extra = mgr.restore_latest({"params": params},
                                               missing_ok=("usage",))
        if step is not None:
            params = tree["params"]
            # A persisted --phi-impl override survives restart (the live CLI
            # flag, if given, wins inside apply_checkpoint_extra).
            cfg = dispatch.apply_checkpoint_extra(cfg, extra)
            # Re-register the calibration usage histograms riding in the
            # params tree so the policy's fused_prefetch usage gate works
            # without a fresh calibration pass.
            n_usage = dispatch.register_usage_from_params(params)
            log.info("restored params from step %d (%d phi usage histograms)",
                     step, n_usage)
    if args.phi:
        batch = model.dummy_batch(cfg, 2, 16, with_labels=False)
        cfg, params, maxd = model.calibrate_lm_phi_budgeted(cfg, params, batch)
        log.info("phi calibrated (max L2 density %.3f)", maxd)

    mesh = None
    if args.mesh_model > 1:
        nd = len(jax.devices())
        if nd % args.mesh_model:
            raise SystemExit(f"--mesh-model {args.mesh_model} does not divide "
                             f"{nd} devices (try --host-devices)")
        mesh = make_mesh((nd // args.mesh_model, args.mesh_model),
                         ("data", "model"))
        log.info("serving on %s", dict(mesh.shape))
    tracer = None
    if args.trace_out:
        # Installed process-wide so the dispatch policy's per-call spans
        # interleave with the engine's lifecycle spans in one stream.
        tracer = obs.Tracer(obs.JsonlSink(args.trace_out),
                            wall_time=args.obs)
        obs.set_tracer(tracer)
    eng = Engine(cfg, params, batch_slots=args.slots,
                 max_context=args.max_context, mesh=mesh,
                 paged=args.paged, page_size=args.page_size,
                 num_pages=args.pages, tracer=tracer, wall_time=args.obs)
    rng = np.random.default_rng(0)
    t_sub = time.time()
    for rid in range(args.requests):
        plen = int(rng.integers(4, args.max_context // 4))
        eng.submit(Request(rid=rid, tokens=rng.integers(3, cfg.vocab, plen),
                           max_new_tokens=args.max_new,
                           temperature=args.temperature))
    results = eng.run()
    dt = time.time() - t_sub
    log.info("served %d/%d requests | %d tokens in %.1fs = %.1f tok/s | "
             "%d ticks, slot util %.0f%%",
             len(results), args.requests, eng.decoded_tokens, dt,
             eng.decoded_tokens / max(dt, 1e-9), eng.ticks,
             100.0 * eng.decoded_tokens / max(eng.ticks * args.slots, 1))
    rep = eng.serve_report()
    log.info("scheduler decisions: %s", rep["scheduler_decisions"])
    cache = rep["cache"]
    if rep["paged"]:
        log.info("paged cache: %d pages x %d tokens, hwm %d pages "
                 "(%d bytes) vs contiguous %d bytes",
                 cache["num_pages"], cache["page_size"],
                 cache["hwm_pages"], cache["page_hwm_bytes"],
                 cache["contig_cache_bytes"])
    if args.obs:
        # Same histogram + percentile code path the serve bench reports
        # from (obs.metrics.Histogram.percentile) — one latency story.
        hist = eng.metrics.get("tick_ms")
        log.info("tick p50 %.3fms p99 %.3fms (%d ticks)",
                 hist.percentile(50), hist.percentile(99), hist.count())
    registries = [eng.metrics]
    if args.phi:
        registries.append(dispatch.get_policy().metrics)
        jax.effects_barrier()   # flush callback-fed counters before export
    if args.metrics_out:
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
        log.info("trace written to %s (%d spans)", args.trace_out,
                 sum(tracer.kind_counts.values()))


if __name__ == "__main__":
    main()
