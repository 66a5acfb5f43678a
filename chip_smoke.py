"""Bring-up check on a TPU: serve olmo_1b at its published widths, plain and
spiking+Phi, through the serving engine, from seeded random weights.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded Phi serving on a (1, 4) mesh
                                       # against the one-device Phi run

One chip runs these phases:

  (a) device check: JAX must report a TPU; anything else exits non-zero
      before any result is printed.
  (b) plain: olmo_1b through ``Engine(paged=True)`` (4 slots, 512 context),
      8 seeded requests of 33-128 prompt tokens and 16 greedy new tokens;
      every request retires with 16 tokens and finite logits, and the
      tokens equal those of the dense (non-paged) engine.
  (c) spiking+Phi: ``phi_variant(timesteps=2, q=16)``, calibrated on a
      seeded batch, serves the same requests through the dispatch policy.
      Every prefill GEMM must resolve to a Pallas lowering. Each lowering
      (fused, fused_stream, fused_prefetch) is checked against ``coo`` at
      the olmo_1b GEMM shapes, and 2 requests (prefill + 4 decode steps)
      are served again with ``PhiConfig.impl="coo"`` forced.
  (d) phase times and peak device memory (one-off smoke timings, not
      benchmark numbers).
  (e) the last line: one JSON object naming the device.

The weights sit on a dyadic 2^-10 grid. Every Phi partial sum (a PWP row,
an L2 residual contraction, their accumulation) is then exact in f32, so
the Phi lowerings and the ``coo`` reference must agree bitwise: the bound
on max |Δlogit| between them is 0.

The phase functions run at ``smoke=True`` on any backend (the CPU
rehearsal in tests/test_chip_smoke.py); only ``main()`` checks the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "olmo_1b"
SEED = 0
SLOTS = 4
MAX_CONTEXT = 512
N_REQUESTS = 8
MAX_NEW = 16
PROMPT_LEN = (33, 128)          # two prefill buckets: 64 and 128 tokens
PHI_TIMESTEPS, PHI_Q = 2, 16
CALIB_BATCH = (2, 64)           # seeded calibration tokens
COO_REQUESTS, COO_DECODE_STEPS = 2, 4
COO_LOGIT_BOUND = 0.0           # exact under dyadic weights (see above)
PALLAS_IMPLS = ("fused", "fused_stream", "fused_prefetch")
KERNEL_SHAPES = ((2048, 2048), (2048, 8192), (8192, 2048))   # (K, N)


class CheckFailed(RuntimeError):
    """A smoke check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def say(*parts) -> None:
    print(*parts, flush=True)


# ------------------------------------------------------------------ setup ---
def dyadic_params(cfg, seed: int = SEED):
    """Seeded random weights of ``cfg`` (no Phi state), rounded to the
    2^-10 grid."""
    import jax
    import jax.numpy as jnp
    from repro.distributed.sharding import init_params
    from repro.models import model

    params = init_params(model.lm_specs(cfg.with_(phi=None)),
                         jax.random.PRNGKey(seed))
    return jax.tree.map(lambda x: jnp.round(x * 1024) / 1024, params)


def make_prompts(vocab: int, n: int = N_REQUESTS, seed: int = SEED):
    """``n`` seeded prompts with 33-128 tokens each."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, n)
    return [rng.integers(3, vocab, int(n_tok)).astype(np.int32)
            for n_tok in lens]


def make_engine(cfg, params, *, paged: bool, mesh=None):
    from repro.serve.engine import Engine

    # eos_id=-1: no token ends a request early, each one yields MAX_NEW.
    return Engine(cfg, params, batch_slots=SLOTS, max_context=MAX_CONTEXT,
                  paged=paged, eos_id=-1, record_logits=True, mesh=mesh)


def warm_up(eng, prompts) -> float:
    """Compile the engine's entry points: one 2-token request per prefill
    bucket the prompts use. Returns the seconds it took."""
    from repro.serve.engine import Request, bucket_len

    t0 = time.perf_counter()
    seen = set()
    for p in prompts:
        b = bucket_len(len(p), MAX_CONTEXT)
        if b not in seen:
            seen.add(b)
            eng.submit(Request(rid=-1 - len(seen), tokens=p,
                               max_new_tokens=2))
    eng.run()
    eng.results.clear()
    eng.logit_trace.clear()
    return time.perf_counter() - t0


def serve(eng, prompts, max_new: int = MAX_NEW):
    """Serve ``prompts`` greedily; returns ({rid: tokens}, {rid: logits
    (steps, vocab)}, seconds)."""
    import numpy as np
    from repro.serve.engine import Request

    t0 = time.perf_counter()
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, tokens=p, max_new_tokens=max_new))
    results = eng.run()
    secs = time.perf_counter() - t0
    tokens = {r.rid: list(r.tokens) for r in results if r.rid >= 0}
    logits = {rid: np.stack(rows) for rid, rows in eng.logit_trace.items()
              if rid >= 0}
    return tokens, logits, secs


def max_abs_diff(a: dict, b: dict) -> float:
    import numpy as np

    return max(float(np.abs(a[r] - b[r]).max()) for r in a)


def check_served(tokens: dict, logits: dict, n: int, max_new: int,
                 what: str) -> None:
    import numpy as np

    check(sorted(tokens) == list(range(n)),
          f"{what}: {len(tokens)}/{n} requests retired")
    for rid, toks in tokens.items():
        check(len(toks) == max_new,
              f"{what}: request {rid} retired with {len(toks)} tokens, "
              f"not {max_new}")
        check(bool(np.isfinite(logits[rid]).all()),
              f"{what}: request {rid} has non-finite logits")


# ---------------------------------------------------------------- phases ---
def phase_plain(smoke: bool = False, params=None) -> dict:
    """(b) Plain model: paged engine vs dense engine on the same requests.
    Returns the phase record; ``params`` (the dyadic weights) is reused by
    the Phi phase."""
    from repro.configs import get_config

    cfg = get_config(ARCH, smoke=smoke)
    times = {}
    t0 = time.perf_counter()
    if params is None:
        params = dyadic_params(cfg)
    import jax
    jax.block_until_ready(params)
    times["init"] = time.perf_counter() - t0
    prompts = make_prompts(cfg.vocab)

    paged = make_engine(cfg, params, paged=True)
    times["compile"] = warm_up(paged, prompts)
    tokens, logits, times["serve"] = serve(paged, prompts)
    check_served(tokens, logits, len(prompts), MAX_NEW, "plain paged")

    dense = make_engine(cfg, params, paged=False)
    times["compile_dense"] = warm_up(dense, prompts)
    d_tokens, d_logits, times["serve_dense"] = serve(dense, prompts)
    check_served(d_tokens, d_logits, len(prompts), MAX_NEW, "plain dense")
    dlogit = max_abs_diff(logits, d_logits)
    say(f"plain: {len(tokens)} requests x {MAX_NEW} tokens retired; "
        f"paged vs dense max |dlogit| = {dlogit!r}")
    check(tokens == d_tokens, "plain: paged and dense greedy tokens differ")
    return {"params": params, "times": times, "tokens": tokens,
            "dlogit_dense": dlogit}


def phi_config(smoke: bool = False):
    from repro.configs import get_config, phi_variant

    return phi_variant(get_config(ARCH, smoke=smoke),
                       timesteps=PHI_TIMESTEPS, q=PHI_Q)


def calibrate(cfg, params, seed: int = SEED):
    """Calibrate the Phi state on a seeded token batch and size the L2
    budget with the serve launcher's helper. Returns (cfg, params)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model

    rng = np.random.default_rng(seed + 1)
    batch = {"tokens": jnp.asarray(
        rng.integers(3, cfg.vocab, CALIB_BATCH), jnp.int32)}
    cfg, params, maxd = model.calibrate_lm_phi_budgeted(cfg, params, batch)
    say(f"phi: calibrated, max L2 density {maxd!r}, "
        f"nnz_budget {cfg.phi.nnz_budget!r}")
    return cfg, params


def dispatch_records(run) -> tuple[object, list[dict]]:
    """Run ``run()`` with a tracer installed; returns (its result, the
    dispatch decisions it traced)."""
    from repro import obs

    sink = obs.ListSink()
    obs.set_tracer(obs.Tracer(sink))
    try:
        out = run()
    finally:
        obs.set_tracer(None)
    return out, [r for r in sink.records if r["kind"] == "dispatch"]


def check_kernels(smoke: bool = False) -> None:
    """Each Pallas lowering against ``coo`` at the olmo_1b GEMM shapes
    (q=16, k=16), at a prefill and a decode M, with dyadic weights: the
    outputs must be bitwise equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.patterns import pattern_weight_products
    from repro.kernels import ops

    shapes = ((64, 128), (128, 64)) if smoke else KERNEL_SHAPES
    ms = (2 * 64, 2 * SLOTS) if smoke else (2 * 128, 2 * SLOTS)
    rng = np.random.default_rng(SEED + 2)
    for K, N in shapes:
        T = K // 16
        w = jnp.asarray(np.round(rng.standard_normal((K, N)) * 0.02 * 1024)
                        / 1024, jnp.float32)
        pats = jnp.asarray(rng.random((T, PHI_Q, 16)) < 0.25, jnp.int8)
        pwp = pattern_weight_products(pats, w)
        for M in ms:
            a = jnp.asarray(rng.random((M, K)) < 0.2, jnp.float32)
            want = np.asarray(ops.phi_matmul(a, w, pats, pwp, impl="coo",
                                             nnz_budget=0.9))
            for impl in PALLAS_IMPLS:
                kw = {"p_active": PHI_Q // 2} if impl == "fused_prefetch" \
                    else {}
                got = np.asarray(jax.block_until_ready(ops.phi_matmul(
                    a, w, pats, pwp, impl=impl, **kw)))
                d = float(np.abs(got - want).max())
                check(d == 0.0, f"kernel {impl} M={M} K={K} N={N}: "
                                f"max |out - coo| = {d!r}")
    say(f"kernels: fused, fused_stream, fused_prefetch == coo bitwise at "
        f"(K, N) in {list(shapes)}, M in {list(ms)}")


def phase_phi(smoke: bool = False, params=None) -> dict:
    """(c) Spiking+Phi: calibrate, serve the requests through the policy,
    check the prefill decisions, the kernels and the forced-coo rerun."""
    import jax
    from repro.kernels import dispatch

    cfg = phi_config(smoke)
    times = {}
    if params is None:
        params = dyadic_params(cfg)
    t0 = time.perf_counter()
    cfg, params = calibrate(cfg, params)
    jax.block_until_ready(params)
    times["calibration"] = time.perf_counter() - t0
    prompts = make_prompts(cfg.vocab)
    eng = make_engine(cfg, params, paged=True)
    times["compile"], decisions = dispatch_records(
        lambda: warm_up(eng, prompts))
    dispatch.get_policy().reset(keep_usage=True)
    (tokens, logits, times["serve"]), _ = dispatch_records(
        lambda: serve(eng, prompts))
    check_served(tokens, logits, len(prompts), MAX_NEW, "phi")
    say(f"phi: {len(tokens)} requests x {MAX_NEW} tokens retired")

    seen = set()
    prefill_m = PHI_TIMESTEPS * 2 ** (PROMPT_LEN[0] - 1).bit_length()
    for d in decisions:
        key = (d["site"], d["shape"][0], d["impl"], d["reason"])
        if key in seen:
            continue
        seen.add(key)
        stage = "prefill" if d["shape"][0] >= prefill_m else "decode"
        say(f"  dispatch {stage:7s} {d['site']:10s} M={d['shape'][0]:<5d} "
            f"K={d['shape'][1]:<5d} N={d['shape'][2]:<5d} -> {d['impl']} "
            f"({d['reason']})")
        if stage == "prefill":
            check(d["impl"] in PALLAS_IMPLS,
                  f"phi: prefill GEMM {d['site']} M={d['shape'][0]} resolved "
                  f"to {d['impl']} ({d['reason']}), not a Pallas lowering")
    check(any(d["shape"][0] >= prefill_m for d in decisions),
          "phi: no prefill GEMM was dispatched")

    t0 = time.perf_counter()
    check_kernels(smoke)
    times["kernel_check"] = time.perf_counter() - t0

    # forced coo vs the policy on 2 requests: prefill + COO_DECODE_STEPS
    t0 = time.perf_counter()
    pair = prompts[:COO_REQUESTS]
    max_new = 1 + COO_DECODE_STEPS
    dispatch.get_policy().reset(keep_usage=True)
    pol_tokens, pol_logits, _ = serve(make_engine(cfg, params, paged=True),
                                      pair, max_new)
    coo_cfg = cfg.with_(phi=dataclasses.replace(cfg.phi, impl="coo"))
    coo_tokens, coo_logits, _ = serve(make_engine(coo_cfg, params,
                                                  paged=True), pair, max_new)
    times["coo_check"] = time.perf_counter() - t0
    dlogit = max_abs_diff(pol_logits, coo_logits)
    say(f"phi: policy vs forced coo on {COO_REQUESTS} requests (prefill + "
        f"{COO_DECODE_STEPS} decode steps): max |dlogit| = {dlogit!r} "
        f"(bound {COO_LOGIT_BOUND!r})")
    check(pol_tokens == coo_tokens, "phi: policy and coo greedy tokens differ")
    check(dlogit <= COO_LOGIT_BOUND,
          f"phi: max |dlogit| {dlogit!r} exceeds the bound {COO_LOGIT_BOUND!r}")
    return {"times": times, "tokens": tokens, "dlogit_coo": dlogit}


def phase_four_chips(smoke: bool = False) -> dict:
    """Sharded Phi serving on a (1, 4) ("data", "model") mesh against the
    one-device Phi run of the same requests."""
    import jax
    from repro.distributed.sharding import SERVE_RULES, specs_to_shardings
    from repro.kernels import dispatch
    from repro.launch.mesh import make_mesh
    from repro.models import model

    check(len(jax.devices()) >= 4, f"four chips: {len(jax.devices())} "
                                   "devices visible")
    cfg = phi_config(smoke)
    cfg, params = calibrate(cfg, dyadic_params(cfg))
    prompts = make_prompts(cfg.vocab)
    tokens_1, logits_1, secs_1 = serve(make_engine(cfg, params, paged=True),
                                       prompts)
    check_served(tokens_1, logits_1, len(prompts), MAX_NEW, "one device")

    mesh = make_mesh((1, 4), ("data", "model"))
    shardings = specs_to_shardings(model.lm_specs(cfg), mesh, SERVE_RULES)
    params = jax.device_put(params, shardings)
    spread = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        devs = {s.device.id for s in leaf.addressable_shards}
        spread[jax.tree_util.keystr(path)] = (
            len(devs), leaf.addressable_shards[0].data.shape, leaf.shape)
    split = [k for k, (n, local, full) in spread.items()
             if n == 4 and local != full]
    check(bool(split), "four chips: no parameter is split over the devices")
    check(all(n == 4 for n, _, _ in spread.values()),
          "four chips: a parameter is not on all four devices")
    say(f"four chips: {len(split)}/{len(spread)} parameter arrays split over "
        f"4 devices, e.g. {split[0]} local {spread[split[0]][1]} of "
        f"{spread[split[0]][2]}")

    dispatch.get_policy().reset(keep_usage=True)
    eng = make_engine(cfg, params, paged=True, mesh=mesh)
    (tokens_4, logits_4, secs_4), decisions = dispatch_records(
        lambda: serve(eng, prompts))
    check_served(tokens_4, logits_4, len(prompts), MAX_NEW, "four chips")
    seen = set()
    for d in decisions:
        key = (d["site"], d["shape"][0], d["impl"], d["reason"],
               d.get("shards"))
        if key not in seen:
            seen.add(key)
            say(f"  dispatch {d['site']:14s} M={d['shape'][0]:<5d} "
                f"-> {d['impl']} ({d['reason']}) shards={d.get('shards')}")
    body = [d for d in decisions if d["site"].endswith(".spmd")]
    check(bool(body), "four chips: no Phi GEMM ran inside a shard_map body")
    check(all(d["reason"].startswith("spmd_local_") and d.get("shards") == 4
              for d in body),
          "four chips: an in-body decision is not spmd_local_* with shards=4")
    dlogit = max_abs_diff(logits_1, logits_4)
    say(f"four chips: sharded vs one-device max |dlogit| = {dlogit!r}; "
        f"serve {secs_4!r}s vs {secs_1!r}s")
    check(tokens_1 == tokens_4,
          "four chips: sharded and one-device greedy tokens differ")
    return {"dlogit": dlogit, "tokens": tokens_4}


# ------------------------------------------------------------------- main ---
def device_record():
    """(platform, kind, count) of the visible devices, or exit non-zero
    when JAX finds no TPU."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        say(f"chip_smoke: no accelerator: {e}")
        sys.exit(2)
    if devs[0].platform != "tpu":
        say(f"chip_smoke: JAX finds no TPU (platform {devs[0].platform!r}); "
            "this check runs on a TPU only")
        sys.exit(2)
    return devs[0].platform, devs[0].device_kind, len(devs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded Phi serve path on a (1, 4) "
                         "mesh and its one-device comparison")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        say(f"chip_smoke: no repro package under {ROOT}/src")
        return 2
    platform, kind, count = device_record()
    from repro.utils import enable_compile_cache

    say(f"device: {platform} {kind} x{count}; compile cache "
        f"{enable_compile_cache()}")
    import jax

    t_all = time.perf_counter()
    if args.four_chips:
        check(count == 4, f"--four-chips needs 4 devices, found {count}")
        phase_four_chips()
    else:
        plain = phase_plain()
        phi = phase_phi(params=plain.pop("params"))
        for name, rec in (("plain", plain), ("phi", phi)):
            say(f"times {name}: " + ", ".join(
                f"{k} {v!r}s" for k, v in rec["times"].items()))
    stats = jax.devices()[0].memory_stats() or {}
    say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')!r}; "
        f"total {time.perf_counter() - t_all!r}s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
