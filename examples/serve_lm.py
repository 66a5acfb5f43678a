"""Serve a small LM with batched requests through the continuous-batching
engine — optionally in spiking+Phi mode (the paper's technique as the
serving compute path).

    PYTHONPATH=src python examples/serve_lm.py            # dense serving
    PYTHONPATH=src python examples/serve_lm.py --phi      # spiking+Phi serving
"""
import argparse
import sys
import time

import jax
import numpy as np

sys.path.insert(0, "src")

from repro.configs import get_config, phi_variant
from repro.distributed.sharding import init_params
from repro.models import model
from repro.serve.engine import Engine, Request
from repro.utils import log


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1p5_4b")
    ap.add_argument("--phi", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    if args.phi:
        cfg = phi_variant(cfg, timesteps=2, q=16)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(0))
    if args.phi:
        batch = model.dummy_batch(cfg, 2, 16, with_labels=False)
        cfg, params, maxd = model.calibrate_lm_phi_budgeted(cfg, params, batch)
        log.info("phi calibrated: max L2 density %.3f", maxd)

    eng = Engine(cfg, params, batch_slots=args.slots, max_context=64)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        plen = int(rng.integers(4, 12))
        eng.submit(Request(rid=rid, tokens=rng.integers(3, cfg.vocab, plen),
                           max_new_tokens=args.max_new))
    t0 = time.time()
    results = eng.run()
    dt = time.time() - t0
    for r in sorted(results, key=lambda r: r.rid):
        log.info("req %d (prompt %d tokens) -> %s", r.rid, r.prompt_len, r.tokens)
    log.info("served %d requests, %d decode ticks, %d tokens in %.1fs "
             "(%.1f tok/s, slot util %.0f%%)", len(results), eng.ticks,
             eng.decoded_tokens, dt, eng.decoded_tokens / dt,
             100.0 * eng.decoded_tokens / max(eng.ticks * args.slots, 1))


if __name__ == "__main__":
    main()
