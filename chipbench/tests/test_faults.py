"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run at smoke size on a CPU (everything but the
look for a chip) with one fault planted in the program:

* ``token``: a token altered where it is produced (every sampled id + 1);
* ``state``: the decode step returns the KV pools it was given, unchanged;
* ``half_batch``: the decode step leaves out the second half of the batch,
  handing those slots the first half's logits.
"""
import time

import jax.numpy as jnp
import pytest

from chipbench import harness, loadgen


def plant(monkeypatch, fault):
    from repro.models import model
    from repro.serve import engine

    if fault == "token":
        real = engine.sample

        def altered(logits, key, **kw):
            return (real(logits, key, **kw) + 1) % logits.shape[-1]
        monkeypatch.setattr(engine, "sample", altered)
        return
    real_step = model.decode_step_paged

    def broken(cfg, params, token, pos, pools, table):
        logits, new_pools = real_step(cfg, params, token, pos, pools, table)
        if fault == "state":
            return logits, pools
        half = logits.shape[0] // 2
        return jnp.concatenate([logits[:half], logits[:half]]), new_pools
    monkeypatch.setattr(model, "decode_step_paged", broken)


@pytest.mark.parametrize("fault", ["token", "state", "half_batch"])
@pytest.mark.parametrize("config", ["olmo_1b", "olmo_1b_phi"])
def test_a_planted_fault_is_not_correct(monkeypatch, config, fault):
    """Closed-loop chat keeps every slot busy with outputs of several
    tokens, so each fault has work to spoil."""
    plant(monkeypatch, fault)
    spec = harness.load_spec()
    c = harness.load_config(config)
    cell = harness.Cell(name=f"{config}.faults", chips=1, config=c,
                        traffic=loadgen.load("chat_closed"),
                        end_to_end=spec["end_to_end"], per_layer=[],
                        ref=harness.load_reference(c))
    r = harness.run(cell, 2 ** 32 + 5, 1.0, False, time.perf_counter(), smoke=True)
    assert r["correct"] is False, r["checks"]
