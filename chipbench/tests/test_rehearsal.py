"""A whole cell at smoke size on a CPU, through the same inner functions as
run.py: the result object has the contract's keys, every end-to-end metric
of the cell, and ``correct`` true. Besides the benchmark's cells, the
spiking+Phi configuration under both closed-loop mixes."""
import json
import time

import pytest

from chipbench import harness, loadgen

SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]] + \
    ["olmo_1b_phi.chat_closed", "olmo_1b_phi.long_prompt_closed"]


def cell_of(name):
    if name in [w["name"] for w in SPEC["workloads"]]:
        return harness.find_cell(SPEC, name)
    config, traffic = name.split(".")
    c = harness.load_config(config)
    return harness.Cell(name=name, chips=1, config=c, traffic=loadgen.load(traffic),
                        end_to_end=harness.for_cell(SPEC["end_to_end"], name),
                        per_layer=[], ref=harness.load_reference(c))


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_runs_correct_at_smoke_size(cell):
    c = cell_of(cell)
    r = harness.run(c, 2 ** 31 + 17, 5.0, False, time.perf_counter(), smoke=True)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert {m["name"] for m in c.end_to_end} == set(r["metrics"])
    assert r["checks"]["logit_gap"]["value"] <= 1e-3
    json.dumps(r)
