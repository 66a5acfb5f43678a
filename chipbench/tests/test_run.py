"""run.py without a chip, and the harness finding a cell's files by name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py"),
         "--workload", "olmo_1b.chat", "--seed", str(2 ** 31 + 11),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_run_exits_nonzero_and_prints_no_result():
    p = run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_without_the_program_run_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_cell_resolves_to_its_files():
    spec = harness.load_spec(ROOT)
    for w in spec["workloads"]:
        cell = harness.find_cell(spec, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("open", "closed")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(harness.load_metric(m["name"]).read)
    with pytest.raises(KeyError):
        harness.find_cell(spec, "no_such_cell")


@pytest.mark.parametrize("name", ["olmo_1b", "olmo_1b_phi"])
def test_full_sizes_hold_the_engine_settings(name):
    c = harness.load_config(name)
    s = harness.sizes(c, smoke=False)
    assert {k: s[k] for k in c["engine"]} == c["engine"]
    assert s["hidden_size"] == 2048 and s["correct"] == c["correct"]
    assert harness.sizes(c, smoke=True)["hidden_size"] == 64


ADD = r'''
import json, sys
sys.path.insert(0, sys.argv[1])
from chipbench import harness, loadgen
spec = harness.load_spec(sys.argv[1])
cell = harness.find_cell(spec, "tiny.flat")
print(json.dumps({"config": cell.config["name"], "loop": cell.traffic["loop"],
                  "per_layer": [m["name"] for m in cell.per_layer],
                  "value": harness.load_metric("answer").read(None),
                  "n": len(loadgen.Traffic(cell.traffic, 1, 100).schedule(10.0))}))
'''


def test_a_cell_config_traffic_and_metric_are_added_by_files_alone(tmp_path):
    """A later PR adds files and BENCHMARK.json entries; it edits no file
    of the harness."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = harness.load_spec(ROOT)
    cfg = harness.load_config("olmo_1b")
    cfg["name"] = "tiny"
    (tmp_path / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "chipbench" / "traffic" / "flat.json").write_text(json.dumps({
        "loop": "open", "arrival": {"process": "poisson", "rate": 2.0},
        "prompt": {"dist": "uniform", "min": 8, "max": 16},
        "output": {"dist": "uniform", "min": 1, "max": 2}}))
    (tmp_path / "chipbench" / "metrics" / "answer.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec["configs"].append({"name": "tiny", "source": "x",
                            "file": "chipbench/configs/tiny.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "tiny.flat", "config": "tiny",
                              "traffic": "flat", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "answer", "unit": "1", "better": "higher",
                              "source": "host_clock", "layer": "engine",
                              "moves": "tokens_per_s", "workloads": ["tiny.flat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    p = subprocess.run([sys.executable, "-c", ADD, str(tmp_path)],
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["config"] == "tiny" and out["loop"] == "open"
    assert out["per_layer"] == ["answer"] and out["value"] == 42.0
    assert 10 <= out["n"] <= 30
