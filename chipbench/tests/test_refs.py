"""The reference module that a configuration names: a cell of a new
configuration is added with new files only, the module's weight leaves are
checked against the program's before any weight is made, weights drawn in
parts equal the whole draw, and OLMo's module reads what the single OLMo
reference read before it (digests recorded from that code)."""
import copy
import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness, weights
from chipbench.refs import olmo

SEED = 2 ** 31 + 3
BENCH = harness.HERE


def tree_digest(top):
    """Digest of every file under ``top``, compiled caches aside."""
    h = hashlib.sha256()
    for d, dirs, files in sorted(os.walk(top)):
        dirs[:] = sorted(x for x in dirs if x != "__pycache__")
        for name in sorted(files):
            with open(os.path.join(d, name), "rb") as f:
                h.update(os.path.relpath(os.path.join(d, name), top).encode())
                h.update(f.read())
    return h.hexdigest()


# Run in a copy of the benchmark that holds the new files. The program has
# no registry entry for the new shape, so the script registers one: OLMo's
# with 2 KV heads (4 at full size), the entry a new configuration brings to
# the program.
ADD = r'''
import json, sys, time, types
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from repro.configs import olmo_1b
prog = types.ModuleType("repro.configs.gqa_untied")
prog.smoke = lambda: olmo_1b.smoke().with_(n_kv_heads=2)
prog.full = lambda: olmo_1b.full().with_(n_kv_heads=4)
sys.modules[prog.__name__] = prog
from chipbench import harness
cell = harness.find_cell(harness.load_spec(sys.argv[1]), "gqa_untied.few")
a = cell.ref.arch(harness.sizes(cell.config, True))
r = harness.run(cell, 2 ** 31 + 29, 2.0, False, time.perf_counter(), smoke=True)
print(json.dumps({"bench": harness.HERE, "ref": cell.ref.__file__,
                  "heads": [a.heads, a.kv_heads], "correct": r["correct"],
                  "checks": r["checks"]}))
'''


def gqa_untied():
    """OLMo's configuration with 2 KV heads of 4 (smoke; 4 of 16 at full
    size) and an untied head, naming a reference module of its own."""
    c = copy.deepcopy(harness.load_config("olmo_1b"))
    c.update(name="gqa_untied", arch="gqa_untied", reference="olmo_copy",
             tie_word_embeddings=False, num_key_value_heads=4)
    c["smoke"]["num_key_value_heads"] = 2
    return c


def test_a_new_configuration_cell_takes_new_files_only(tmp_path):
    """A configuration file, a traffic file and a reference module (a copy
    of OLMo's under a new name), with their BENCHMARK.json entries, make a
    cell that runs correct; no file of the benchmark is edited."""
    before = tree_digest(BENCH)
    bench = tmp_path / "chipbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    c = gqa_untied()
    (bench / "configs" / "gqa_untied.json").write_text(json.dumps(c, indent=1))
    (bench / "traffic" / "few_closed.json").write_text(json.dumps(
        {"loop": "closed", "clients": 3,
         "prompt": {"dist": "uniform", "min": 20, "max": 60},
         "output": {"dist": "uniform", "min": 4, "max": 9}, "pool": 8}))
    shutil.copy(bench / "refs" / "olmo.py", bench / "refs" / "olmo_copy.py")
    spec = harness.load_spec()
    spec["configs"].append({"name": "gqa_untied", "source": c["source"],
                            "file": "chipbench/configs/gqa_untied.json",
                            "reduced": [], "why": "a test of the interface"})
    spec["workloads"].append({"name": "gqa_untied.few", "config": "gqa_untied",
                              "traffic": "few_closed", "chips": 1, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    p = subprocess.run([sys.executable, "-c", ADD, str(tmp_path),
                        os.path.join(harness.ROOT, "src")],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["bench"] == str(bench)
    assert out["ref"] == str(bench / "refs" / "olmo_copy.py")
    assert out["heads"] == [4, 2]
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["tokens_compared"]["value"] > 0
    assert tree_digest(BENCH) == before


def test_a_configuration_without_a_reference_is_refused(monkeypatch):
    load = harness.load_config

    def without_reference(name):
        c = load(name)
        del c["reference"]
        return c

    monkeypatch.setattr(harness, "load_config", without_reference)
    with pytest.raises(KeyError, match='"reference"'):
        harness.find_cell(harness.load_spec(), "olmo_1b.chat")


def test_leaves_that_differ_from_the_program_stop_set_up(monkeypatch):
    def leaves(a):
        out = dict(olmo.leaves(a))
        del out["decoder/stack/p0/wv"]
        shape, dtype, init = out["decoder/stack/p0/wk"]
        out["decoder/stack/p0/wk"] = ((shape[0], shape[1], shape[2] // 2), dtype, init)
        out["decoder/stack/p0/norm/gain"] = ((a.layers, a.d_model), "float32", "ones")
        return out

    def no_weights(*a, **k):
        raise AssertionError("weights made before the leaves were checked")

    cell = harness.find_cell(harness.load_spec(), "olmo_1b.chat")
    fake = SimpleNamespace(**{k: getattr(olmo, k) for k in
                              ("arch", "program_fields", "gaps", "padded_len",
                               "model_ops")}, leaves=leaves)
    monkeypatch.setattr(weights, "make", no_weights)
    with pytest.raises(ValueError) as e:
        harness.set_up(dataclasses.replace(cell, ref=fake), SEED, 1.0, smoke=True)
    for path in ("decoder/stack/p0/wv", "decoder/stack/p0/wk",
                 "decoder/stack/p0/norm/gain"):
        assert path in str(e.value)
    assert "decoder/stack/p0/wq" not in str(e.value)


@pytest.mark.parametrize("tied", [True, False])
def test_a_draw_of_some_leaves_equals_the_whole_draw(tied):
    leaves = olmo.leaves(olmo.arch(harness.sizes(harness.load_config("olmo_1b"), True)))
    whole = weights.make(SEED, leaves, tied)
    for names in (["head"], ["decoder/stack/p0/wk", "embed"], ["decoder/stack/p0/mlp/w2"]):
        part = weights.make(SEED, leaves, tied, names)
        assert sorted(part) == sorted(names)
        for n in names:
            np.testing.assert_array_equal(np.asarray(part[n]), np.asarray(whole[n]))


def test_init_rules():
    leaves = {"gain": ((3, 8), "float32", "ones"), "bias": ((8,), "bfloat16", "zeros"),
              "w": ((512, 256), "float32", 0.5), "v": ((512, 256), "float32", None),
              "u": ((512, 256), "float32")}
    w = {k: np.asarray(v) for k, v in weights.make(SEED, leaves).items()}
    np.testing.assert_array_equal(w["gain"], np.ones((3, 8), np.float32))
    assert w["bias"].dtype.name == "bfloat16" and not w["bias"].astype(np.float32).any()
    assert w["w"].std() == pytest.approx(0.5, rel=0.02)
    np.testing.assert_array_equal(w["w"] * weights.GRID, np.round(w["w"] * weights.GRID))
    assert w["v"].std() == pytest.approx(512 ** -0.5, rel=0.02)
    assert not np.array_equal(w["u"], w["v"])         # keyed by path
    leaves["u"] = ((512, 256), "float32", None)
    np.testing.assert_array_equal(np.asarray(weights.make(SEED, leaves, names=["u"])["u"]),
                                  w["u"])


def digest(x):
    return hashlib.sha256(np.ascontiguousarray(np.asarray(x)).tobytes()).hexdigest()[:16]


# Recorded from the single OLMo reference (``reference.Arch``,
# ``weight_leaves``, ``forward``, ``gaps`` and ``work.model_ops``) that
# refs/olmo.py replaced, at the same seed, tokens and counts.
LEAVES = {"decoder/stack/p0/mlp/w1": "610cd1f6902c656e",
          "decoder/stack/p0/mlp/w2": "7438b93bf328ed08",
          "decoder/stack/p0/mlp/w3": "30979b6171124918",
          "decoder/stack/p0/wk": "0b060f85fc43cfa5", "decoder/stack/p0/wo": "15e51d2243ac3bc7",
          "decoder/stack/p0/wq": "750a0a0ac65cd3b5", "decoder/stack/p0/wv": "c5a6330e89748162",
          "embed": "993d877060cdd2d7", "head": "a630f8fdc0bba494"}
UNTIED_HEAD = "2a87235663540651"
RECORDED = {
    "olmo_1b": {"logits": "8195711350f786bc", "int8 logits": "54b4dc6d075c4ca3",
                "gaps": ("36de6076070b51d5", "9bc03c63aac5801e"),
                "model_ops": 2912734019584},
    "olmo_1b_phi": {"logits": "e3389d3a28d5c989", "int8 logits": "e982dc63fec0e53c",
                    "gaps": ("41f59b57fda392d9", "2ffad62c388dafb3"),
                    "model_ops": 251196473344},
}


@pytest.mark.parametrize("name", ["olmo_1b", "olmo_1b_phi"])
def test_olmo_reads_as_before(name):
    c = harness.load_config(name)
    a = olmo.arch(harness.sizes(c, True))
    draw = harness.Draw(SEED, olmo.leaves(a), c["tie_word_embeddings"])
    w = draw(olmo.leaves(a))
    assert {k: digest(v) for k, v in w.items()} == LEAVES
    assert digest(weights.make(SEED, olmo.leaves(a), False)["head"]) == UNTIED_HEAD
    toks = np.zeros(256, np.int32)
    toks[:50] = (np.arange(50) * 7 + 3) % 128
    want = RECORDED[name]
    assert digest(olmo.forward(a, None, w, toks)) == want["logits"]
    assert digest(olmo.forward(a, "int8", w, toks)) == want["int8 logits"]
    prompt, served = toks[:30], [int(t) for t in toks[30:50]]
    assert (digest(olmo.gaps(a, draw, prompt, served)),
            digest(olmo.gaps(a, draw, prompt, served, "int8"))) == want["gaps"]
    assert olmo.model_ops(olmo.arch(c), prompt_lens=[1000, 17, 300],
                          decode_contexts=[1001, 1002, 18, 301, 2048]) == want["model_ops"]
