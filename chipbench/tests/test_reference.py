"""The plain reference against the paged engine at smoke size on a CPU:
prefill, then decode through the page pool, plain and spiking+Phi. Both run
in float32 here (the smoke configuration computes in float32), so the
logits agree to float32 rounding and the greedy tokens are the reference's
best."""
import numpy as np
import pytest

from chipbench import harness, reference, weights
from chipbench.refs import olmo

SEED = 2 ** 31 + 3
PROMPTS = [17, 40, 64]
NEW = 6


def served(name):
    from repro.serve.engine import Engine, Request

    c = harness.load_config(name)
    cfg = harness.program_config(c, True, olmo)
    leaves = olmo.leaves(olmo.arch(harness.sizes(c, True)))
    params = harness.program_params(cfg, leaves, SEED, c["tie_word_embeddings"])
    if c.get("spiking"):
        cfg, params = harness.calibrate(cfg, params, c, SEED)
    s = harness.sizes(c, True)
    eng = Engine(cfg, params, batch_slots=s["slots"], max_context=s["max_context"],
                 paged=True, page_size=s["page_size"], num_pages=s["num_pages"],
                 eos_id=-1, record_logits=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, cfg.vocab, n).astype(np.int32) for n in PROMPTS]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, tokens=p, max_new_tokens=NEW))
    res = {r.rid: r.tokens for r in eng.run()}
    return c, prompts, res, {r: np.stack(v) for r, v in eng.logit_trace.items()}


@pytest.mark.parametrize("name", ["olmo_1b", "olmo_1b_phi"])
def test_engine_logits_match_the_reference(name):
    c, prompts, toks, logits = served(name)
    arch = olmo.arch(harness.sizes(c, True))
    w = weights.make(SEED, olmo.leaves(arch), c["tie_word_embeddings"])
    for rid, p in enumerate(prompts):
        seq = np.concatenate([p, np.asarray(toks[rid][:-1], np.int32)])
        padded = np.zeros(reference.padded_len(len(seq)), np.int32)
        padded[:len(seq)] = seq
        ref = np.asarray(olmo.forward(arch, None, w, padded))
        ref = ref[len(p) - 1:len(seq)]
        assert logits[rid].shape == ref.shape
        np.testing.assert_allclose(logits[rid], ref, atol=2e-4, rtol=0)
        gaps = olmo.gaps(arch, lambda names: w, p, toks[rid])
        assert float(gaps.max()) <= 2e-4


def test_weights_are_the_same_for_harness_and_reference():
    c = harness.load_config("olmo_1b")
    cfg = harness.program_config(c, True, olmo)
    arch = olmo.arch(harness.sizes(c, True))
    harness.check_leaves(olmo.leaves(arch), harness.param_leaves(cfg))
    assert harness.param_leaves(cfg) == {p: v[:2] for p, v in olmo.leaves(arch).items()}


def test_a_tied_head_is_the_embeddings_transpose():
    c = harness.load_config("olmo_1b")
    assert c["tie_word_embeddings"] is True
    arch = olmo.arch(harness.sizes(c, True))
    tied = weights.make(SEED, olmo.leaves(arch), True)
    untied = weights.make(SEED, olmo.leaves(arch), False)
    np.testing.assert_array_equal(np.asarray(tied["head"]), np.asarray(tied["embed"]).T)
    assert not np.array_equal(np.asarray(untied["head"]), np.asarray(tied["head"]))
    for k in untied:
        if k != "head":
            np.testing.assert_array_equal(np.asarray(untied[k]), np.asarray(tied[k]))
