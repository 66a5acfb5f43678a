"""The layer-typed hybrid (granite-4.0-h: Mamba-2 layers with GQA layers
between them, an MLP after each) on the serving path, at smoke size in f32.

* The Mamba-2 block (conv bias, gate-then-norm) equals a plain per-token
  recurrence, in prefill and decode.
* Attention with ``attention_multiplier`` (q pre-scaled), no positional
  encoding and ``residual_multiplier`` equals the plain formula.
* A right-padded (bucketed) prefill hands decode the raw prompt's state.
* The paged engine serves the contiguous engine's tokens, also through a
  forced preemption and resume, and its ``decode`` events carry the
  recurrent state the step read and wrote.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.sharding import init_params
from repro.models import layers as ll
from repro.models import mamba2, model, transformer
from repro.obs import ListSink, Tracer
from repro.serve.engine import Engine, Request

CFG = get_config("granite_4_0_h_micro", smoke=True)


def _params(cfg=CFG, seed=0):
    """Seeded weights with every leaf drawn, the ones a fresh init leaves
    at 0 or 1 (conv biases, A_log, dt_bias, D, gains) among them."""
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [x + 0.3 * jax.random.normal(k, x.shape, x.dtype)
              if x.ndim <= 2 else x for x, k in zip(leaves, keys)]
    return jax.tree.unflatten(tree, leaves)


def _layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


# ------------------------------------------------------------- the blocks --

def _plain_mamba(cfg, p, x):
    """Mamba-2 as written: projections, causal conv with bias and silu,
    softplus dt, the per-token recurrence, D skip, rmsnorm(y * silu(z))."""
    S = x.shape[0]
    H, P, N, kc = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state, cfg.conv_kernel

    def conv(u, w, b):
        up = jnp.concatenate([jnp.zeros((kc - 1, u.shape[1])), u])
        return jax.nn.silu(sum(up[i:i + S] * w[i] for i in range(kc)) + b)

    z = x @ p["wz"]
    xs = conv(x @ p["wx"], p["conv_x"], p["conv_x_bias"]).reshape(S, H, P)
    B = conv(x @ p["wB"], p["conv_B"], p["conv_B_bias"])
    C = conv(x @ p["wC"], p["conv_C"], p["conv_C_bias"])
    dt = jax.nn.softplus(x @ p["wdt"] + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    h = jnp.zeros((H, P, N))
    ys = []
    for t in range(S):
        h = h * jnp.exp(dt[t] * A)[:, None, None] + \
            (dt[t][:, None] * xs[t])[:, :, None] * B[t]
        ys.append(jnp.einsum("hpn,n->hp", h, C[t]) + p["D"][:, None] * xs[t])
    g = jnp.stack(ys).reshape(S, H * P) * jax.nn.silu(z)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + cfg.norm_eps)
    return (g * p["norm_w"]) @ p["wo"], h


def test_mamba_block_with_bias_and_gate_then_norm_matches_plain_formula():
    p = _layer(_params()["decoder"]["r0"]["mamba"], 0)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 13, CFG.d_model))
    want, h = _plain_mamba(CFG, p, x[0])
    got, (ssm, conv) = mamba2.mamba_prefill(CFG, p, x[:, :9])
    np.testing.assert_allclose(got[0], want[:9], rtol=2e-5, atol=2e-5)
    for t in range(9, 13):            # decode continues from prefill's state
        out, (ssm, conv) = mamba2.mamba_decode(CFG, p, x[:, t], (ssm, conv))
        np.testing.assert_allclose(out[0], want[t], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ssm[0], h, rtol=2e-5, atol=2e-5)


def test_attention_multiplier_is_a_q_prescale():
    """NoPE GQA with scores scaled by attention_multiplier (1/64 with
    head_dim 16: q times 1/16 before the lowering's 1/4) and the output
    added times residual_multiplier, in prefill and decode."""
    p = _layer(_params()["decoder"]["r1"], 0)
    S, hd, rep = 10, CFG.hd, CFG.n_heads // CFG.n_kv_heads
    x = jax.random.normal(jax.random.PRNGKey(3), (1, S, CFG.d_model))
    h = ll.rmsnorm(x[0], p["ln1"]["w"], CFG.norm_eps)
    q = (h @ p["wq"]).reshape(S, CFG.n_heads, hd)
    k = jnp.repeat((h @ p["wk"]).reshape(S, CFG.n_kv_heads, hd), rep, 1)
    v = jnp.repeat((h @ p["wv"]).reshape(S, CFG.n_kv_heads, hd), rep, 1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * CFG.attention_multiplier
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v).reshape(S, -1)
    want = x[0] + CFG.residual_multiplier * (o @ p["wo"])

    pos = jnp.broadcast_to(jnp.arange(S)[None], (1, S))
    got, (kc, vc) = transformer.attn_block_prefill(CFG, p, x, pos, True,
                                                   want_cache=True)
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)
    cache = tuple(jnp.pad(c[:, :S - 1], ((0, 0), (0, 1), (0, 0), (0, 0)))
                  for c in (kc, vc))
    last, _ = transformer.attn_block_decode(CFG, p, x[:, S - 1:], jnp.array([S - 1]),
                                            cache, True)
    np.testing.assert_allclose(last[0, 0], want[-1], rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- bucketed prefill --

@pytest.mark.parametrize("plen", [2, 11])
def test_bucketed_prefill_equals_raw_length_prefill(plen):
    """Right-padded to 16 with the true length given, prefill returns the
    raw prompt's last logits, SSM states, conv rings (zeros before a
    prompt shorter than the conv) and KV over the prompt."""
    params = _params()
    tokens = jax.random.randint(jax.random.PRNGKey(plen), (1, plen), 3, CFG.vocab)
    raw_logits, raw = model.prefill(CFG, params, {"tokens": tokens})
    padded = jnp.zeros((1, 16), jnp.int32).at[:, :plen].set(tokens)
    logits, st = model.prefill_padded(CFG, params, {"tokens": padded},
                                      jnp.array([plen - 1]))
    np.testing.assert_allclose(logits, raw_logits, rtol=1e-5, atol=1e-5)
    for run in ("r0", "r2"):                      # Mamba-2 runs
        for a, b in zip(jax.tree.leaves(st[run]), jax.tree.leaves(raw[run])):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    for a, b in zip(st["r1"], raw["r1"]):         # attention KV
        np.testing.assert_allclose(a[:, :, :plen], b, rtol=1e-5, atol=1e-5)
    # without the true length the pad is folded into the state
    _, folded = model._forward(CFG, params, {"tokens": padded}, want_cache=True)
    assert not np.allclose(folded["r0"][0], raw["r0"][0], atol=1e-3)


# ------------------------------------------------------------------ engine --

def _requests(lens, max_new, seed=11):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, tokens=[int(t) for t in rng.integers(3, CFG.vocab, n)],
                    max_new_tokens=max_new, temperature=0.0)
            for i, n in enumerate(lens)]


def _serve(params, reqs, **kw):
    eng = Engine(CFG, params, batch_slots=2, max_context=32, **kw)
    for r in reqs:
        eng.submit(r)
    return eng, {r.rid: r.tokens for r in eng.run()}


@pytest.fixture(scope="module")
def served():
    params = _params()
    lens, max_new = (9, 9, 9, 9), 10
    dense, dense_res = _serve(params, _requests(lens, max_new))
    paged, paged_res = _serve(params, _requests(lens, max_new), paged=True,
                              page_size=8)
    sink = ListSink()
    tight, tight_res = _serve(params, _requests(lens, max_new), paged=True,
                              page_size=8, num_pages=4,
                              tracer=Tracer(sink, wall_time=False))
    return {"dense": (dense, dense_res), "paged": (paged, paged_res),
            "tight": (tight, tight_res), "records": sink.records}


def test_paged_hybrid_serves_the_contiguous_tokens(served):
    dense, dense_res = served["dense"]
    paged, paged_res = served["paged"]
    assert dense.bucketed and not dense.paged
    assert paged.paged and paged.bucketed
    assert paged_res == dense_res
    assert {rid: len(t) for rid, t in paged_res.items()} == {i: 10 for i in range(4)}


def test_preempted_hybrid_resumes_token_identical(served):
    """A pool at its floor preempts mid-decode; the re-prefill of prompt
    plus generated prefix rebuilds KV and recurrent state, and every stream
    ends as in the unconstrained run."""
    tight, tight_res = served["tight"]
    sched = tight.scheduler.report()
    assert sched.get("preempt_pool_dry", 0) > 0, sched
    assert tight_res == served["paged"][1]


def test_decode_events_carry_the_recurrent_state_bytes(served):
    """Per slot: each Mamba-2 layer's f32 SSM state (heads x head_dim x
    d_state) and its three conv rings (k - 1 past inputs of x, B and C)."""
    tight = served["tight"][0]
    n_mamba = CFG.layer_kinds.count("mamba")
    per_slot = n_mamba * (CFG.ssm_heads * CFG.ssm_headdim * CFG.ssm_state * 4
                          + (CFG.conv_kernel - 1) * (CFG.d_inner + 2 * CFG.ssm_state)
                          * jnp.dtype(CFG.compute_dtype).itemsize)
    assert tight.slot_state_bytes == per_slot
    decodes = [r for r in served["records"] if r["kind"] == "decode"]
    assert decodes and all(r["state_bytes"] == r["active"] * per_slot
                           for r in decodes)
    assert {"bucket", "prompt_len"} <= set(
        next(r for r in served["records"] if r["kind"] == "prefill"))


def test_attention_only_engine_events_carry_no_state_bytes():
    cfg = get_config("olmo_1b", smoke=True)
    sink = ListSink()
    eng = Engine(cfg, init_params(model.lm_specs(cfg), jax.random.PRNGKey(0)),
                 batch_slots=2, max_context=32, paged=True, page_size=8,
                 tracer=Tracer(sink, wall_time=False))
    eng.submit(Request(rid=0, tokens=[5, 6, 7], max_new_tokens=3))
    eng.run()
    assert eng.slot_state_bytes == 0
    assert all("state_bytes" not in r for r in sink.records if r["kind"] == "decode")


# ----------------------------------------------------------------- config --

def test_granite_full_config_is_the_published_one():
    cfg = get_config("granite-4.0-h-micro")
    kinds = cfg.layer_kinds
    assert len(kinds) == 40
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [5, 15, 25, 35]
    assert transformer.layer_runs(cfg) == [("mamba", 5), ("attention", 1), ("mamba", 4)]
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd) == (2048, 32, 8, 64)
    assert (cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state, cfg.d_ff) == (64, 64, 128, 8192)
    assert cfg.vocab == 100352 and cfg.position == "none"
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (12, 0.22, 1 / 64, 8)
    assert cfg.q_heads_padded == 32 and cfg.kv_heads_padded == 8
    tot, _ = cfg.param_count()
    assert 3.39e9 < tot < 3.40e9            # tied head counted as its leaf
