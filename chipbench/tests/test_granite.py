"""granite4_h_micro's reference module at the published sizes: its weight
leaves are the program's parameter tree, its work counts are the ones
worked by hand, and the model step's readers read a hand-built run."""
import math
from types import SimpleNamespace

import pytest

from chipbench import harness, peaks, trace_reduce
from chipbench.refs import granite

CONFIG = harness.load_config("granite4_h_micro")
A = granite.arch(harness.sizes(CONFIG, False))
V5E = peaks.peaks("TPU v5 lite")
HEAD = 2048 * 100352


def test_leaves_are_the_program_tree_at_full_size():
    """Paths, shapes and dtypes (bf16 weights; f32 norm gains, A_log, D,
    dt_bias) equal the program's; 3.19 B parameters without the head leaf,
    3.40 B with it; 36 Mamba-2 and 4 attention layers, attention at 5, 15,
    25 and 35."""
    cfg = harness.program_config(CONFIG, False, granite)
    leaves = granite.leaves(A)
    harness.check_leaves(leaves, harness.param_leaves(cfg))
    n = sum(math.prod(s) for s, *_ in leaves.values())
    assert n == 3_396_916_992 and n - HEAD == 3_191_396_096
    assert A.layer_types.count("mamba") == 36
    assert [i for i, k in enumerate(A.layer_types) if k == "attention"] == [5, 15, 25, 35]
    assert {d for s, d, *_ in leaves.values()} == {"bfloat16", "float32"}
    assert leaves["decoder/r0/mamba/wz"][:2] == ((20, 2048, 4096), "bfloat16")
    # each layer of the published order maps to its run and its place there
    assert A.where()[:7] == [("r0", 0), ("r0", 1), ("r0", 2), ("r0", 3),
                             ("r0", 4), ("r1", 0), ("r2", 0)]
    assert A.where()[10] == ("r0", 5) and A.where()[35] == ("r1", 3)


def test_leaf_check_refuses_a_program_without_conv_bias():
    cfg = harness.program_config(CONFIG, False, granite)
    prog = harness.param_leaves(cfg)
    del prog["decoder/r0/mamba/conv_x_bias"]
    with pytest.raises(ValueError, match="conv_x_bias"):
        harness.check_leaves(granite.leaves(A), prog)


def test_model_ops_by_hand():
    d, inner, N, H, ff = 2048, 4096, 128, 64, 8192
    mlp = 2 * 3 * d * ff
    mamba = 2 * (2 * d * inner + 2 * d * N + d * H + inner * d) + mlp \
        + 4 * inner * N + 2 * 4 * (inner + 2 * N)
    attn = 2 * (d * 2048 + 2 * d * 512 + 2048 * d) + mlp
    per_token = 36 * mamba + 4 * attn
    head = 2 * d * 100352
    assert granite.model_ops(A, decode_contexts=[100]) == \
        per_token + 4 * 4 * 32 * 64 * 100 + head
    assert granite.model_ops(A, prompt_lens=[3]) == \
        3 * per_token + 4 * 4 * 32 * 64 * 6 + head


def test_decode_bytes_by_hand():
    """Weights once (the embedding table aside), the state read and
    written, 8 KB of KV a live token (4 layers x K, V x 8 heads x 64 x
    bf16)."""
    w = 2 * (3_396_916_992 - HEAD)
    assert granite.decode_bytes(A, weights=2, state_bytes=0, kv_tokens=0) == w
    assert granite.decode_bytes(A, weights=2, state_bytes=5, kv_tokens=3) == \
        w + 10 + 3 * 8192


def ctx(trace=True, state=True):
    w = harness.Window(t0=10.0, t_close=20.0, t1=20.5)
    events = [{"kind": "decode", "active": a, "wall_ms": t * 1e3,
               "kv_pages": pages, **({"state_bytes": a * 76_437_504} if state else {})}
              for t, a, pages in [(11.0, 2, 40), (12.0, 4, 90), (13.0, 3, 70),
                                  (25.0, 9, 500)]]
    red = trace_reduce.Reduced(
        window_s=10.5, busy_s=9.0, devices=1, op_s={},
        module_s={"jit__unknown(1)": [0.03, 0.03, 0.03],
                  "jit__unknown(2)": [0.2]}, idle_gaps={}) if trace else None
    return harness.Context(cell=SimpleNamespace(config=CONFIG), ref=granite,
                           arch=A, peak=V5E, window=w, timeline=[], ticks=[],
                           setup={}, engine_events=events, dispatch=[], trace=red)


def read(name, c):
    return harness.load_metric(name).read(c)


def test_decode_step_ms_finds_the_hybrid_decode_program():
    """``decode_step_ms`` finds the hybrid's decode program as it finds
    OLMo's: the unnamed program whose run count matches the decode events."""
    assert read("decode_step_ms", ctx()) == pytest.approx(30.0)
    assert read("decode_step_ms", ctx(trace=False)) is None


def test_hybrid_decode_roofline_reads_the_window_steps():
    """Three decode events in the window; their mean least bytes over the
    program's 30 ms and 819 GB/s."""
    steps = [(2, 40), (4, 90), (3, 70)]
    mean = sum(granite.decode_bytes(A, weights=2, state_bytes=a * 76_437_504,
                                    kv_tokens=p * 16) for a, p in steps) / 3
    got = read("hybrid_decode_roofline", ctx())
    assert got == pytest.approx(mean / 0.030 / 819e9 * 100)
    assert 0 < got < 100
    assert read("hybrid_decode_roofline", ctx(trace=False)) is None
    # an engine without recurrent layers gives it nothing to read
    assert read("hybrid_decode_roofline", ctx(state=False)) is None


def test_mfu_counts_the_hybrid_window_work():
    """``mfu`` counts the hybrid's window work by ``refs/granite.py``."""
    from chipbench import e2e

    c = ctx()
    r = e2e.ReqTimeline(0, 10.5, 300, 3)
    r.stamps = [11.0, 11.1, 11.2]
    c.timeline = [r]
    ops = granite.model_ops(A, prompt_lens=[300], decode_contexts=[301, 302])
    assert read("mfu", c) == pytest.approx(ops / (10.5 * 197e12) * 100)
