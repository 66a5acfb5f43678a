"""Tests for repro.analysis — the analyzer itself is part of the gated
surface: every rule must fire on a known-bad fixture with the right rule id,
and every production lowering must pass clean."""
import json
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.contracts import (
    CounterSpec,
    actual_vmem_bytes,
    check_counters,
    check_coverage,
    check_padded_extent,
    check_vmem_model,
    jaxpr_dims,
    trace_abstract,
)
from repro.analysis.lint import lint_source
from repro.analysis.registry import (
    ATTN_CASES,
    CONTRACTS,
    MATMUL_CASES,
    run_contracts,
)


# ------------------------------------------------------- known-bad fixtures --
def _tail_dropping_call(x):
    """Fixture: the PR-7 bug class — grid floors S // block on an unpadded
    operand, silently truncating the tail rows."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    S, D = x.shape
    b = 128
    return pl.pallas_call(
        kernel, grid=(S // b,),
        in_specs=[pl.BlockSpec((b, D), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((b, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x)


def test_fixture_tail_dropping_grid_flagged():
    x = jax.ShapeDtypeStruct((600, 64), jnp.float32)
    _, recs = trace_abstract(_tail_dropping_call, x)
    found = list(check_coverage(recs[0], lowering="fixture", case="tail"))
    rules = {f.rule for f in found}
    assert rules == {"PHI-COV-GRID"}, found
    # both the unread tail input block and the unwritten output block
    assert {f.detail for f in found} == {"in0", "out0"}


def _f32_counter_call(x):
    """Fixture: the PR-3 bug class — an f32 audit counter whose per-block
    bound exceeds the 2**24 exact-integer range."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref, c_ref):
        o_ref[...] = x_ref[...]
        c_ref[0] = jnp.sum(x_ref[...])          # f32 add-reduction counter

    M, K = x.shape
    return pl.pallas_call(
        kernel, grid=(1,),
        in_specs=[pl.BlockSpec((M, K), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((M, K), lambda i: (0, 0)),
                   pl.BlockSpec((1,), lambda i: (0,))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.float32)],
        interpret=True)(x)


def test_fixture_f32_counter_flagged():
    x = jax.ShapeDtypeStruct((4096, 8192), jnp.float32)  # 2**25 elements
    _, recs = trace_abstract(_f32_counter_call, x)
    spec = (CounterSpec(out_index=1, name="cnt",
                        bound=lambda r: r.data_operands[0].shape[0]
                        * r.data_operands[0].shape[1]),)
    found = list(check_counters(recs[0], spec, lowering="fixture",
                                case="acc"))
    assert [f.rule for f in found] == ["PHI-ACC-WIDTH"]
    # int32 holds the same bound fine
    _, recs2 = trace_abstract(_f32_counter_call,
                              jax.ShapeDtypeStruct((64, 64), jnp.float32))
    assert not list(check_counters(recs2[0], spec, lowering="fixture",
                                   case="acc_small"))


def test_fixture_undersized_vmem_model_flagged():
    from repro.kernels import ops

    case = MATMUL_CASES[0]
    bm, bn = ops.autotune_fused_blocks(case.M, case.K, case.N, case.q,
                                       case.T)
    a = jax.ShapeDtypeStruct((case.M, case.K), jnp.float32)
    pats = jax.ShapeDtypeStruct((case.T, case.q, case.k), jnp.float32)
    pwp = jax.ShapeDtypeStruct((case.T, case.q + 1, case.N), jnp.float32)
    w = jax.ShapeDtypeStruct((case.K, case.N), jnp.float32)
    _, recs = trace_abstract(
        lambda a_, p_, pw_, w_: ops.phi_fused(a_, p_, pw_, w_,
                                              block_m=bm, block_n=bn),
        a, pats, pwp, w)
    actual = actual_vmem_bytes(recs[0])
    assert actual > 0
    found = list(check_vmem_model(recs[0], actual // 2, lowering="fixture",
                                  case="vm"))
    assert [f.rule for f in found] == ["PHI-VMEM-MODEL"]
    # the real model bounds the real kernel
    assert not list(check_vmem_model(
        recs[0], ops._fused_vmem_bytes(bm, bn, case.K, case.T, case.q),
        lowering="fixture", case="vm_ok"))


def test_fixture_floor_truncation_has_no_pad_evidence():
    """PHI-COV-PAD: a floor-truncating jnp lowering never materializes the
    padded extent; the pad-and-mask idiom does."""
    def floored(x):                      # drops the tail — PR-7 shape class
        S = x.shape[0]
        return x[: (S // 128) * 128].reshape(S // 128, 128, -1).sum(1)

    def padded(x):
        S = x.shape[0]
        pad = (-S) % 128
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        return xp.reshape((S + pad) // 128, 128, -1).sum(1)

    x = jax.ShapeDtypeStruct((600, 64), jnp.float32)
    bad = list(check_padded_extent(jaxpr_dims(floored, x), {"seq": 640},
                                   lowering="fixture", case="floor"))
    assert [f.rule for f in bad] == ["PHI-COV-PAD"]
    assert not list(check_padded_extent(jaxpr_dims(padded, x), {"seq": 640},
                                        lowering="fixture", case="pad"))


_DUP_PSPEC_SRC = textwrap.dedent("""
    from jax.sharding import PartitionSpec as P
    RULES = {"w": P("data", "data"), "b": P(None, "model")}
""")

_UNFLUSHED_SRC = textwrap.dedent("""
    import numpy as np
    from jax.experimental import io_callback

    _STATS = {}

    def record(step, value):
        io_callback(lambda v: _STATS.setdefault("x", []).append(np.asarray(v)),
                    None, value, ordered=False)

    def summarize():
        return sum(len(v) for v in _STATS.values())
""")

_FLUSHED_SRC = _UNFLUSHED_SRC.replace(
    "    return sum(",
    "    import jax\n    jax.effects_barrier()\n    return sum(")
assert _FLUSHED_SRC != _UNFLUSHED_SRC

_HWCONST_SRC = "E_MATCH_PJ = 2.0\nDRAM_GBPS = 64e9\n"

_TRACERBOOL_SRC = textwrap.dedent("""
    import jax.numpy as jnp

    def gate(x):
        if jnp.any(x > 0):
            return x
        return -x
""")


def test_fixture_duplicate_pspec_flagged():
    found = lint_source(_DUP_PSPEC_SRC, "fixture/pspec.py")
    assert [f.rule for f in found] == ["PHI-LINT-PSPEC-DUP"]
    assert "data" in found[0].message


def test_fixture_unflushed_io_callback_flagged():
    found = lint_source(_UNFLUSHED_SRC, "fixture/telemetry.py")
    assert [f.rule for f in found] == ["PHI-LINT-BARRIER"]
    assert "summarize" in found[0].symbol
    # the barrier-before-read version is clean
    assert not lint_source(_FLUSHED_SRC, "fixture/telemetry.py")


def test_fixture_hwconst_flagged_outside_home_only():
    found = lint_source(_HWCONST_SRC, "src/repro/sim/somewhere.py")
    assert sorted(f.symbol for f in found) == ["DRAM_GBPS", "E_MATCH_PJ"]
    assert {f.rule for f in found} == {"PHI-LINT-HWCONST"}
    assert not lint_source(_HWCONST_SRC, "src/repro/core/hwconst.py")


def test_fixture_tracer_bool_flagged():
    found = lint_source(_TRACERBOOL_SRC, "fixture/gate.py")
    assert [f.rule for f in found] == ["PHI-LINT-TRACERBOOL"]
    # dtype probes are concrete on tracers: not flagged
    assert not lint_source(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    if jnp.issubdtype(x.dtype, jnp.integer):\n"
        "        return x\n    return -x\n", "fixture/ok.py")


# ------------------------------------------------------ production surface --
def test_registry_covers_every_dispatch_impl():
    from repro.kernels.dispatch import ATTN_IMPLS, IMPLS

    covered = {impl for c in CONTRACTS for impl in c.impls}
    assert set(IMPLS) | set(ATTN_IMPLS) <= covered


def test_shape_matrix_includes_non_divisible_shapes():
    assert any(c.M % 128 for c in MATMUL_CASES)
    assert any(c.S % 128 for c in ATTN_CASES)


@pytest.mark.parametrize("contract", CONTRACTS, ids=lambda c: c.name)
def test_production_lowerings_pass_clean(contract):
    findings = run_contracts(names=(contract.name,))
    assert findings == [], [f.key for f in findings]


def test_production_tree_lints_clean():
    from pathlib import Path

    from repro.analysis.lint import lint_paths

    root = Path(__file__).resolve().parents[1]
    assert lint_paths(root) == []


def test_lint_scope_includes_obs_package():
    """The default lint walk must cover ``src/repro/obs`` — the obs layer's
    io_callback-fed metric stores are exactly what PHI-LINT-BARRIER guards
    (a reader without ``jax.effects_barrier()`` under-counts)."""
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    walked = sorted(p.relative_to(root).as_posix()
                    for p in (root / "src" / "repro").rglob("*.py"))
    assert "src/repro/obs/metrics.py" in walked
    assert "src/repro/obs/drift.py" in walked
    assert "src/repro/obs/trace.py" in walked


def test_vmem_reconstruction_nonzero_for_gated_lowerings():
    """The VMEM cross-check must not pass vacuously: the traced records of
    every byte-model-gated lowering reconstruct a positive working set."""
    from repro.kernels import ops

    case = MATMUL_CASES[0]
    bm, bn, gt = ops.autotune_stream_blocks(case.M, case.K, case.N, case.q,
                                            case.T)
    a = jax.ShapeDtypeStruct((case.M, case.K), jnp.float32)
    pats = jax.ShapeDtypeStruct((case.T, case.q, case.k), jnp.float32)
    pwp = jax.ShapeDtypeStruct((case.T, case.q + 1, case.N), jnp.float32)
    w = jax.ShapeDtypeStruct((case.K, case.N), jnp.float32)
    _, recs = trace_abstract(
        lambda a_, p_, pw_, w_: ops.phi_fused_stream(
            a_, p_, pw_, w_, block_m=bm, block_n=bn, group_t=gt),
        a, pats, pwp, w)
    actual = actual_vmem_bytes(recs[0])
    assert actual > 0
    # double-buffered scratch dominates the streaming working set
    assert recs[0].scratch, "native stream path must declare scratch"


# ------------------------------------------------------------ baseline/CLI --
def test_baseline_requires_justifications(tmp_path):
    from repro.analysis.__main__ import load_baseline

    p = tmp_path / "baseline.json"
    p.write_text(json.dumps([{"key": "PHI-LINT-HWCONST:x.py:FREQ"}]))
    allow, bad = load_baseline(p)
    assert allow == {} and len(bad) == 1

    p.write_text(json.dumps([{"key": "PHI-LINT-HWCONST:x.py:FREQ",
                              "justification": "vendored table, documented"}]))
    allow, bad = load_baseline(p)
    assert bad == [] and "PHI-LINT-HWCONST:x.py:FREQ" in allow


def test_committed_baseline_entries_all_justified():
    from repro.analysis.__main__ import load_baseline

    _, bad = load_baseline()
    assert bad == []


def test_cli_reports_live_and_exits_nonzero(tmp_path, monkeypatch):
    """End-to-end: a lint finding in a scanned tree → exit 1 + JSON report."""
    import repro.analysis.__main__ as main_mod

    root = tmp_path / "repo"
    (root / "src" / "repro").mkdir(parents=True)
    (root / "src" / "repro" / "bad.py").write_text(_DUP_PSPEC_SRC)
    monkeypatch.setattr(main_mod, "_REPO_ROOT", root)
    out = tmp_path / "report.json"
    rc = main_mod.main(["--layer", "lint", "--json", str(out)])
    assert rc == 1
    report = json.loads(out.read_text())
    assert report["summary"]["live"] == 1
    assert report["findings"][0]["rule"] == "PHI-LINT-PSPEC-DUP"
