"""Kernel-level benchmark: Phi sparse matmul vs dense on the XLA CPU backend.

Wall-time on CPU is NOT the TPU score (that's §Roofline) — this validates the
*algorithmic* claim end-to-end on real silicon: at paper-like densities the
COO Phi path beats the dense matmul because the work is proportional to
nnz(L2), not M·K·N. Also times the Pallas kernels in interpret mode for
correctness-path latency bookkeeping.

Per-impl rows are forced through the ``kernels.dispatch`` execution policy
(per-call overrides — the benchmark is the A/B harness), plus one
``policy_pick`` row recording what the policy itself resolves for the bench
shape on this backend. ``--json PATH`` additionally writes a structured
``BENCH_kernels.json`` (per-impl latency + modelled HBM bytes + dispatch
decisions) which CI uploads as an artifact, so the perf trajectory is
tracked across PRs.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.assign import assign_patterns, pack_l2_coo_jit
from repro.core.patterns import (
    PhiConfig,
    active_pattern_sets,
    calibrate,
    pattern_usage,
    pattern_weight_products,
)
from repro.kernels import dispatch, ops, ref


def _time(fn, *args, reps: int = 5) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def main(json_path: str | None = None) -> list[str]:
    records: list[dict] = []

    def rec(name: str, us: float, derived: str, **extra) -> None:
        records.append({"name": name, "us_per_call": round(us, 1),
                        "derived": derived, **extra})

    rng = np.random.default_rng(0)
    M, K, N = 2048, 256, 512
    protos = (rng.random((24, K)) < 0.11).astype(np.float32)
    a = protos[rng.integers(0, 24, M)]
    a = jnp.asarray(np.abs(a - (rng.random((M, K)) < 0.02)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    pats = jnp.asarray(calibrate(np.asarray(a), PhiConfig(k=16, q=128, iters=10)))
    pwp = pattern_weight_products(pats, w)

    dense = jax.jit(lambda a, w: a @ w)
    t_dense = _time(dense, a, w)
    rec("dense_matmul", t_dense, "1.00x")

    idx, res = assign_patterns(a, pats)
    coo = pack_l2_coo_jit(res, int(0.08 * M * K))
    rowsv, cols, signs, _ = coo

    @jax.jit
    def phi_post_match(idx, rowsv, cols, signs, w, pwp):
        out1 = ref.l1_gather_ref(idx, pwp)
        out2 = ref.l2_spmm_ref(rowsv, cols, signs, w, M)
        return out1 + out2

    t_phi = _time(phi_post_match, idx, rowsv, cols, signs, w, pwp)
    rec("phi_coo_post_match", t_phi, f"{t_dense / t_phi:.2f}x_vs_dense"
        "_cpu (CPU XLA gather/scatter is scalar — see roofline for the"
        " TPU target; theoretical op ratio below)")

    from repro.core.assign import phi_stats
    from repro.core.opcount import matmul_opcounts
    st = phi_stats(np.asarray(a), np.asarray(pats))
    oc = matmul_opcounts(st, n=N)
    rec("phi_theoretical_acs", 0.0, f"{oc.speedup_over_bit:.2f}"
        f"x_fewer_ACs_than_bit_sparse_{oc.speedup_over_dense:.1f}x_vs_dense")

    @jax.jit
    def phi_full(a, w, pats, pwp):
        return dispatch.phi_matmul(a, w, pats, pwp, site="bench.coo",
                                   override="coo")

    t_full = _time(phi_full, a, w, pats, pwp)
    rec("phi_coo_incl_match", t_full, f"{t_dense / t_full:.2f}x_vs_dense_cpu",
        impl="coo")

    # interpret-mode pallas latencies (correctness path, not perf)
    t_matcher = _time(lambda: ops.matcher(a, pats))
    rec("pallas_matcher_interpret", t_matcher, "interpret")

    # ---- fused single-pass kernel vs the 3-kernel pipeline ----------------
    # Wall time on TPU is the real score; in interpret mode (CPU) both paths
    # run the Pallas interpreter so the decisive comparison is the modelled
    # HBM traffic (perfmodel.phi_kernel_traffic): fusion eliminates the
    # (M, T) index and (M, K) residual round-trips entirely.
    on_tpu = jax.default_backend() == "tpu"
    bench_m = M if on_tpu else 512          # interpreter is slow; shrink off-TPU
    ab = a[:bench_m]
    reps = 5 if on_tpu else 1

    t_3k = _time(lambda: dispatch.phi_matmul(ab, w, pats, pwp,
                                             site="bench.pallas",
                                             override="pallas"), reps=reps)
    t_fused = _time(lambda: dispatch.phi_matmul(ab, w, pats, pwp,
                                                site="bench.fused",
                                                override="fused"), reps=reps)
    mode = "tpu" if on_tpu else "interpret"
    rec(f"pallas_3kernel_{mode}", t_3k, f"{t_3k / t_fused:.2f}x_of_fused",
        impl="pallas")
    rec(f"pallas_fused_{mode}", t_fused, "1.00x", impl="fused")

    # What the execution policy itself resolves for this shape/backend —
    # the default every production call site now gets.
    pol = dispatch.get_policy()
    d = pol.resolve(site="bench.policy", m=bench_m, k_dim=K, n=N,
                    t=pats.shape[0], q=pats.shape[1])
    rec("policy_pick", 0.0, f"impl={d.impl}_reason={d.reason}",
        impl=d.impl, reason=d.reason)

    traffic = {}
    from repro.core.perfmodel import GemmShape, phi_kernel_traffic
    for tag, pwp_b in (("f32pwp", 4), ("int8pwp", 1)):
        tr = phi_kernel_traffic(GemmShape(M, K, N), k=16, q=128,
                                pwp_bytes_per_el=pwp_b)
        b3, bf = tr["three_kernel"], tr["fused"]
        traffic[tag] = {"three_kernel": b3.total, "fused": bf.total,
                        "ratio": b3.total / bf.total}
        rec(f"hbm_bytes_3kernel_{tag}", b3.total,
            f"idx+residual+coo_roundtrips="
            f"{b3.idx_bytes + b3.residual_bytes + b3.coo_bytes:.0f}B")
        rec(f"hbm_bytes_fused_{tag}", bf.total,
            f"{b3.total / bf.total:.2f}x_less_traffic_than_3kernel")

    # ---- large-K: the K-streaming fused kernel vs the old coo demotion ----
    # K=16384 is the shape class the PR 2 policy demoted to "coo" (the
    # all-resident fused kernel's VMEM gate); the streaming kernel keeps it
    # on the fused dataflow. Benchmarked at a small M/q so the interpret-
    # mode run stays cheap; the HBM model is the cross-backend claim.
    import os
    Ml, Kl, Nl, ql = (1024 if on_tpu else 128), 16384, 512, 16
    Tl = Kl // 16
    al = jnp.asarray((rng.random((Ml, Kl)) < 0.08), jnp.float32)
    wl = jnp.asarray(rng.standard_normal((Kl, Nl)), jnp.float32)
    patsl = jnp.asarray(calibrate(np.asarray(al),
                                  PhiConfig(k=16, q=ql, iters=3)))
    pwpl = pattern_weight_products(patsl, wl)
    dl = pol.resolve(site="bench.largeK_policy", m=Ml, k_dim=Kl, n=Nl,
                     t=Tl, q=ql)
    rec("policy_pick_largeK", 0.0, f"impl={dl.impl}_reason={dl.reason}",
        impl=dl.impl, reason=dl.reason,
        blocks=list(dl.blocks or ()), shape=[Ml, Kl, Nl])
    t_stream = _time(lambda: dispatch.phi_matmul(
        al, wl, patsl, pwpl, site="bench.stream", override="fused_stream"),
        reps=reps)
    rec("largeK_fused_stream_" + mode, t_stream, "1.00x",
        impl="fused_stream", shape=[Ml, Kl, Nl])
    prev_chunk = os.environ.get("PHI_CHUNK_ROWS")
    os.environ["PHI_CHUNK_ROWS"] = "128"   # keep the XLA scatter run small
    try:
        t_coo_lk = _time(lambda: dispatch.phi_matmul(
            al, wl, patsl, pwpl, site="bench.largeK_coo", override="coo"),
            reps=reps)
    finally:
        if prev_chunk is None:
            os.environ.pop("PHI_CHUNK_ROWS", None)
        else:
            os.environ["PHI_CHUNK_ROWS"] = prev_chunk
    rec("largeK_coo_" + mode, t_coo_lk,
        f"{t_coo_lk / t_stream:.2f}x_of_fused_stream", impl="coo",
        shape=[Ml, Kl, Nl])
    for tag, pwp_b in (("f32pwp", 4), ("int8pwp", 1)):
        trl = phi_kernel_traffic(GemmShape(Ml, Kl, Nl), k=16, q=ql,
                                 block_n=512, pwp_bytes_per_el=pwp_b)
        b3, bs = trl["three_kernel"], trl["fused_stream"]
        traffic[f"largeK_{tag}"] = {
            "three_kernel": b3.total, "fused_stream": bs.total,
            "ratio": b3.total / bs.total}
        rec(f"hbm_bytes_largeK_stream_{tag}", bs.total,
            f"{b3.total / bs.total:.2f}x_less_traffic_than_3kernel")

    # ---- pattern-usage skew: the PWP-prefetching kernel -------------------
    # Zipf-distributed pattern references (p ∝ 1/rank², the skew class the
    # paper's 27.73% PWP-usage measurement comes from): the calibration
    # histogram shows a small hot set and only the referenced fraction of
    # the PWP bank reaches VMEM. The policy resolves fused_prefetch off the
    # chip and plain fused on a TPU; the timed row forces fused_prefetch.
    qz = 128
    Mz, Kz, Nz = (2048 if on_tpu else 256), 64, 256
    zprob = 1.0 / (np.arange(qz) + 1.0) ** 2
    zprob /= zprob.sum()
    zprotos = (rng.random((qz, Kz)) < 0.25).astype(np.float32)
    az = np.abs(zprotos[rng.choice(qz, Mz, p=zprob)]
                - (rng.random((Mz, Kz)) < 0.02)).astype(np.float32)
    az = jnp.asarray(az, jnp.float32)
    wz = jnp.asarray(rng.standard_normal((Kz, Nz)), jnp.float32)
    patsz = jnp.asarray(calibrate(np.asarray(az),
                                  PhiConfig(k=16, q=qz, iters=6)))
    pwpz = pattern_weight_products(patsz, wz)
    usage = pattern_usage(np.asarray(az), np.asarray(patsz))
    active, usage_frac = active_pattern_sets(usage)
    p_active = 0 if active is None else int(active.shape[-1])
    dz = pol.resolve(site="bench.skew_policy", m=Mz, k_dim=Kz, n=Nz,
                     t=patsz.shape[0], q=qz, usage=usage)
    rec("policy_pick_skew", 0.0, f"impl={dz.impl}_reason={dz.reason}",
        impl=dz.impl, reason=dz.reason, shape=[Mz, Kz, Nz],
        usage_ratio=round(usage_frac, 4), p_active=p_active)
    t_pref = _time(lambda: dispatch.phi_matmul(
        az, wz, patsz, pwpz, site="bench.prefetch",
        override="fused_prefetch", usage=usage), reps=reps)
    rec("skew_fused_prefetch_" + mode, t_pref, "1.00x",
        impl="fused_prefetch", shape=[Mz, Kz, Nz])
    t_fused_z = _time(lambda: dispatch.phi_matmul(
        az, wz, patsz, pwpz, site="bench.skew_fused", override="fused"),
        reps=reps)
    rec("skew_fused_" + mode, t_fused_z,
        f"{t_fused_z / t_pref:.2f}x_of_fused_prefetch", impl="fused",
        shape=[Mz, Kz, Nz])
    for tag, pwp_b in (("f32pwp", 4), ("int8pwp", 1)):
        trz = phi_kernel_traffic(GemmShape(Mz, Kz, Nz), k=16, q=qz,
                                 pwp_bytes_per_el=pwp_b,
                                 pwp_usage=usage_frac)
        bf, bp = trz["fused"], trz["fused_prefetch"]
        traffic[f"skew_{tag}"] = {
            "fused": bf.total, "fused_prefetch": bp.total,
            "pwp_usage": usage_frac,
            "pwp_ratio": bp.pwp_bytes / bf.pwp_bytes,
            "ratio": bf.total / bp.total}
        rec(f"hbm_bytes_skew_prefetch_{tag}", bp.total,
            f"pwp_stream_x{bp.pwp_bytes / bf.pwp_bytes:.2f}_of_fused")

    # ---- mesh-aware SPMD dispatch: shard_map body keeps the fused path ----
    # The pre-PR-6 policy blanket-demoted every SPMD call to coo. Inside a
    # shard_map body the operands are per-shard local arrays, so the policy
    # re-gates on the local shape (spmd_local_* reasons). A one-device
    # shard_map records the decision row; the HBM model quantifies the
    # per-device win of an 8-way row-parallel shard of the bench shape.
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    smesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    f_spmd = shard_map(lambda a_, w_: dispatch.phi_matmul(
        a_, w_, pats, pwp, site="bench.spmd"),
        mesh=smesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
    t_spmd = _time(lambda: f_spmd(ab, w), reps=reps)
    dsp = pol.last_decision("bench.spmd")
    rec("spmd_shard_map_" + mode, t_spmd,
        f"impl={dsp.impl}_reason={dsp.reason}", impl=dsp.impl,
        reason=dsp.reason, shape=[bench_m, K, N], shards=dsp.shards)
    from repro.core.perfmodel import phi_sharded_traffic
    for tag, pwp_b in (("f32pwp", 4), ("int8pwp", 1)):
        sh8 = phi_sharded_traffic(GemmShape(M, K, N), shards=8,
                                  row_parallel=True, k=16, q=128,
                                  pwp_bytes_per_el=pwp_b)
        traffic[f"sharded8_{tag}"] = {
            "fused": sh8["fused"].total, "fused_impl": sh8["fused_impl"],
            "coo_demotion": sh8["coo"], "psum_bytes": sh8["psum_bytes"],
            "ratio": sh8["coo"] / sh8["fused"].total}
        rec(f"hbm_bytes_sharded8_{tag}", sh8["fused"].total,
            f"{sh8['coo'] / sh8['fused'].total:.2f}"
            "x_less_per_device_than_coo_demotion")

    # ---- Phi-sparse attention: the spiking-transformer hot path -----------
    # Binary spike Q/K make the flash score blocks Phi matmuls (L1 pattern
    # gather + L2 residual, kernels/phi_attention.py); the policy resolves
    # phi_flash for spike sites and keeps dense flash for LM-style dense
    # Q/K. Wall rows are the A/B through the policy; the gated claim is the
    # modelled HBM traffic at the paper Table-4 residual densities.
    from repro.core.perfmodel import phi_attention_traffic
    from repro.models import flash as flash_mod
    Ba, Sa, Ha, Da = 1, 256, 2, 64
    qa = jnp.asarray(rng.random((Ba, Sa, Ha, Da)) < 0.1, jnp.float32)
    ka = jnp.asarray(rng.random((Ba, Sa, Ha, Da)) < 0.1, jnp.float32)
    va = jnp.asarray(rng.random((Ba, Sa, Ha, Da)) < 0.1, jnp.float32)
    patsa = jnp.asarray(calibrate(
        np.asarray(ka).reshape(-1, Da), PhiConfig(k=16, q=64, iters=6)))
    da = pol.resolve_attention(site="bench.attn_spike", s=Sa, d=Da, heads=Ha,
                               batch=Ba, t=patsa.shape[0], q=patsa.shape[1],
                               kp=patsa.shape[2], spike_qk=True,
                               has_patterns=True)
    rec("policy_pick_attn_spike", 0.0, f"impl={da.impl}_reason={da.reason}",
        impl=da.impl, reason=da.reason, shape=[Ba, Sa, Ha, Da],
        blocks=list(da.blocks or ()))
    dd = pol.resolve_attention(site="bench.attn_dense", s=Sa, d=Da, heads=Ha,
                               batch=Ba, spike_qk=False, has_patterns=False)
    rec("policy_pick_attn_dense", 0.0, f"impl={dd.impl}_reason={dd.reason}",
        impl=dd.impl, reason=dd.reason, shape=[Ba, Sa, Ha, Da])
    bqa, bkva = da.blocks
    t_attn_phi = _time(lambda: pol.attention(
        qa, ka, va, patsa, site="bench.attn_phi", spike_qk=True), reps=reps)
    rec("attn_phi_flash_" + mode, t_attn_phi, "1.00x", impl="phi_flash",
        shape=[Ba, Sa, Ha, Da])
    t_attn_dense = _time(lambda: flash_mod.flash_attention(
        qa, ka, va, False, None, None, bqa, bkva), reps=reps)
    rec("attn_dense_flash_" + mode, t_attn_dense,
        f"{t_attn_dense / t_attn_phi:.2f}x_of_phi_flash", impl="flash",
        shape=[Ba, Sa, Ha, Da])
    # input spike density -> Table-4 L2⁺+L2⁻ residual density (PAPER_RANDOM)
    attn_table4 = {0.05: 0.026, 0.10: 0.034, 0.20: 0.068}
    for dens, l2 in attn_table4.items():
        tra = phi_attention_traffic(Sa, Da, heads=Ha, batch=Ba, k=16,
                                    q=int(patsa.shape[1]), block_q=bqa,
                                    block_kv=bkva, l2_density=l2)
        traffic[f"attn_p{int(dens * 100):02d}"] = tra
        rec(f"hbm_bytes_attn_p{int(dens * 100):02d}", tra["phi_flash"],
            f"{tra['phi_attn_ratio']:.2f}x_less_traffic_than_dense_flash")

    if json_path:
        jax.effects_barrier()   # flush policy telemetry callbacks
        payload = {
            "schema": 5,
            "backend": jax.default_backend(),
            "shape": {"m": M, "k": K, "n": N, "bench_m": bench_m},
            "sharded_shape": {"m": M, "k": K, "n": N, "shards": 8,
                              "row_parallel": True},
            "large_k_shape": {"m": Ml, "k": Kl, "n": Nl},
            "skew_shape": {"m": Mz, "k": Kz, "n": Nz, "q": qz,
                           "pwp_usage": round(usage_frac, 6),
                           "p_active": p_active},
            "attn_shape": {"b": Ba, "s": Sa, "h": Ha, "d": Da,
                           "block_q": bqa, "block_kv": bkva},
            "rows": records,
            # primary-shape rows only (large-K rows carry a "shape" key and
            # would otherwise clobber the per-impl summary)
            "per_impl_us": {r["impl"]: r["us_per_call"]
                            for r in records
                            if "impl" in r and r["us_per_call"]
                            and "shape" not in r},
            "hbm_model_bytes": traffic,
            "dispatch_decisions": [
                {"site": s, "impl": i, "reason": r, "traces": n}
                for (s, i, r), n in sorted(pol.decisions().items())],
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)

    return [ "kernels,name,us_per_call,derived" ] + [
        f"kernels,{r['name']},{r['us_per_call']:.1f},{r['derived']}"
        for r in records]


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", nargs="?", const="BENCH_kernels.json",
                    default=None, metavar="PATH",
                    help="also write structured results (default path "
                         "BENCH_kernels.json when the flag is given bare)")
    args = ap.parse_args()
    print("\n".join(main(json_path=args.json)))
