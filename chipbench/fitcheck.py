"""Fit check without the chip: compile a configuration's paged decode step
and its largest prefill for a described TPU v5e and print what each
program needs on the device beside what the configuration keeps there.

    JAX_PLATFORMS=cpu python3 chipbench/fitcheck.py olmo_1b olmo_1b_phi

Nothing runs. The execution policy is told the backend is a TPU, so the
programs hold the lowerings the chip would run. A compile for a described
chip is written to no cache here.
"""
import os
import sys
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16e9


def fit(name: str, topo_name: str = "v5e:2x2") -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness
    from repro.distributed.sharding import is_spec
    from repro.kernels import dispatch, ops
    from repro.models import model

    dispatch._backend = lambda: "tpu"
    ops._interpret = lambda: False
    c = harness.load_config(name)
    cfg = harness.program_config(c, False, harness.load_reference(c))
    e = c["engine"]
    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu",
                                     topology_name=topo_name).devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                         sharding=chip),
                          model.lm_specs(cfg), is_leaf=is_spec)
    pools = jax.tree.map(sds, model.paged_state_specs(cfg, e["num_pages"],
                                                      e["page_size"]))
    B, lp = e["slots"], e["max_context"] // e["page_size"]
    i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=chip)
    out = {}
    dec = jax.jit(partial(model.decode_step_paged, cfg)).lower(
        params, i32((B,)), i32((B,)), pools, i32((B, lp))).compile()
    pre = jax.jit(partial(model.prefill_padded, cfg)).lower(
        params, {"tokens": i32((1, e["max_context"]))}, i32((1,))).compile()
    for prog, comp in (("decode", dec), ("prefill", pre)):
        m = comp.memory_analysis()
        out[prog] = {"arguments": m.argument_size_in_bytes,
                     "outputs": m.output_size_in_bytes,
                     "temps": m.temp_size_in_bytes,
                     "aliased": m.alias_size_in_bytes}
    kept = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params)) + \
        sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pools))
    out["kept_bytes"] = kept
    worst = max(out[p]["temps"] + out[p]["outputs"] - out[p]["aliased"]
                for p in ("decode", "prefill"))
    out["peak_estimate"] = kept + worst
    out["fits"] = out["peak_estimate"] < HBM_BYTES
    return out


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    ok = True
    for name in (argv if argv is not None else sys.argv[1:]):
        r = fit(name)
        print(name, r, flush=True)
        ok &= r["fits"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
