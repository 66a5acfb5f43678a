# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# Phi's hot spot IS a custom pipeline (paper Sec. 4); lowerings here:
#   matcher.py / phi_gather.py / phi_spmm.py — the 3-kernel pipeline
#   phi_fused.py — single-pass fused kernel (match + L1 + L2 in VMEM),
#                  all-resident, K-streaming (group grid axis) and
#                  PWP-prefetching (per-stripe compact bank) variants
#   lif.py — LIF neuron update
#   paged_attention.py — one-token decode attention over the paged KV pool
#   ops.py — padded/jit'd public wrappers + impl dispatch (phi_matmul)
#   ref.py — pure-jnp oracles
from repro.kernels.phi_fused import (  # noqa: F401
    phi_fused_pallas,
    phi_fused_prefetch_pallas,
    phi_fused_stream_pallas,
)

__all__ = ["phi_fused_pallas", "phi_fused_prefetch_pallas",
           "phi_fused_stream_pallas"]
