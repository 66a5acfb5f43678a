"""Phi execution-policy layer: context-aware impl dispatch.

The model layer never names a kernel lowering. Every production
``phi_matmul`` call site routes through a :class:`PhiExecutionPolicy`,
which resolves the impl **per call** — the software analogue of the Phi
ASIC picking its execution strategy from the workload context (paper
Sec. 4) rather than baking it into the model definition.

Resolution order (first match wins):

  1. per-call override        — benchmarks / oracle comparisons;
  2. configured override      — ``PhiConfig.impl`` (``--phi-impl`` CLI flag)
                                or the ``PHI_IMPL`` env var; a Pallas-based
                                override (fused/pallas) is demoted to "coo"
                                inside an SPMD region, because honoring it
                                there would fail to compile;
  3. SPMD gate                — mesh-aware. Inside a *pjit-traced* SPMD
                                region (explicit ``spmd_region`` annotation
                                or an active logical-axis mesh, with no
                                shard_map axis env) the Pallas kernels
                                cannot be partitioned by the SPMD pipeline
                                → "coo" (pure XLA). Inside a ``shard_map``
                                *body*, however, every operand is already
                                the per-shard local slice and a Pallas call
                                runs unpartitioned on it — so the policy
                                re-gates on the local (M, K_loc, N_loc)
                                shape and keeps the fused lowerings
                                (``spmd_local_*`` reasons, with the
                                cooperating shard count recorded on the
                                decision), demoting to "coo" only for
                                transforms or shards whose local shape
                                busts even the streaming VMEM budget;
  4. transform gate           — under autodiff or vmap tracing the Pallas
                                kernels have no VJP/batching rule → "coo"
                                (differentiable gather/scatter XLA path);
  5. launch-cost crossover    — on the native TPU backend, tiny-M calls
                                (decode steps) whose modelled XLA-path bytes
                                undercut the cheapest fused lowering plus one
                                kernel launch → "coo": the fused kernels
                                stream the full PWP bank and weight stripe
                                per M-stripe regardless of M, so at tiny M
                                the fixed streams plus the launch overhead
                                (``perfmodel.PALLAS_LAUNCH_BYTES``) dominate;
  6. usage gate               — off the native backend, when the call
                                site has a calibration pattern-usage
                                histogram showing skew
                                (``patterns.active_pattern_sets``) and the
                                compact working set fits VMEM →
                                "fused_prefetch": per-M-stripe compact
                                banks, only referenced PWP rows reach VMEM.
                                On "tpu" the banks are gathered in HBM
                                first, so skew alone never picks it there
                                (override only);
  7. shape gate               — the fused kernel holds a (bm, K) activation
                                block plus a (K, bn) weight stripe in VMEM;
                                shapes where even the smallest block config
                                busts the VMEM budget → "fused_stream" (the
                                K-streaming fused kernel: only a group of
                                K-partitions resident, pipelined HBM→VMEM
                                copies); only shapes where even
                                streaming busts VMEM (pathological pattern
                                counts) → "coo";
  8. default                  — "fused", the fastest single-device lowering
                                (native on TPU, interpret mode elsewhere),
                                with blocks from ``autotune_fused_blocks``.

Telemetry: dispatch decisions are recorded at trace time (per site, impl,
reason); the fused kernel's per-M-block ``l2_nnz`` audit counters are
aggregated at run time via ``io_callback`` and converted by
``core.perfmodel.packer_budget_report`` into the static capacity an ASIC
packer (or the budgeted coo/pallas lowerings) would have needed to run the
same workload drop-free.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Any

import jax
import numpy as np

from repro.obs import trace as obs_trace
from repro.utils import log

IMPLS = ("fused", "fused_stream", "fused_prefetch", "pallas", "coo", "ref")
# Attention lowerings (PR 7): "phi_flash" = pattern-hierarchical flash
# (kernels/phi_attention.py; Pallas kernel, or its pjit-safe pure-XLA
# fallback when the reason carries an "_xla" suffix); "flash" = the dense
# blockwise lowering in models/flash.py. Only binary spike Q/K sites with a
# calibrated pattern bank resolve "phi_flash" — dense LM attention keeps
# "flash".
ATTN_IMPLS = ("phi_flash", "flash")
_PALLAS_IMPLS = ("fused", "fused_stream", "fused_prefetch", "pallas")
# emit the l2_nnz audit counter
_FUSED_IMPLS = ("fused", "fused_stream", "fused_prefetch")
_CKPT_KEY = "phi_impl"
_USAGE_KEY = "phi_usage"

_tls = threading.local()


def _backend() -> str:
    """Backend the policy reasons about (module-level so tests can pin a
    native backend without owning TPU hardware)."""
    return jax.default_backend()


# ----------------------------------------------------------- context probes ---
def _axis_env_sizes() -> dict:
    """Named-axis sizes in scope: non-empty exactly inside a shard_map/pmap
    body trace."""
    from jax._src.core import get_axis_env
    return dict(get_axis_env().axis_sizes)


def _axis_env_nonempty() -> bool:
    """True inside a shard_map/pmap body trace (named axes are in scope)."""
    return bool(_axis_env_sizes())


def _axis_env_shards() -> int:
    """Device count cooperating in the innermost shard_map/pmap axis env
    (the product of the named-axis sizes)."""
    out = 1
    for size in _axis_env_sizes().values():
        out *= int(size)
    return out


@contextlib.contextmanager
def spmd_region():
    """Explicitly mark a dynamic extent as SPMD (the pjit step builders wrap
    their traced bodies with this, belt-and-braces over the mesh probe)."""
    prev = getattr(_tls, "spmd", 0)
    _tls.spmd = prev + 1
    try:
        yield
    finally:
        _tls.spmd = prev


def in_spmd_region() -> bool:
    """True when the caller is being traced inside a pjit/shard_map SPMD
    region: an explicit ``spmd_region`` annotation, an active logical-axis
    mesh (the pjit step builders trace under ``sharding.use_rules``), or a
    shard_map/pmap axis environment."""
    if getattr(_tls, "spmd", 0):
        return True
    from repro.distributed.sharding import current_mesh
    if current_mesh() is not None:
        return True
    return _axis_env_nonempty()


@contextlib.contextmanager
def autodiff_region():
    """Mark a dynamic extent whose trace will be differentiated. The train
    step builders wrap their loss+grad computation with this: under
    scan-over-layers the body is traced *before* the JVP transform is
    applied, so per-call tracer sniffing cannot see the upcoming backward
    pass — the explicit signal keeps the whole extent on the
    differentiable XLA lowering."""
    prev = getattr(_tls, "autodiff", 0)
    _tls.autodiff = prev + 1
    try:
        yield
    finally:
        _tls.autodiff = prev


def in_autodiff_region() -> bool:
    """True inside an ``autodiff_region`` context (grad/vjp tracing): the
    Pallas lowerings define no VJP, so the policy must pick an XLA path."""
    return bool(getattr(_tls, "autodiff", 0))


def _under_transform(*arrays: Any) -> bool:
    """True when any operand is traced by vmap (its tracer carries a batch
    dim) or by JVP / linearize, as under ``grad`` (its tracer carries a
    tangent): the Pallas kernels define no VJP or batching rule, so those
    transforms need the XLA path. Staging tracers (``jit``, ``scan``,
    ``shard_map`` bodies) carry neither."""
    return any(isinstance(x, jax.core.Tracer)
               and (hasattr(x, "batch_dim") or hasattr(x, "tangent"))
               for x in arrays)


# ---------------------------------------------------------------- decisions ---
@dataclasses.dataclass(frozen=True)
class Decision:
    """One resolved dispatch: which lowering runs at a call site and why."""

    impl: str
    reason: str
    site: str
    shape: tuple            # (M, K, N, T, q)
    backend: str
    # fused/fused_prefetch: (block_m, block_n); fused_stream: (block_m,
    # block_n, group_t) — the K-group depth rides along so telemetry can
    # report it; else None.
    blocks: tuple | None = None
    # fused_prefetch: measured PWP-bank usage fraction (P+1)/(q+1) and the
    # static gather-buffer size P from the calibration histogram.
    usage_ratio: float | None = None
    p_active: int | None = None
    # fused_prefetch with runtime match telemetry: the (T, P) active sets
    # derived from the site's aggregated match histogram. When set, the
    # kernel gathers from these instead of running the trace-time
    # ``stripe_active_sets`` pre-pass (one less read of the activations);
    # None = pre-pass (the fallback, and the telemetry's source).
    runtime_sets: Any = None
    # SPMD-local resolution (shard_map body): the number of devices
    # cooperating on this call — ``shape`` is each shard's LOCAL problem,
    # so telemetry readers multiply by this to recover the global GEMM.
    # None outside shard_map.
    shards: int | None = None


class PhiExecutionPolicy:
    """Resolves ``impl`` per phi_matmul call and aggregates telemetry."""

    def __init__(self, override: str | None = None,
                 telemetry: bool = True) -> None:
        if override is None:
            override = os.environ.get("PHI_IMPL") or None
        if override is not None and override not in IMPLS:
            raise ValueError(f"unknown Phi impl override {override!r}; "
                             f"expected one of {IMPLS}")
        self.override = override
        self.telemetry = telemetry and os.environ.get("PHI_TELEMETRY") != "0"
        self._lock = threading.Lock()
        # Typed metric mirror of the telemetry below (obs/metrics.py): the
        # decision counts live in a labelled counter — decisions() / report()
        # stay as thin views over it. Decisions happen at trace time, so
        # under jit caching the counts reflect traces, not steps.
        from repro.obs.metrics import MetricsRegistry
        self.metrics = MetricsRegistry(namespace="phi")
        self._dec = self.metrics.counter(
            "dispatch_decisions", "trace-time dispatch resolutions",
            labelnames=("site", "impl", "reason"))
        # site -> most recent full Decision (incl. local shape + shards).
        self._last: dict[str, Decision] = {}
        # site -> runtime counters fed by the fused kernel's l2_nnz output.
        self._sites: dict[str, dict] = {}
        # site -> (T, q+1) calibration pattern-usage histogram. Registered
        # by the calibration paths (calibrate_lm_phi / snn PhiState) so the
        # usage gate can fire for traced call sites whose histogram cannot
        # ride as an operand (it must be concrete at trace time).
        self._usage: dict[str, np.ndarray] = {}

    # --------------------------------------------------------------- usage --
    def register_usage(self, site: str, usage: Any) -> None:
        """Attach a calibration pattern-usage histogram ((T, q+1) counts) to
        a dispatch site. Re-registration with the same shape accumulates
        (scan-over-layers call sites pool their layers' histograms)."""
        u = np.asarray(usage, np.int64)
        with self._lock:
            prev = self._usage.get(site)
            if prev is not None and prev.shape == u.shape:
                u = prev + u
            self._usage[site] = u

    def usage_for(self, site: str) -> np.ndarray | None:
        """The calibration pattern-usage histogram registered for ``site``
        ((T, q+1) int64 counts), or None if never calibrated."""
        with self._lock:
            return self._usage.get(site)

    def runtime_shards_for(self, site: str) -> int:
        """Mesh extent recorded for ``site``'s runtime counters (1 when the
        site has only executed outside shard_map, or not at all)."""
        jax.effects_barrier()   # flush in-flight telemetry callbacks
        with self._lock:
            return int(self._sites.get(site, {}).get("shards", 1))

    def runtime_usage_for(self, site: str) -> np.ndarray | None:
        """The site's aggregated *runtime* match histogram ((T, q+1) int64),
        fed by the prefetch pre-pass through :meth:`_record_nnz`. None until
        the site has executed (or when every observed row-partition was
        unmatched — there is nothing to derive gather sets from)."""
        jax.effects_barrier()   # flush in-flight telemetry callbacks
        with self._lock:
            hist = self._sites.get(site, {}).get("usage_runtime")
            if hist is None or hist[:, :-1].sum() <= 0:
                return None
            return hist.copy()

    def site_telemetry(self, prefix: str = "") -> list[dict]:
        """Scheduler-facing snapshot of every registered dispatch site.

        One row per site whose name starts with ``prefix``, each carrying
        the signals the serve scheduler scores on (``serve/scheduler.py``):

        * ``usage_ratio`` / ``p_active`` — calibration-histogram skew
          (``patterns.active_pattern_sets``): a low ratio means the site
          streams a small active slice of its PWP bank, i.e. the
          ``fused_prefetch`` path pays off and co-batched traffic shares
          the gathered rows;
        * ``warm`` / ``executions`` — whether the site has executed (a cold
          site's first trace pays the pre-pass; later traces reuse its
          runtime sets), and how often;
        * ``impl`` / ``reason`` — the most recent resolved Decision, if any;
        * ``drift_score`` — PSI between the site's calibration histogram and
          its aggregated runtime match histogram (``repro.obs.drift``), None
          until both exist — the bank-swap trigger signal;
        * ``shards`` — mesh extent of the runtime counters (1 off-mesh).

        Sites come from the calibration registry (:meth:`register_usage`),
        the runtime counters (:meth:`_record_nnz`) *and* the decision log,
        so the view covers calibrated-but-never-run sites and sites that
        resolved decisions without runtime counters.
        """
        jax.effects_barrier()   # flush in-flight telemetry callbacks
        from repro.core.patterns import active_pattern_sets
        from repro.obs.drift import site_drift
        rows: list[dict] = []
        with self._lock:
            # _last too: a site can have resolved decisions without ever
            # executing (telemetry off, or the call never ran) — the view
            # must still cover it (regression-tested edge case).
            names = sorted(set(self._usage) | set(self._sites)
                           | set(self._last))
            for site in names:
                if prefix and not site.startswith(prefix):
                    continue
                usage = self._usage.get(site)
                sets, ratio = (active_pattern_sets(usage)
                               if usage is not None else (None, 1.0))
                counters = self._sites.get(site)
                execs = 0 if counters is None else int(
                    counters.get("executions", 0))
                hist = None if counters is None else \
                    counters.get("usage_runtime")
                drift = None
                if usage is not None and hist is not None and hist.sum() > 0:
                    drift = float(site_drift(usage, hist))
                last = self._last.get(site)
                rows.append({
                    "site": site,
                    "usage_ratio": float(ratio),
                    "p_active": None if sets is None else int(sets.shape[-1]),
                    "skewed": sets is not None,
                    "warm": execs > 0,
                    "executions": execs,
                    "shards": 1 if counters is None else int(
                        counters.get("shards", 1)),
                    "drift_score": drift,
                    "impl": None if last is None else last.impl,
                    "reason": None if last is None else last.reason,
                })
        return rows

    # ------------------------------------------------------------- resolve --
    def resolve(self, *, site: str = "anon", m: int, k_dim: int, n: int,
                t: int, q: int, override: str | None = None,
                config_override: str | None = None,
                transform: bool = False, usage: Any = None) -> Decision:
        """Resolve the impl for one call. Override precedence: per-call
        ``override`` > ``config_override`` (``PhiConfig.impl`` threaded by
        the model layer) > the policy-level override (``PHI_IMPL`` env).

        ``usage`` is the call site's calibration pattern-usage histogram
        ((T, q+1) counts, host-side); defaults to whatever was registered
        for ``site`` via :meth:`register_usage`. A skewed histogram enables
        the ``fused_prefetch`` lowering.
        """
        from repro.core.patterns import active_pattern_sets
        from repro.kernels import ops

        for o in (override, config_override):
            if o is not None and o not in IMPLS:
                raise ValueError(f"unknown Phi impl override {o!r} at "
                                 f"site {site!r}; expected one of {IMPLS}")
        backend = _backend()
        shape = (m, k_dim, n, t, q)
        spmd = in_spmd_region()
        transform = transform or in_autodiff_region()
        # A shard_map body traces with *local* per-shard operands: a Pallas
        # call there runs unpartitioned on each shard's slice, so the fused
        # lowerings are executable and (m, k_dim, n, t) already ARE the
        # local shape to gate on. A pjit-traced region (explicit annotation
        # or mesh context, no axis env) sees global operands that XLA would
        # have to partition through the pallas_call — not supported → coo.
        spmd_local = spmd and not transform and _axis_env_nonempty()
        shards = _axis_env_shards() if spmd_local else None
        if usage is None:
            usage = self.usage_for(site)
        active_sets, usage_ratio = (active_pattern_sets(usage)
                                    if usage is not None else (None, 1.0))
        p_active = None if active_sets is None else int(active_sets.shape[-1])
        ov, which = next(
            ((o, lbl) for o, lbl in ((override, "call"),
                                     (config_override, "config"),
                                     (self.override, "policy"))
             if o is not None), (None, None))
        mode = "native" if backend == "tpu" else "interpret"
        # On the chip the compact banks are gathered by XLA in HBM before the
        # kernel runs, so the modelled PWP-byte saving does not happen there:
        # skew picks "fused_prefetch" only off the chip (interpret mode keeps
        # it exercised) and it stays an override on "tpu".
        auto_p = None if backend == "tpu" else p_active
        if ov is not None:
            # Overrides are honored only where they can actually execute: a
            # Pallas-based choice inside a pjit-traced SPMD region or a
            # differentiated/vmapped trace silently forces a failed compile
            # — demote. Inside a shard_map *body* (``spmd_local``) the
            # kernels run on the local shards, so the override goes through
            # the same VMEM gating as anywhere else. A "fused" choice whose
            # smallest block config busts VMEM streams its K axis instead
            # (same fused dataflow, group-resident), and only falls to
            # "coo" when even streaming doesn't fit. A "fused_prefetch"
            # choice needs a skewed usage histogram to size its gather
            # buffer — without one it runs the closest executable fused
            # lowering instead.
            if spmd and not spmd_local and ov in _PALLAS_IMPLS:
                d = Decision("coo", f"spmd_region_demotes_{ov}", site, shape,
                             backend)
            elif transform and ov in _PALLAS_IMPLS:
                d = Decision("coo", f"autodiff_demotes_{ov}", site, shape,
                             backend)
            elif ov == "fused_prefetch":
                gate = ops.fused_shape_viable(m, k_dim, n, t, q,
                                              p_active=p_active)
                if gate == "fused_prefetch":
                    d = Decision(ov, f"{which}_override", site, shape,
                                 backend)
                elif gate == "coo":
                    d = Decision("coo", "vmem_gate_demotes_fused_prefetch",
                                 site, shape, backend)
                elif p_active is not None:
                    # Skew WAS measured — the compact working set just
                    # busts VMEM; don't tell the operator to fix
                    # calibration when the budget is the cause.
                    d = Decision(gate, "vmem_gate_streams_fused_prefetch",
                                 site, shape, backend)
                else:                        # "fused" or "fused_stream"
                    d = Decision(gate, "no_skew_demotes_fused_prefetch",
                                 site, shape, backend)
            elif ov in _FUSED_IMPLS and (
                    gate := ops.fused_shape_viable(m, k_dim, n, t, q)) != ov:
                if gate == "coo":
                    d = Decision("coo", f"vmem_gate_demotes_{ov}", site,
                                 shape, backend)
                elif ov == "fused":          # gate == "fused_stream"
                    d = Decision("fused_stream", "vmem_gate_streams_fused",
                                 site, shape, backend)
                else:                        # "fused_stream" on a roomier
                    d = Decision(ov, f"{which}_override", site, shape,
                                 backend)    # shape: still executable
            else:
                d = Decision(ov, f"{which}_override", site, shape, backend)
        elif spmd and not spmd_local:
            d = Decision("coo", "spmd_region", site, shape, backend)
        elif spmd:
            # Mesh-aware SPMD resolution: re-gate on the per-shard local
            # shape and keep the fused dataflow wherever it fits; "coo"
            # only where even K-streaming busts the VMEM budget, or where
            # the launch-cost crossover says the local GEMM is too tiny.
            gate = ops.fused_shape_viable(m, k_dim, n, t, q,
                                          p_active=auto_p)
            if gate != "coo" and backend == "tpu" and \
                    ops.launch_cost_prefers_coo(m, k_dim, n, t, q):
                d = Decision("coo", "spmd_local_launch_cost", site, shape,
                             backend)
            elif gate == "coo":
                d = Decision("coo", "spmd_local_vmem_gate", site, shape,
                             backend)
            elif gate == "fused_prefetch":
                d = Decision("fused_prefetch",
                             f"spmd_local_prefetch_{mode}", site, shape,
                             backend)
            elif gate == "fused_stream":
                d = Decision("fused_stream", f"spmd_local_k_stream_{mode}",
                             site, shape, backend)
            else:
                d = Decision("fused", f"spmd_local_fused_{mode}", site,
                             shape, backend)
        elif transform:
            d = Decision("coo", "autodiff_or_vmap", site, shape, backend)
        else:
            gate = ops.fused_shape_viable(m, k_dim, n, t, q,
                                          p_active=auto_p)
            if gate != "coo" and backend == "tpu" and \
                    ops.launch_cost_prefers_coo(m, k_dim, n, t, q):
                # Cost crossover (native backend only — interpret-mode wall
                # time is meaningless, and CPU runs keep the Pallas kernels
                # exercised): at tiny M the fused kernels' fixed full-bank
                # streams plus one kernel launch lose to the XLA path.
                d = Decision("coo", "launch_cost_crossover", site, shape,
                             backend)
            elif gate == "coo":
                d = Decision("coo", "fused_vmem_gate", site, shape, backend)
            elif gate == "fused_prefetch":
                d = Decision("fused_prefetch",
                             f"pattern_usage_prefetch_{mode}", site, shape,
                             backend)
            elif gate == "fused_stream":
                d = Decision("fused_stream", f"vmem_gate_k_stream_{mode}",
                             site, shape, backend)
            else:
                d = Decision("fused", f"single_device_default_{mode}", site,
                             shape, backend)
        if d.impl == "fused":  # default or override-forced: autotune blocks
            d = dataclasses.replace(
                d, blocks=ops.autotune_fused_blocks(m, k_dim, n, q, t))
        elif d.impl == "fused_stream":
            d = dataclasses.replace(
                d, blocks=ops.autotune_stream_blocks(m, k_dim, n, q, t))
        elif d.impl == "fused_prefetch":
            d = dataclasses.replace(
                d, usage_ratio=usage_ratio, p_active=p_active,
                blocks=ops.autotune_prefetch_blocks(m, k_dim, n, q, t,
                                                    p_active))
            # Runtime match telemetry (aggregated by _record_nnz from the
            # pre-pass histograms of earlier executions) supplies the
            # gather sets directly — this trace skips the trace-time
            # stripe_active_sets pre-pass and its extra activation read.
            # Fallback: no telemetry yet -> pre-pass (which then feeds the
            # telemetry).
            rt_hist = self.runtime_usage_for(site)
            if (rt_hist is not None and d.p_active
                    and rt_hist.shape == (t, q + 1)):
                from repro.core.patterns import top_p_sets
                d = dataclasses.replace(
                    d, runtime_sets=top_p_sets(rt_hist, d.p_active),
                    reason=d.reason + "_runtime_sets")
        if shards is not None:
            # per-shard telemetry: ``shape`` is the local problem; every
            # decision resolved inside the shard_map body carries the
            # cooperating device count (overrides included).
            d = dataclasses.replace(d, shards=shards)
        self._record_decision(d)
        return d

    # --------------------------------------------------------- attention --
    def resolve_attention(self, *, site: str = "anon", s: int, d: int,
                          heads: int = 1, batch: int = 1, t: int = 0,
                          q: int = 0, kp: int = 0, spike_qk: bool = False,
                          has_patterns: bool = False,
                          override: str | None = None,
                          config_override: str | None = None,
                          transform: bool = False) -> Decision:
        """Resolve the attention lowering for one call site.

        The spike-input gate is declarative: the caller states whether its
        Q/K operands are binary spike tensors (``spike_qk``) — binarity is a
        value property invisible at trace time. Only spike sites with a
        calibrated pattern bank resolve ``"phi_flash"``; everything else —
        dense LM attention, autodiff/vmap traces (the Phi lowerings define
        no VJP; ``models/flash.py`` does), missing banks — keeps
        ``"flash"``. Inside a pjit-traced SPMD region the Phi path stays
        available through its pure-XLA fallback (reason suffix ``_xla``);
        a shard_map body re-gates the Pallas kernel on the local shape
        (``spmd_local_*``, shard count recorded) exactly like the matmul
        rows. ``Decision.shape`` maps the score GEMM:
        (batch·heads·s, d, s, t, q); ``Decision.blocks`` carries the
        (block_q, block_kv) both the Phi arm *and* a forced dense-flash arm
        must share for the bitwise A/B contract.
        """
        from repro.kernels import ops

        for o in (override, config_override):
            if o is not None and o not in ATTN_IMPLS:
                raise ValueError(
                    f"unknown attention impl override {o!r} at site "
                    f"{site!r}; expected one of {ATTN_IMPLS}")
        backend = _backend()
        shape = (batch * heads * s, d, s, t, q)
        # Off-TPU the Phi production path is the pure-XLA lowering, not the
        # interpret-mode Pallas kernel: only the XLA path shares the dense
        # flash accumulator *code*, which is what anchors the bitwise A/B
        # contract (the interpret kernel keeps scores exact but cannot track
        # XLA's fusion rounding ulp-for-ulp), and interpret mode is orders of
        # magnitude slower anyway. Tests drive the kernel directly.
        mode = "native" if backend == "tpu" else "xla"
        spmd = in_spmd_region()
        transform = transform or in_autodiff_region()
        spmd_local = spmd and not transform and _axis_env_nonempty()
        shards = _axis_env_shards() if spmd_local else None
        ov, which = next(
            ((o, lbl) for o, lbl in ((override, "call"),
                                     (config_override, "config"),
                                     (None, "policy"))
             if o is not None), (None, None))
        viable = has_patterns and ops.attn_shape_viable(s, d, t, q, kp)
        if ov == "flash":
            dec = Decision("flash", f"{which}_override", site, shape, backend)
        elif ov == "phi_flash":
            if transform:
                dec = Decision("flash", "autodiff_demotes_phi_flash", site,
                               shape, backend)
            elif not has_patterns:
                dec = Decision("flash", "no_patterns_demotes_phi_flash",
                               site, shape, backend)
            elif spmd and not spmd_local:
                dec = Decision("phi_flash", "spmd_region_phi_flash_xla",
                               site, shape, backend)
            elif not viable:
                dec = Decision("phi_flash", "vmem_gate_phi_flash_xla", site,
                               shape, backend)
            else:
                dec = Decision("phi_flash", f"{which}_override", site,
                               shape, backend)
        elif transform:
            dec = Decision("flash", "autodiff_keeps_flash", site, shape,
                           backend)
        elif not spike_qk:
            dec = Decision("flash", "dense_qk_keeps_flash", site, shape,
                           backend)
        elif not has_patterns:
            dec = Decision("flash", "no_patterns_keeps_flash", site, shape,
                           backend)
        elif spmd and not spmd_local:
            # pjit-traced SPMD region: a pallas_call cannot be partitioned,
            # but the pure-XLA Phi lowering can — keep the decomposition.
            dec = Decision("phi_flash", "spmd_region_phi_flash_xla", site,
                           shape, backend)
        elif spmd_local:
            if viable:
                dec = Decision("phi_flash", f"spmd_local_phi_flash_{mode}",
                               site, shape, backend)
            else:
                dec = Decision("phi_flash", "spmd_local_vmem_phi_flash_xla",
                               site, shape, backend)
        elif not viable:
            dec = Decision("phi_flash", "vmem_gate_phi_flash_xla", site,
                           shape, backend)
        else:
            dec = Decision("phi_flash", f"spike_qk_phi_flash_{mode}", site,
                           shape, backend)
        dec = dataclasses.replace(
            dec, blocks=ops.autotune_attn_blocks(s, d, t, q, kp))
        if shards is not None:
            dec = dataclasses.replace(dec, shards=shards)
        self._record_decision(dec)
        return dec

    def attention(self, q: jax.Array, k: jax.Array, v: jax.Array,
                  patterns: jax.Array | None = None, *,
                  site: str = "anon", causal: bool = False,
                  window: int | None = None, chunk: int | None = None,
                  spike_qk: bool = False, override: str | None = None,
                  config_override: str | None = None) -> jax.Array:
        """Policy-dispatched flash attention: q/k/v (B, S, H, D).

        ``patterns`` is the (T, qp, kp) bank calibrated on the site's K
        spike rows (None for uncalibrated/dense sites). Both lowerings run
        the blocks the decision carries, so a forced ``override="flash"``
        A/B arm is bit-identical to the resolved ``phi_flash`` one for
        binary Q/K.
        """
        from repro.kernels import ops
        from repro.models import flash as flash_mod

        B, S, H, D = q.shape
        t = qp = kp = 0
        if patterns is not None:
            t, qp, kp = np.asarray(patterns).shape[-3:]
        dec = self.resolve_attention(
            site=site, s=S, d=D, heads=H, batch=B, t=t, q=qp, kp=kp,
            spike_qk=spike_qk, has_patterns=patterns is not None,
            override=override, config_override=config_override,
            transform=_under_transform(q, k, v))
        bq, bkv = dec.blocks
        if dec.impl == "flash":
            return flash_mod.flash_attention(q, k, v, causal, window, chunk,
                                             bq, bkv)
        mode = "xla" if dec.reason.endswith("_xla") else "pallas"
        return ops.phi_flash_attention(
            q, k, v, patterns, causal=causal, window=window, chunk=chunk,
            block_q=bq, block_kv=bkv, impl=mode)

    def resolve_paged_decode(self, *, site: str, batch: int, heads: int,
                             kv_heads: int, head_dim: int, page_size: int,
                             logical_pages: int, dtype: Any) -> Decision:
        """Resolve the paged decode attention lowering for one call site:
        ``paged_kernel`` (``kernels/paged_attention.py``, the pages each
        slot holds) or ``gather`` (every slot's whole context view).

        The Pallas kernel on the native TPU backend for the shapes it takes
        (``paged_attention.unsupported``); else the ``gather`` lowering,
        which is also the production path off the TPU (the kernel's
        interpret mode is for tests) and inside a pjit-traced SPMD region,
        where a ``pallas_call`` cannot be partitioned. ``Decision.shape``
        is (batch·heads, head_dim, context, kv_heads, page_size);
        ``Decision.blocks`` carries the kernel's pages per block.
        """
        from repro.kernels import paged_attention

        backend = _backend()
        shape = (batch * heads, head_dim, logical_pages * page_size,
                 kv_heads, page_size)
        why = paged_attention.unsupported(page_size, kv_heads, head_dim,
                                          dtype)
        if backend != "tpu":
            dec = Decision("gather", f"{backend}_keeps_gather", site, shape,
                           backend)
        elif in_spmd_region():
            dec = Decision("gather", "spmd_region_keeps_gather", site, shape,
                           backend)
        elif why is not None:
            dec = Decision("gather", f"{why}_keeps_gather", site, shape,
                           backend)
        else:
            dec = Decision(
                "paged_kernel", "tpu_paged_kernel", site, shape, backend,
                blocks=(paged_attention.block_pages(page_size,
                                                    logical_pages),))
        self._record_decision(dec)
        return dec

    def _record_decision(self, d: Decision) -> None:
        first = self._dec.get(site=d.site, impl=d.impl, reason=d.reason) == 0
        self._dec.inc(site=d.site, impl=d.impl, reason=d.reason)
        with self._lock:
            self._last[d.site] = d
        tracer = obs_trace.get_tracer()
        if tracer is not None:
            # Host-side and trace-time only: the span cannot perturb the
            # traced computation (the obs_bench exactness contract).
            tracer.emit("dispatch", site=d.site, impl=d.impl, reason=d.reason,
                        shape=[int(x) for x in d.shape],
                        blocks=(None if d.blocks is None
                                else [int(b) for b in d.blocks]),
                        shards=d.shards)
        if first:
            log.info("phi dispatch: %s -> %s (%s, M=%d K=%d N=%d)",
                     d.site, d.impl, d.reason, *d.shape[:3])

    # ------------------------------------------------------------- execute --
    def matmul(self, a: jax.Array, w: jax.Array, patterns: jax.Array,
               pwp: jax.Array, *, site: str = "anon",
               override: str | None = None, config_override: str | None = None,
               nnz_budget: float = 0.08,
               gather_dtype: Any = None, pwp_scale: jax.Array | None = None,
               usage: Any = None) -> jax.Array:
        """Policy-dispatched ``phi_matmul``: resolve the impl from context,
        run it, and (fused path) stream the l2_nnz audit counters out.

        ``usage`` is the site's calibration pattern-usage histogram (host
        numpy, concrete at trace time); when omitted, the policy's registry
        (:meth:`register_usage`) is consulted for ``site``.
        """
        from repro.kernels import ops

        K = a.shape[-1]
        T, q, _ = patterns.shape
        N = w.shape[-1]
        M = int(np.prod(a.shape[:-1])) if a.ndim > 1 else 1
        if usage is None:
            usage = self.usage_for(site)
        # patterns must be sniffed too: a vmap that batches only the pattern
        # bank (per-layer pattern sets) otherwise dispatches to a Pallas
        # impl with no batching rule and fails to compile.
        d = self.resolve(site=site, m=M, k_dim=K, n=N, t=T, q=q,
                         override=override, config_override=config_override,
                         transform=(in_autodiff_region()
                                    or _under_transform(a, w, patterns, pwp)),
                         usage=usage)
        if d.impl not in _FUSED_IMPLS:
            return ops.phi_matmul(a, w, patterns, pwp, impl=d.impl,
                                  nnz_budget=nnz_budget,
                                  gather_dtype=gather_dtype,
                                  pwp_scale=pwp_scale)
        hist = None
        if d.impl == "fused":
            bm, bn = d.blocks
            group_t = 0                    # all K-partitions resident
            out, nnz = ops.phi_fused(a, patterns, pwp, w, pwp_scale=pwp_scale,
                                     block_m=bm, block_n=bn)
        elif d.impl == "fused_prefetch":
            bm, bn = d.blocks
            group_t = 0                    # all K-partitions resident
            if d.runtime_sets is not None:
                # aggregated runtime match telemetry supplies the gather
                # sets: no trace-time pre-pass, no extra activation read
                out, nnz = ops.phi_fused_prefetch(
                    a, patterns, pwp, w, p_active=d.p_active,
                    pwp_scale=pwp_scale, block_m=bm, block_n=bn,
                    runtime_sets=jax.numpy.asarray(d.runtime_sets))
            elif self.telemetry:
                # pre-pass fallback; its match histogram streams out below
                # and becomes the runtime telemetry later traces gather from
                out, nnz, hist = ops.phi_fused_prefetch(
                    a, patterns, pwp, w, p_active=d.p_active,
                    pwp_scale=pwp_scale, block_m=bm, block_n=bn,
                    return_hist=True)
            else:
                out, nnz = ops.phi_fused_prefetch(a, patterns, pwp, w,
                                                  p_active=d.p_active,
                                                  pwp_scale=pwp_scale,
                                                  block_m=bm, block_n=bn)
        else:
            bm, bn, group_t = d.blocks
            out, nnz = ops.phi_fused_stream(a, patterns, pwp, w,
                                            pwp_scale=pwp_scale,
                                            block_m=bm, block_n=bn,
                                            group_t=group_t)
        if self.telemetry:
            # Inside a shard_map body the callback fires once per shard
            # with that shard's local counters — so ``executions``/``rows``
            # aggregate per-shard work and ``shards`` labels the site.
            from jax.experimental import io_callback
            bm_eff = ops.effective_block_m(M, bm)
            if hist is not None:
                io_callback(lambda v, h, s=site, b=bm_eff, k=K, r=M,
                            g=group_t, u=d.usage_ratio, sh=d.shards:
                            self._record_nnz(s, b, k, r, v, group_t=g,
                                             usage_ratio=u, match_hist=h,
                                             shards=sh),
                            None, nnz, hist, ordered=False)
            else:
                io_callback(lambda v, s=site, b=bm_eff, k=K, r=M, g=group_t,
                            u=d.usage_ratio, sh=d.shards:
                            self._record_nnz(s, b, k, r, v, group_t=g,
                                             usage_ratio=u, shards=sh),
                            None, nnz, ordered=False)
        return out

    def _record_nnz(self, site: str, block_m: int, k_dim: int, rows: int,
                    nnz: Any, group_t: int = 0,
                    usage_ratio: float | None = None,
                    match_hist: Any = None,
                    shards: int | None = None) -> None:
        nnz = np.asarray(nnz)
        with self._lock:
            c = self._sites.setdefault(site, {
                "executions": 0, "rows": 0, "l2_nnz_total": 0,
                "l2_nnz_max_block": 0, "block_m": block_m, "k_dim": k_dim,
                "group_t": group_t, "usage_ratio": usage_ratio,
                "shards": shards or 1,
            })
            c["executions"] += 1
            c["rows"] += rows
            c["l2_nnz_total"] += int(nnz.sum())
            c["l2_nnz_max_block"] = max(c["l2_nnz_max_block"],
                                        int(nnz.max(initial=0)))
            c["block_m"], c["k_dim"], c["group_t"] = block_m, k_dim, group_t
            c["usage_ratio"] = usage_ratio
            if shards:
                # per-shard telemetry: executions/rows/l2_nnz above count
                # each shard's callback separately; this labels the site
                # with the mesh extent they came from.
                c["shards"] = shards
            if match_hist is not None:
                # runtime match telemetry: per-site (T, q+1) histogram of
                # actual pattern references, streamed by the prefetch
                # pre-pass. resolve() derives later traces' gather sets
                # from this aggregate (reason suffix "_runtime_sets").
                h = np.asarray(match_hist, np.int64)
                prev = c.get("usage_runtime")
                if prev is not None and prev.shape == h.shape:
                    h = prev + h
                c["usage_runtime"] = h
            max_block = c["l2_nnz_max_block"]
        # Metric mirror (sums/counts are order-independent, so these stay
        # deterministic under the unordered callbacks; readers flush with
        # jax.effects_barrier() first — report() does).
        self.metrics.counter("site_executions", "fused-kernel callbacks",
                             labelnames=("site",)).inc(site=site)
        self.metrics.counter("site_rows", "activation rows processed",
                             labelnames=("site",)).inc(rows, site=site)
        self.metrics.counter("site_l2_nnz", "streamed L2 nonzeros",
                             labelnames=("site",)).inc(int(nnz.sum()),
                                                       site=site)
        self.metrics.gauge("site_l2_nnz_max_block", "peak per-block L2 nnz",
                           labelnames=("site",)).set(max_block, site=site)

    # ----------------------------------------------------------- reporting --
    def decisions(self) -> dict[tuple[str, str, str], int]:
        """Trace counts keyed by (site, impl, reason) — decisions happen at
        trace time, so under jit caching these count traces, not steps.
        (A thin view over the ``phi_dispatch_decisions`` counter.)"""
        return {key: int(v) for key, v in self._dec.items()}

    def last_decision(self, site: str) -> Decision | None:
        """The most recent Decision resolved for ``site`` — carries the
        local problem shape and, inside shard_map, the shard count."""
        with self._lock:
            return self._last.get(site)

    def report(self) -> dict:
        """Dispatch counts + the perfmodel packer-budget view of the
        aggregated fused-kernel l2_nnz counters."""
        from repro.core.perfmodel import packer_budget_report
        # The l2_nnz counters arrive through unordered io_callbacks; flush
        # them or a report taken right after a step under-counts (the PR-1
        # calibration race, caught by PHI-LINT-BARRIER).
        jax.effects_barrier()
        decisions = self.decisions()
        with self._lock:
            sites = {k: dict(v) for k, v in self._sites.items()}
        return {"decisions": decisions,
                "packer_budgets": packer_budget_report(sites)}

    def metrics_snapshot(self) -> dict:
        """Deterministic JSON view of the policy's metric registry, flushed
        past any in-flight telemetry callbacks first."""
        jax.effects_barrier()
        return self.metrics.snapshot()

    def log_report(self, prefix: str = "phi") -> None:
        """Log :meth:`report` (dispatch counts + packer budgets) at INFO."""
        rep = self.report()
        for (site, impl, reason), count in sorted(rep["decisions"].items()):
            log.info("%s dispatch: %-28s -> %-6s %-28s %d trace(s)",
                     prefix, site, impl, reason, count)
        for b in rep["packer_budgets"]:
            log.info("%s packer:   %-28s execs=%-5d l2_nnz=%-10d "
                     "peak_block_density=%.4f -> cap_required=%d "
                     "(nnz_budget >= %.4f)", prefix, b.site, b.executions,
                     b.l2_nnz_total, b.peak_block_density, b.cap_required,
                     b.nnz_budget_required)

    def reset(self, keep_usage: bool = False) -> None:
        """Clear telemetry: decisions, runtime counters and metrics — plus
        the calibration usage registry unless ``keep_usage`` is set.

        ``keep_usage=True`` is the between-runs reset (``Engine.
        reset_telemetry``): run counters must zero so back-to-back runs
        report identically, but the calibration histograms describe the
        *model*, not the run, and wiping them would silently disable the
        prefetch usage gate for every later trace."""
        with self._lock:
            self._last.clear()
            self._sites.clear()
            if not keep_usage:
                self._usage.clear()
        self.metrics.reset()


# ------------------------------------------------------ per-shard usage ------
def shard_usage_histogram(usage: Any, shards: int) -> np.ndarray | None:
    """Per-shard view of a (T, q+1) pattern-usage histogram for a call whose
    K axis is split ``shards``-ways under shard_map (row-parallel).

    The pattern bank's T row-partitions split with K — shard ``i`` owns
    histogram rows ``[i·T/shards, (i+1)·T/shards)``. The shard_map body is
    traced ONCE for all shards, so the policy can be handed only a single
    concrete histogram: the element-wise max over the shard slices. A
    pattern hot in ANY shard then stays inside the prefetch gather-buffer
    sizing, which keeps the one traced decision valid for every shard
    (exactness never depends on the set choice — only the streamed-bytes
    win does). Column-parallel calls replicate the bank: pass ``shards=1``
    (identity). Returns None when T does not divide (the divisibility
    fallback replicated the weight instead, so there is no local slice)."""
    if usage is None or shards <= 1:
        return usage
    u = np.asarray(usage)
    t = u.shape[0]
    if t % shards:
        return None
    return u.reshape(shards, t // shards, u.shape[1]).max(axis=0)


# ---------------------------------------------------------- default policy ---
_default_policy = PhiExecutionPolicy()


def get_policy() -> PhiExecutionPolicy:
    """The process-wide execution policy every call site dispatches through."""
    return _default_policy


def set_policy(policy: PhiExecutionPolicy) -> PhiExecutionPolicy:
    """Swap the process-wide policy; returns the previous one (tests use
    this to install a fresh policy and restore the old)."""
    global _default_policy
    prev, _default_policy = _default_policy, policy
    return prev


def phi_matmul(a: jax.Array, w: jax.Array, patterns: jax.Array,
               pwp: jax.Array, **kwargs: Any) -> jax.Array:
    """Module-level shorthand: policy-dispatched Phi matmul. Accepts the
    same keywords as :meth:`PhiExecutionPolicy.matmul` (``site``,
    ``override``, ``nnz_budget``, ``gather_dtype``, ``pwp_scale``)."""
    return _default_policy.matmul(a, w, patterns, pwp, **kwargs)


def phi_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        patterns: jax.Array | None = None,
                        **kwargs: Any) -> jax.Array:
    """Module-level shorthand: policy-dispatched flash attention. Accepts
    the same keywords as :meth:`PhiExecutionPolicy.attention` (``site``,
    ``causal``/``window``/``chunk``, ``spike_qk``, ``override``)."""
    return _default_policy.attention(q, k, v, patterns, **kwargs)


# -------------------------------------------------- checkpoint persistence ---
def checkpoint_extra(cfg: Any) -> dict:
    """Policy-relevant config to persist in a checkpoint's ``extra`` dict."""
    phi = getattr(cfg, "phi", None)
    if phi is not None and getattr(phi, "impl", None) is not None:
        return {_CKPT_KEY: phi.impl}
    return {}


def apply_checkpoint_extra(cfg: Any, extra: dict | None) -> Any:
    """Re-apply a persisted impl override onto a restored config. A live
    override (CLI/config) wins over the checkpointed one."""
    impl = (extra or {}).get(_CKPT_KEY)
    phi = getattr(cfg, "phi", None)
    if impl and phi is not None and getattr(phi, "impl", None) is None:
        return cfg.with_(phi=dataclasses.replace(phi, impl=impl))
    return cfg


def usage_checkpoint_extra(usage: dict | None) -> dict:
    """Pattern-usage histograms as a JSON-able checkpoint ``extra`` payload.

    ``usage`` maps layer/site name -> (T, q+1) counts (the ``PhiState.usage``
    dict of the SNN path; the LM path's histograms additionally live in the
    params tree as arrays). Returned as nested lists so the checkpoint
    manifest carries them verbatim — the restore side reconstructs with
    :func:`usage_from_checkpoint_extra`.
    """
    if not usage:
        return {}
    return {_USAGE_KEY: {name: np.asarray(u).astype(np.int64).tolist()
                         for name, u in usage.items()}}


def usage_from_checkpoint_extra(extra: dict | None) -> dict:
    """Inverse of :func:`usage_checkpoint_extra`: name -> (T, q+1) int64."""
    raw = (extra or {}).get(_USAGE_KEY) or {}
    return {name: np.asarray(v, np.int64) for name, v in raw.items()}


def register_usage_from_params(params: Any, prefix: str = "lm") -> int:
    """Walk a calibrated LM param tree and (re-)register every ``phi_*``
    usage histogram with the default policy under its dispatch site name
    (``f"{prefix}.{weight}"``). Used after a checkpoint restore, where the
    histograms arrive as params-tree arrays but the policy registry (which
    the usage gate reads at trace time) starts empty. Returns the number of
    sites registered."""
    pol = get_policy()
    count = 0

    def _walk(node: Any) -> None:
        nonlocal count
        if not isinstance(node, dict):
            return
        for key, val in node.items():
            if key.startswith("phi_") and isinstance(val, dict):
                u = val.get("usage")
                if u is not None:
                    u = np.asarray(u)
                    if u.ndim == 3:     # layer-stacked: pooled histogram
                        u = u[0]
                    if u.size and u.sum() > 0:
                        pol.register_usage(f"{prefix}.{key[4:]}", u)
                        count += 1
            elif isinstance(val, dict):
                _walk(val)

    _walk(params)
    return count
