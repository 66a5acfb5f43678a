"""Continuous-batching serving engine (vLLM-lite for this framework).

A fixed pool of ``batch_slots`` decode lanes over one batched decode-state
tree. Per tick:
  1. admit queued requests into free slots — the telemetry-driven scheduler
     (``serve/scheduler.py``) picks *which* queued requests go first, from
     the dispatch policy's per-site telemetry (cold sites warm up on a
     single request; skewed sites admit same-bucket cohorts); each admitted
     prompt is prefilled (batch=1) and its caches are spliced into the
     batched state at the slot index;
  2. one fused ``decode_step`` advances *all* active slots;
  3. finished slots (EOS / budget) emit results and free up.

Paged KV cache (``paged=True``): instead of a contiguous ``max_context``
cache per slot, full-attention KV leaves live in a shared page pool
(``serve/page_manager.py``) and each slot holds a page *table*; slot memory
is O(tokens generated) and decode is bitwise identical to the contiguous
engine (tested under dyadic weights). When the pool runs dry the scheduler
picks a victim to preempt — it re-queues with its generated prefix and
resumes token-identically: its re-prefill of prompt plus prefix rebuilds
every cache and state it had.

Layer-typed hybrids (granite-4.0-h: Mamba-2 layers and a few GQA layers)
page only their attention layers' KV. A Mamba-2 layer's recurrent state
(SSM state and conv rings) has no sequence axis: it is one fixed-size entry
per slot, kept beside the pools in the decode state the step carries
(``self.pools``), overwritten when a slot is admitted and read and written
by every decode step. Each traced ``decode`` event carries ``state_bytes``,
the recurrent state of the step's active slots.

Ring caches (swa/chunked) are already O(window), and the ssm and zamba2
stacks keep their state in dense slots: those configs are not paged — the
same capability gate as ``bucketed``.

Prompt bucketing: admissions pad the prompt to the next power-of-two length
(capped at ``max_context``) and read the logits at the true last position,
so warm traffic with mixed prompt lengths reuses a handful of prefill jit
entries instead of compiling one per distinct length. Right-padding is
exact for causal full attention (the prompt never attends to the pad), and
for the Mamba-2 layers of a layer-typed stack, whose prefill is told the
true length: ``dt`` is zero past it, so the pad neither decays nor writes
the SSM state, and the conv rings are read at the true length. Ring caches
and the ssm/zamba2 stacks would fold pad tokens into state, so those archs
prefill at the raw length (``transformer.pads_exactly`` decides).

Phi mode: the engine never names a kernel impl — every spiking GEMM inside
prefill/decode routes through the ``kernels.dispatch`` execution policy
(fused single-pass on a single device; mesh-aware ``spmd_local_*``
re-gating inside the shard_map bodies when the engine is given a device
``mesh``). ``phi_report()`` exposes the policy's dispatch decisions and
the aggregated l2_nnz packer budgets for the served traffic.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model
from repro.models.config import ModelConfig
from repro.models.transformer import pads_exactly
from repro.obs.metrics import DEFAULT_BUCKETS, TICK_BUCKETS, MetricsRegistry
from repro.obs.trace import Tracer
from repro.serve.page_manager import PageManager
from repro.serve.sampling import sample
from repro.serve.scheduler import TelemetryScheduler


@dataclasses.dataclass
class Request:
    """One generation request. ``prefix`` is engine-internal preemption
    bookkeeping (tokens already generated before a re-queue) — leave it
    empty on submit."""

    rid: int
    tokens: np.ndarray              # prompt tokens (P,)
    max_new_tokens: int = 32
    temperature: float = 0.0
    prefix: list[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Result:
    """Finished generation: every token generated for ``rid`` (across
    preemptions, in order) and the original prompt length."""

    rid: int
    tokens: list[int]
    prompt_len: int


def bucket_len(plen: int, cap: int) -> int:
    """Next power-of-two >= ``plen``, capped at ``cap``.

    Raises ValueError when ``cap < plen`` — a prompt longer than the
    context window has no valid bucket (the engine rejects such prompts at
    ``submit()``; regression-tested).
    """
    if cap < plen:
        raise ValueError(f"prompt length {plen} exceeds bucket cap {cap}")
    b = 1
    while b < plen:
        b *= 2
    return min(b, cap)


class Engine:
    """Continuous-batching serve loop over one model (see module docstring).

    ``paged=True`` enables the paged KV cache for the configs of
    ``transformer.pads_exactly`` (silently kept dense otherwise — the
    capability gate). ``num_pages``
    defaults to the contiguous capacity (``batch_slots`` full lanes) so
    admission is never pool-blocked unless the caller constrains it;
    ``record_logits=True`` keeps a per-request trace of every sampled-from
    logits row (parity tests / benches).
    """

    def __init__(self, cfg: ModelConfig, params: Any, *, batch_slots: int = 4,
                 max_context: int = 512, eos_id: int = 2, seed: int = 0,
                 mesh=None, paged: bool = False, page_size: int = 16,
                 num_pages: int | None = None,
                 scheduler: TelemetryScheduler | None = None,
                 record_logits: bool = False,
                 tracer: Tracer | None = None,
                 wall_time: bool = False):
        """Allocate the decode state (dense slots or page pool) and jit the
        prefill/decode/splice entry points.

        ``tracer`` records the request lifecycle and each tick's phases as
        spans (obs/trace.py; see :meth:`tick`); ``wall_time=True``
        additionally samples each tick's wall time into the
        ``serve_tick_ms`` histogram — off by default so the metric
        snapshot stays deterministic. Both are host-side only:
        instrumented runs are bitwise identical to uninstrumented ones
        (gated by ``benchmarks/obs_bench.py``).
        """
        assert cfg.frontend == "none", "engine serves token-in token-out archs"
        self.cfg = cfg
        self.params = params
        self.B = batch_slots
        self.max_context = max_context
        self.eos_id = eos_id
        self.key = jax.random.PRNGKey(seed)
        self.mesh = mesh
        # Engine-scoped metrics: every run counter lives in this registry,
        # so a second engine in the same process starts from zero and
        # reset_telemetry() can zero this engine without touching others.
        self.metrics = MetricsRegistry(namespace="serve")
        self.scheduler = scheduler or TelemetryScheduler(metrics=self.metrics)
        self.tracer = tracer
        self.wall_time = wall_time
        self._m_ticks = self.metrics.counter("ticks", "engine iterations")
        self._m_decoded = self.metrics.counter(
            "decoded_tokens", "tokens decoded across all slots")
        self._m_submitted = self.metrics.counter(
            "requests_submitted", "requests accepted into the queue")
        self._m_retired = self.metrics.counter(
            "requests_retired", "requests finished (incl. context_full)")
        self._m_preempted = self.metrics.counter(
            "requests_preempted", "pool-dry evictions (re-queued)")
        self._m_latency_ticks = self.metrics.histogram(
            "request_latency_ticks",
            "admit -> retire latency in engine ticks (per slot residency)",
            buckets=TICK_BUCKETS)
        self._m_tick_ms = self.metrics.histogram(
            "tick_ms",
            "wall time of each tick that decoded: the gap every busy slot "
            "saw between two tokens (wall_time engines only)",
            buckets=DEFAULT_BUCKETS)
        self._admit_tick = [0] * batch_slots
        self.record_logits = record_logits
        self.logit_trace: dict[int, list[np.ndarray]] = {}
        # Right-padding is exact only where every layer allows it (see module
        # docstring); other archs keep raw-length prefill. Paging shares the
        # gate.
        self.bucketed = pads_exactly(cfg)
        self.paged = paged and self.bucketed
        if paged and not self.paged:
            self.scheduler.note("paged_gate_dense")

        self.pm: PageManager | None = None
        if self.paged:
            if num_pages is None:
                num_pages = batch_slots * (max_context // page_size)
            self.pm = PageManager(num_pages=num_pages, page_size=page_size,
                                  slots=batch_slots, max_context=max_context)
            self.pools = model.init_paged_state(cfg, num_pages, page_size,
                                                slots=batch_slots)
            self.state = None
        else:
            self.state = model.init_decode_state(cfg, batch_slots, max_context)
        # One slot's recurrent state, read and written by each decode step.
        self.slot_state_bytes = sum(
            leaf.size * leaf.dtype.itemsize // leaf.shape[1]
            for leaf in model.state_leaves(
                cfg, self.pools if self.paged else self.state, "slot"))
        self.pos = np.zeros(batch_slots, np.int64)
        self.active = np.zeros(batch_slots, bool)
        self.budget = np.zeros(batch_slots, np.int64)
        self.out_tokens: list[list[int]] = [[] for _ in range(batch_slots)]
        self.slot_req: list[Request | None] = [None] * batch_slots
        self.queue: list[Request] = []
        self.results: list[Result] = []

        self._decode = jax.jit(partial(model.decode_step, cfg))
        self._decode_paged = jax.jit(partial(model.decode_step_paged, cfg))
        self._prefill = jax.jit(partial(model.prefill, cfg))
        self._prefill_padded = jax.jit(partial(model.prefill_padded, cfg))
        self._insert = jax.jit(self._insert_impl)
        self._splice = jax.jit(self._splice_impl)

    @property
    def ticks(self) -> int:
        """Engine iterations so far (thin view over ``serve_ticks``)."""
        return int(self._m_ticks.get())

    @property
    def decoded_tokens(self) -> int:
        """Tokens decoded so far (thin view over ``serve_decoded_tokens``)."""
        return int(self._m_decoded.get())

    def _emit(self, kind: str, **attrs: Any) -> None:
        """Tracer event carrying the current tick counter (no-op untraced)."""
        if self.tracer is not None:
            self.tracer.emit(kind, tick=self.ticks, **attrs)

    def _span(self, kind: str, **attrs: Any):
        """Tracer span (emit-on-exit, profiler annotation ``engine.<kind>``)
        or a null context when untraced."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(kind, "engine." + kind, tick=self.ticks,
                                **attrs)

    def _ctx(self):
        """Mesh context for traced calls: under a mesh the sharding rules
        route the phi GEMMs through ``_phi_sharded_matmul``'s shard_map and
        the dispatch policy re-gates on the per-shard shape."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.distributed.sharding import SERVE_RULES, use_rules
        return use_rules(SERVE_RULES, self.mesh)

    # ------------------------------------------------------------- plumbing
    @staticmethod
    def _slot_put(c, n, slot):
        """Write batch-1 state ``n`` (layers, 1, ...) at ``slot`` of ``c``."""
        idx = (jnp.zeros((), jnp.int32), slot) + \
            (jnp.zeros((), jnp.int32),) * (c.ndim - 2)
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), idx)

    @staticmethod
    def _page_put(pool, n, pages):
        # Prefill caches are (n_scan, 1, bl, H, hd); pad the sequence axis
        # to a whole number of pages, chop into page chunks and scatter them
        # to this slot's physical pages. Junk in the pad tail is exactly the
        # junk the contiguous engine keeps past the prompt — masked, then
        # progressively overwritten by decode.
        ps = pool.shape[2]
        npg = pages.shape[0]
        pad = npg * ps - n.shape[2]
        if pad:
            n = jnp.pad(n, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (n.ndim - 3))
        chunks = n.reshape((n.shape[0], npg, ps) + n.shape[3:])
        return pool.at[:, pages].set(chunks.astype(pool.dtype))

    def _insert_impl(self, state, new_state, slot):
        return jax.tree.map(partial(self._slot_put, slot=slot), state, new_state)

    def _splice_impl(self, pools, new_state, pages, slot):
        """Admission into the paged state: KV into the slot's pages, a
        layer-typed stack's recurrent state into the slot."""
        def put(kind, pool, n):
            fn = (partial(self._page_put, pages=pages) if kind == "kv"
                  else partial(self._slot_put, slot=slot))
            return jax.tree.map(fn, pool, n)

        return jax.tree.map(put, model.state_kinds(self.cfg), pools, new_state)

    def submit(self, req: Request) -> None:
        """Queue a request. Prompts longer than ``max_context - 1`` are
        rejected here — there would be no cache slot left for even one
        generated token (see ``bucket_len``)."""
        plen = len(req.tokens)
        if plen > self.max_context - 1:
            raise ValueError(
                f"request {req.rid}: prompt length {plen} exceeds "
                f"max_context - 1 = {self.max_context - 1}; raise "
                f"max_context or truncate the prompt")
        self.queue.append(req)
        self._m_submitted.inc()
        self._emit("submit", rid=req.rid, prompt_len=plen)

    # ----------------------------------------------------------------- tick
    def _admit(self) -> None:
        free = [s for s in range(self.B) if not self.active[s]]
        if not free or not self.queue:
            return
        # Non-phi models have no dispatch sites of their own: pin FIFO via an
        # empty snapshot so leftover telemetry from other models served in
        # this process can never steer their admission order.
        snap = (None if self.cfg.phi is not None else
                {"sites": 0, "warm": False, "mean_usage_ratio": 1.0})
        picks = self.scheduler.select(self.queue, len(free), self.max_context,
                                      snapshot=snap)
        while free and picks:
            req = picks.pop(0)
            prompt = np.concatenate([np.asarray(req.tokens, np.int64),
                                     np.asarray(req.prefix, np.int64)])
            plen = len(prompt)
            if plen > self.max_context - 1:
                # A re-queued prefix grew to the context edge: finish with
                # what we have (the unpreempted run would truncate there too).
                self.results.append(
                    Result(req.rid, list(req.prefix), len(req.tokens)))
                self.scheduler.note("retire_context_full")
                self._m_retired.inc()
                self._emit("retire", rid=req.rid, reason="context_full",
                           tokens=len(req.prefix))
                continue
            if self.paged:
                bl = bucket_len(plen, self.max_context)
                if not self.pm.reserve_prefill(free[0], bl):
                    # Pool dry: stop admitting, put the rest back in order.
                    self.scheduler.note("admit_blocked_pool")
                    self._emit("admit_blocked", rid=req.rid)
                    picks.insert(0, req)
                    break
            self._admit_one(free.pop(0), req, prompt)
        if picks:
            self.queue[:0] = picks

    def _admit_one(self, slot: int, req: Request, prompt: np.ndarray) -> None:
        prompt = prompt[None, :].astype(np.int32)
        plen = prompt.shape[1]
        bl = bucket_len(plen, self.max_context) if self.bucketed else plen
        self._emit("resume" if req.prefix else "admit", rid=req.rid,
                   slot=slot, prompt_len=plen, bucket=bl)
        # The span ends when the first token is on the host (the one sync
        # of an admission), so it holds the prefill's whole device time.
        with self._span("prefill", rid=req.rid, slot=slot, bucket=bl,
                        prompt_len=plen):
            with self._ctx():
                if self.bucketed:
                    padded = np.zeros((1, bl), np.int32)
                    padded[0, :plen] = prompt[0]
                    logits, new_state = self._prefill_padded(
                        self.params, {"tokens": jnp.asarray(padded)},
                        jnp.full((1,), plen - 1, jnp.int32))
                else:
                    logits, new_state = self._prefill(
                        self.params, {"tokens": jnp.asarray(prompt)})
            if self.paged:
                n = max(1, -(-bl // self.pm.page_size))
                pages = self.pm.tables[slot, :n].copy()
                self.pools = self._splice(self.pools, new_state,
                                          jnp.asarray(pages), jnp.int32(slot))
            else:
                new_state = model.extend_caches(self.cfg, new_state,
                                                self.max_context)
                self.state = self._insert(self.state, new_state,
                                          jnp.int32(slot))
            self.key, sk = jax.random.split(self.key)
            first = sample(logits, sk, temperature=req.temperature)
            if self.record_logits:
                self.logit_trace.setdefault(req.rid, []).append(
                    np.asarray(logits[0]))
            self.out_tokens[slot] = [int(first[0])]
        self.pos[slot] = plen
        self.budget[slot] = req.max_new_tokens - len(req.prefix)
        self.active[slot] = True
        self.slot_req[slot] = req
        self._admit_tick[slot] = self.ticks

    # ------------------------------------------------------------ preemption
    def _preempt(self, slot: int) -> None:
        """Evict ``slot``: free its pages and re-queue the request at the
        front with its generated prefix (it resumes token-identically)."""
        req = self.slot_req[slot]
        req.prefix = list(req.prefix) + list(self.out_tokens[slot])
        self.queue.insert(0, req)
        self.scheduler.note("requeue_preempted")
        self._m_preempted.inc()
        self._emit("preempt", rid=req.rid, slot=slot,
                   generated=len(self.out_tokens[slot]))
        self.pm.release(slot)
        self.active[slot] = False
        self.slot_req[slot] = None
        self.out_tokens[slot] = []

    def _ensure_pages(self) -> None:
        """Map the page each active slot's next token lands in, preempting
        scheduler-chosen victims while the pool is dry. Terminates: every
        preemption frees >= 1 page, and a sole survivor always fits
        (``num_pages >= logical_pages``, checked at construction)."""
        for slot in range(self.B):
            if not self.active[slot]:
                continue
            while self.active[slot] and \
                    not self.pm.ensure(slot, int(self.pos[slot])):
                cands = [(s, int(self.budget[s]) - len(self.out_tokens[s]),
                          self.slot_req[s].rid)
                         for s in range(self.B) if self.active[s]]
                self._preempt(self.scheduler.pick_victim(cands))

    def _retire(self) -> None:
        for slot in range(self.B):
            if not self.active[slot]:
                continue
            toks = self.out_tokens[slot]
            done = len(toks) >= self.budget[slot] or (toks and toks[-1] == self.eos_id)
            if done or self.pos[slot] >= self.max_context - 1:
                req = self.slot_req[slot]
                self.results.append(Result(
                    req.rid, list(req.prefix) + list(toks), len(req.tokens)))
                if self.paged:
                    self.pm.release(slot)
                self.active[slot] = False
                self.slot_req[slot] = None
                self._m_retired.inc()
                # Latency covers this slot residency (admit -> retire); a
                # preempted request's earlier residencies were traced as
                # their own admit/preempt spans.
                lat = self.ticks - self._admit_tick[slot]
                self._m_latency_ticks.observe(lat)
                self._emit("retire", rid=req.rid, slot=slot,
                           tokens=len(req.prefix) + len(toks),
                           latency_ticks=lat)

    def tick(self) -> bool:
        """One engine iteration; returns False when fully idle.

        Traced, the tick is a ``tick`` span (with the ``active`` slots it
        decoded) tiled by phase spans: ``schedule`` (admission, holding a
        ``prefill`` span per admitted request), ``pages`` (paged engines),
        ``inputs`` (last tokens, positions and page table to the device),
        ``step`` (decode, sample and the sync on the sampled tokens: the
        part bound to the device) and ``finish`` (bookkeeping, retire)."""
        t0 = time.perf_counter() if self.wall_time else 0.0
        with self._span("tick") as span:
            active = self._tick()
            if span is not None:
                span.attrs["active"] = active
        if self.wall_time and active:
            self._m_tick_ms.observe((time.perf_counter() - t0) * 1e3)
        return active > 0 or bool(self.queue)

    def _tick(self) -> int:
        """The phases of :meth:`tick`; returns the slots it decoded."""
        with self._span("schedule"):
            self._admit()
        if self.paged:
            with self._span("pages"):
                self._ensure_pages()
        if not self.active.any():
            return 0
        with self._span("inputs"):
            last = jnp.asarray(np.array(
                [self.out_tokens[b][-1] if self.active[b] else 0
                 for b in range(self.B)], np.int32))
            pos = jnp.asarray(self.pos.astype(np.int32))
            tables = jnp.asarray(self.pm.tables) if self.paged else None
            # Per-slot temperatures: a sampled request batched next to a
            # greedy one must not perturb the greedy stream.
            temps = np.array([r.temperature if r is not None else 0.0
                              for r in self.slot_req], np.float32)
        with self._span("step"):
            with self._ctx():
                if self.paged:
                    logits, self.pools = self._decode_paged(
                        self.params, last, pos, self.pools, tables)
                else:
                    logits, self.state = self._decode(self.params, last,
                                                      pos, self.state)
            self.key, sk = jax.random.split(self.key)
            nxt = np.asarray(sample(logits, sk, temperature=temps))
        with self._span("finish"):
            if self.record_logits:
                logits_np = np.asarray(logits)
                for b in range(self.B):
                    if self.active[b]:
                        self.logit_trace.setdefault(
                            self.slot_req[b].rid, []).append(logits_np[b])
            pages = {}
            if self.paged:
                # Pages the step's attention read, ceil((pos + 1) / ps) per
                # active slot, and the whole-context view's slots × Lp.
                live = self.pos[self.active] // self.pm.page_size + 1
                pages = {"kv_pages": int(live.sum()),
                         "kv_pages_view": self.B * self.pm.logical_pages}
            decoded = 0
            for b in range(self.B):
                if self.active[b]:
                    self.out_tokens[b].append(int(nxt[b]))
                    self.pos[b] += 1
                    decoded += 1
            if self.slot_state_bytes:
                # The recurrent state the step read and wrote for its slots.
                pages["state_bytes"] = decoded * self.slot_state_bytes
            self._emit("decode", active=decoded, tokens=decoded, **pages)
            self._m_decoded.inc(decoded)
            self._m_ticks.inc()
            self._retire()
        return decoded

    def run(self, max_ticks: int = 10_000) -> list[Result]:
        """Tick until queue and slots drain (or ``max_ticks``); returns the
        accumulated Results."""
        while self.tick() or self.queue or self.active.any():
            if self.ticks >= max_ticks:
                break
            if not self.queue and not self.active.any():
                break
        if self.cfg.phi is not None:
            from repro.kernels import dispatch
            from repro.obs.drift import DriftMonitor
            dispatch.get_policy().log_report(prefix="serve")
            # Drift pass over the served sites: publishes per-site
            # drift_score gauges and the drift_alert counter the future
            # bank-swap subsystem consumes (docs/observability.md).
            verdict = DriftMonitor(
                dispatch.get_policy(),
                prefix=self.scheduler.config.site_prefix).check()
            if verdict["alerts"]:
                from repro.utils import log
                log.warning("sparsity drift past threshold at %s",
                            ", ".join(verdict["alerts"]))
        return self.results

    # ------------------------------------------------------------ reporting
    def reset_telemetry(self, include_policy: bool = True) -> None:
        """Zero every run counter so a fresh run over this engine (or the
        next engine in this process) reports from scratch.

        Clears the engine-scoped metric registry (and the scheduler's, when
        the caller wired its own), the logit traces, and — unless
        ``include_policy=False`` — the process dispatch policy's *runtime*
        telemetry. The policy's calibration usage registry survives
        (``reset(keep_usage=True)``): it describes the model, not the run,
        and wiping it would disable the prefetch usage gate. Regression-
        tested: two back-to-back identical runs report identical counts.
        """
        self.metrics.reset()
        if self.scheduler.metrics is not self.metrics:
            self.scheduler.metrics.reset()
        self.logit_trace.clear()
        if include_policy:
            from repro.kernels import dispatch
            dispatch.get_policy().reset(keep_usage=True)

    def phi_report(self) -> dict:
        """Execution-policy telemetry for the traffic served so far:
        per-site dispatch decisions + l2_nnz packer budgets."""
        from repro.kernels import dispatch
        return dispatch.get_policy().report()

    def cache_report(self) -> dict:
        """Cache-memory accounting: the contiguous allocation this
        configuration would need, and (paged mode) the pool size and the
        high-water mark actually touched — the bench asserts
        ``page_hwm_bytes < contig_cache_bytes``."""
        specs = model.decode_state_specs(self.cfg, self.B, self.max_context)
        contig = sum(int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
                     for s in model.state_leaves(self.cfg, specs, "kv"))
        out: dict[str, Any] = {"contig_cache_bytes": int(contig)}
        if self.paged:
            pool_bytes = sum(v.size * v.dtype.itemsize for v in
                             model.state_leaves(self.cfg, self.pools, "kv"))
            per_page = pool_bytes // (self.pm.num_pages + 1)
            out.update(self.pm.report())
            out["pool_bytes"] = int(pool_bytes)
            out["page_bytes"] = int(per_page)
            out["page_hwm_bytes"] = int(per_page * self.pm.hwm_pages)
        return out

    def serve_report(self) -> dict:
        """Scheduler decision counts + cache accounting + run counters."""
        return {
            "scheduler_decisions": self.scheduler.report(),
            "cache": self.cache_report(),
            "ticks": self.ticks,
            "decoded_tokens": self.decoded_tokens,
            "paged": self.paged,
        }
