"""Inner functions of one benchmark run: find the cell's files by name, set
up the system under test, drive the measured window, reduce it to metrics,
and decide ``correct`` against the plain reference.

Everything here runs at ``smoke=True`` on a CPU too (tests rehearse it);
only ``run.py`` looks for the chip. What belongs to one configuration, one
traffic mix or one per-layer metric lives in files of its own:

    chipbench/configs/<config>.json    sizes, engine settings, check limits,
                                       the ``reference`` module's name
    chipbench/refs/<module>.py         the architecture's plain reference,
                                       weight leaves and work count
    chipbench/traffic/<traffic>.json   parameters of the one generator
    chipbench/metrics/<metric>.py      ``read(ctx) -> float | None``

From the program the harness takes only the serving engine (as
``launch/serve.py`` builds it), its calibration, its tracer events and its
dispatch records.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any

import numpy as np

from chipbench import e2e, loadgen, peaks, trace_reduce, weights

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DRAIN_S = 60.0              # how long a request due in the window is waited for
SAMPLE_TOKENS = 256         # served tokens the reference checks, at least
MAX_REF_TOKENS = 49152      # forward tokens the reference may spend
MAX_REF_REQUESTS = 24


# ------------------------------------------------------------- the spec ---
def load_spec(root: str = ROOT) -> dict:
    """``BENCHMARK.json`` at the checkout's root."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    """Configuration file ``chipbench/configs/<name>.json``."""
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def load_metric(name: str):
    """The reader module of per-layer metric ``name``."""
    return importlib.import_module(f"chipbench.metrics.{name}")


def load_reference(config: dict):
    """The reference module ``chipbench/refs/<module>.py`` that
    configuration ``config`` names."""
    if "reference" not in config:
        raise KeyError(f"configuration {config['name']!r} names no \"reference\" "
                       "(the module chipbench/refs/<reference>.py of its architecture)")
    return importlib.import_module(f"chipbench.refs.{config['reference']}")


def for_cell(entries: list[dict], cell: str) -> list[dict]:
    """The metric entries that apply to ``cell``."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Cell:
    """One workload entry with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    ref: Any                    # the configuration's reference module


def find_cell(spec: dict, name: str) -> Cell:
    """Resolve workload ``name`` of ``spec`` to its files."""
    for w in spec["workloads"]:
        if w["name"] == name:
            config = load_config(w["config"])
            return Cell(name=name, chips=int(w["chips"]), config=config,
                        traffic=loadgen.load(w["traffic"]),
                        end_to_end=for_cell(spec["end_to_end"], name),
                        per_layer=for_cell(spec["per_layer"], name),
                        ref=load_reference(config))
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def sizes(config: dict, smoke: bool) -> dict:
    """The configuration with its engine settings, and with the CPU
    rehearsal's sizes laid over it where ``smoke``."""
    out = {**config, **config["engine"]}
    return {**out, **config["smoke"]} if smoke else out


# ---------------------------------------------------- the system under test ---
def program_config(c: dict, smoke: bool, ref):
    """The program's ``ModelConfig`` of configuration ``c``, checked against
    the attributes the reference module says the file fixes."""
    import jax.numpy as jnp
    from repro.configs import get_config, phi_variant

    cfg = get_config(c["arch"], smoke=smoke)
    sp = c.get("spiking")
    if sp:
        cfg = phi_variant(cfg, timesteps=sp["timesteps"], q=sp["q"], k=sp["k"])
    want = ref.program_fields(sizes(c, smoke))
    got = {k: getattr(cfg, k) for k in want}
    for k, v in want.items():
        if isinstance(v, np.dtype):
            got[k] = jnp.dtype(got[k])
    if got != want:
        raise ValueError(f"program config differs from {c['name']}.json: "
                         f"{ {k: got[k] for k in want if got[k] != want[k]} }")
    return cfg


def param_leaves(cfg) -> dict[str, tuple[tuple[int, ...], str]]:
    """{path: (shape, dtype)} of the program's weights (no Phi state)."""
    import jax
    from repro.distributed.sharding import is_spec
    from repro.models import model

    flat = jax.tree_util.tree_flatten_with_path(
        model.lm_specs(cfg.with_(phi=None)), is_leaf=is_spec)[0]
    return {"/".join(k.key for k in path): (tuple(s.shape), np.dtype(s.dtype).name)
            for path, s in flat}


def check_leaves(ref_leaves: dict, prog_leaves: dict) -> None:
    """Refuse a reference whose weight leaves differ from the program's
    parameter tree in path, shape or dtype; the error names every path that
    differs."""
    ours = {p: (tuple(v[0]), v[1]) for p, v in ref_leaves.items()}
    differ = sorted(p for p in set(ours) | set(prog_leaves)
                    if ours.get(p) != prog_leaves.get(p))
    if differ:
        raise ValueError("the reference's weight leaves differ from the program's "
                         "parameter tree (path: reference / program): " + "; ".join(
                             f"{p}: {ours.get(p)} / {prog_leaves.get(p)}" for p in differ))


def program_params(cfg, leaves: dict, seed: int, tied: bool):
    """The benchmark's seeded weights of ``leaves`` (the reference module's)
    in the program's parameter tree."""
    import jax
    from repro.distributed.sharding import is_spec
    from repro.models import model

    flat = weights.make(seed, leaves, tied)
    specs = model.lm_specs(cfg.with_(phi=None))
    paths, treedef = jax.tree_util.tree_flatten_with_path(specs, is_leaf=is_spec)
    return jax.tree_util.tree_unflatten(
        treedef, [flat["/".join(k.key for k in p)] for p, _ in paths])


def calibrate(cfg, params, c: dict, seed: int):
    """The program's Phi calibration on a seeded token batch, as the serve
    launcher runs it. Returns (cfg, params)."""
    import jax.numpy as jnp
    from repro.models import model

    rng = np.random.default_rng([seed, 1])
    batch = {"tokens": jnp.asarray(rng.integers(
        loadgen.FIRST_TOKEN_ID, cfg.vocab, tuple(c["calibration"]["batch"])),
        jnp.int32)}
    cfg, params, maxd = model.calibrate_lm_phi_budgeted(cfg, params, batch)
    say(f"phi calibrated: max L2 density {maxd!r}, nnz_budget "
        f"{cfg.phi.nnz_budget!r}")
    return cfg, params


def make_engine(cfg, params, eng: dict, seed: int, tracer=None):
    """The paged serving engine at the configuration's sizes. ``eos_id=-1``:
    every request yields the tokens it asked for."""
    from repro.serve.engine import Engine

    return Engine(cfg, params, batch_slots=eng["slots"],
                  max_context=eng["max_context"], paged=True,
                  page_size=eng["page_size"], num_pages=eng["num_pages"],
                  eos_id=-1, seed=seed % 2 ** 31, tracer=tracer,
                  wall_time=tracer is not None)


def buckets(lo: int, hi: int, max_context: int) -> list[int]:
    """Prefill buckets of every prompt length in [lo, hi]."""
    from repro.serve.engine import bucket_len

    return sorted({bucket_len(n, max_context) for n in range(lo, hi + 1)})


def warm_up(eng, bucket_list: list[int], vocab: int) -> None:
    """Compile and run once the cell's prefill buckets and the decode step:
    one request per bucket, two tokens each."""
    import jax
    from repro.serve.engine import Request

    for i, b in enumerate(bucket_list):
        n = min(b, eng.max_context - 2)
        eng.submit(Request(rid=-1 - i, tokens=np.full(n, loadgen.FIRST_TOKEN_ID,
                                                      np.int32),
                           max_new_tokens=2))
    while eng.queue or eng.active.any():
        eng.tick()
    jax.block_until_ready(eng.pools)
    eng.results.clear()


# ------------------------------------------------------------- the window ---
def say(*parts) -> None:
    """A line of the run's log (standard error)."""
    print(*parts, file=sys.stderr, flush=True)


def annotate(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Driver:
    """Submits requests to the engine, ticks it, and stamps each token with
    the host time at which the tick that produced it returned."""

    def __init__(self, eng, clock=time.perf_counter):
        self.eng = eng
        self.clock = clock
        self.timeline: dict[int, e2e.ReqTimeline] = {}
        self.prompts: dict[int, np.ndarray] = {}
        self.finished: dict[int, list[int]] = {}
        self.ticks: list[tuple[float, float, int]] = []
        self.lateness: list[float] = []
        self._n_results = len(eng.results)

    def submit(self, item: loadgen.Item, due: float) -> None:
        from repro.serve.engine import Request

        rid = len(self.timeline)
        with annotate("bench.submit"):
            self.eng.submit(Request(rid=rid, tokens=item.prompt,
                                    max_new_tokens=item.max_new))
        self.lateness.append(self.clock() - due)
        self.timeline[rid] = e2e.ReqTimeline(rid, due, len(item.prompt),
                                             item.max_new)
        self.prompts[rid] = item.prompt

    def _stamp(self, rid: int, n: int, t: float) -> None:
        r = self.timeline[rid]
        r.stamps.extend([t] * (n - len(r.stamps)))

    def tick(self) -> list[int]:
        """One engine tick; returns the requests it finished."""
        t0 = self.clock()
        with annotate("bench.tick"):
            self.eng.tick()
        t1 = self.clock()
        done = []
        for res in self.eng.results[self._n_results:]:
            self._stamp(res.rid, len(res.tokens), t1)
            self.finished[res.rid] = list(res.tokens)
            done.append(res.rid)
        self._n_results = len(self.eng.results)
        for b, req in enumerate(self.eng.slot_req):
            if req is not None:
                self._stamp(req.rid, len(req.prefix) + len(self.eng.out_tokens[b]), t1)
        self.ticks.append((t0, t1, int(self.eng.active.sum())))
        return done

    def busy(self) -> bool:
        return bool(self.eng.queue) or bool(self.eng.active.any())


@dataclasses.dataclass
class Window:
    """Host-clock bounds of the measured window."""

    t0: float
    t_close: float
    t1: float


def fill_closed(drv: Driver, traffic: loadgen.Traffic) -> None:
    """Set-up of a closed loop: every client sends its first request, and
    the engine ticks until it has admitted as many as it has slots."""
    waiting = max(0, traffic.clients - drv.eng.B)
    for _ in range(traffic.clients):
        drv.submit(traffic.item(len(drv.timeline)), drv.clock())
    for _ in range(10 * traffic.clients):
        for _ in drv.tick():
            drv.submit(traffic.item(len(drv.timeline)), drv.clock())
        if len(drv.eng.queue) <= waiting:
            return
    raise RuntimeError(f"closed loop: {len(drv.eng.queue)} requests never admitted")


def run_closed(drv: Driver, traffic: loadgen.Traffic, seconds: float) -> Window:
    """Each client sends its next request when its last one completes."""
    t0 = drv.clock()
    t_close = t0 + seconds
    t1 = t_close
    with annotate(trace_reduce.WINDOW):
        while drv.clock() < t_close:
            for _ in drv.tick():
                drv.submit(traffic.item(len(drv.timeline)), drv.clock())
            t1 = max(t1, drv.ticks[-1][1])
    drain(drv, t_close)
    return Window(t0, t_close, t1)


def drain(drv: Driver, t_close: float) -> None:
    """Tick, sending nothing new, until every request sent has its first
    token (at most ``DRAIN_S`` past the close)."""
    waiting = [r for r in drv.timeline.values() if not r.stamps]
    while waiting and drv.clock() < t_close + DRAIN_S:
        drv.tick()
        waiting = [r for r in waiting if not r.stamps]


class Sender:
    """An open loop: sends each request of one schedule when it falls due,
    whatever the engine is doing, and ticks the engine while it has work.
    Due times are offsets from ``origin`` on the ``Driver``'s clock; the
    window opens at ``opens``, a fixed point of the schedule."""

    def __init__(self, drv: Driver, sched: list[loadgen.Item], origin: float,
                 opens: float):
        self.drv = drv
        self.sched = sched
        self.origin = origin
        self.opens = opens
        self.i = 0

    def run_until(self, t_end: float) -> float:
        """Send and tick until ``t_end``; returns the end of the last tick
        (``t_end`` where none ran past it)."""
        drv, t1 = self.drv, t_end
        while drv.clock() < t_end:
            now = drv.clock()
            while self.i < len(self.sched) and \
                    self.origin + self.sched[self.i].due <= now:
                item = self.sched[self.i]
                drv.submit(item, self.origin + item.due)
                self.i += 1
            if not drv.busy():
                nxt = (self.origin + self.sched[self.i].due
                       if self.i < len(self.sched) else t_end)
                with annotate("bench.wait"):
                    time.sleep(max(0.0, min(nxt, t_end) - drv.clock()))
                continue
            drv.tick()
            t1 = max(t1, drv.ticks[-1][1])
        return t1


def lead_in(drv: Driver, traffic: loadgen.Traffic, seconds: float) -> Sender:
    """Set-up of an open loop: its schedule for the lead-in and a window of
    ``seconds``, run for the lead-in, so that the window opens on the
    engine as the offered load keeps it and not on an idle one."""
    origin = drv.clock()
    sender = Sender(drv, traffic.schedule(traffic.lead_in_s + seconds), origin,
                    origin + traffic.lead_in_s)
    sender.run_until(sender.opens)
    return sender


def run_open(sender: Sender, seconds: float) -> Window:
    """The window of an open loop, which goes on with the schedule the
    lead-in began; then the engine is ticked until every request due in
    the window has its first token (at most ``DRAIN_S``). The window opens
    where the schedule says, not where the lead-in's last tick ended, so a
    stall across the opening stays in the window with the work it held up,
    and the same requests fall due in every run."""
    drv = sender.drv
    t0 = sender.opens
    t_close = t0 + seconds
    with annotate(trace_reduce.WINDOW):
        t1 = sender.run_until(t_close)
    drain(drv, t_close)
    return Window(t0, t_close, t1)


# ------------------------------------------------------------ correctness ---
def pick_sample(drv: Driver, seed: int, padded_len
                ) -> list[tuple[np.ndarray, list[int]]]:
    """Seeded sample of the served requests for the reference: the one with
    the most served tokens, then others in a seeded order, until
    ``SAMPLE_TOKENS`` served tokens (or the reference's budget) are reached.
    Finished requests come first; where they hold too few tokens, the
    tokens served so far of requests still running are added. A request
    costs the reference ``padded_len(tokens)`` forward tokens."""
    running = {}
    for b, req in enumerate(drv.eng.slot_req):
        if req is not None and req.rid in drv.timeline:
            running[req.rid] = list(req.prefix) + list(drv.eng.out_tokens[b])
    rng = np.random.default_rng([seed, 2])
    out, n_served, n_fwd = [], 0, 0
    for pool in (drv.finished, running):
        rids = [r for r in pool if pool[r]]
        if not rids:
            continue
        longest = max(rids, key=lambda r: (len(pool[r]), -r))
        order = [longest] + [r for r in rng.permutation(rids).tolist() if r != longest]
        for rid in order:
            if n_served >= SAMPLE_TOKENS or len(out) >= MAX_REF_REQUESTS:
                return out
            cost = padded_len(len(drv.prompts[rid]) + len(pool[rid]))
            if out and n_fwd + cost > MAX_REF_TOKENS:
                return out
            out.append((drv.prompts[rid], pool[rid]))
            n_served += len(pool[rid])
            n_fwd += cost
    return out


class Draw:
    """``draw(names)``: the named leaves made from the run's seed. The last
    set drawn is kept, so that asking for it again costs nothing."""

    def __init__(self, seed: int, leaves: dict, tied: bool):
        self.seed, self.leaves, self.tied = seed, leaves, tied
        self._last: tuple[frozenset, dict] = (frozenset(), {})

    def __call__(self, names) -> dict:
        key = frozenset(names)
        if key != self._last[0]:
            self._last = (frozenset(), {})      # free the last set first
            self._last = (key, weights.make(self.seed, self.leaves, self.tied, key))
        return self._last[1]


def reference_gaps(cell: Cell, seed: int, sample, smoke: bool,
                   quant: str | None = None) -> np.ndarray:
    """Every sampled served token's gap below the reference's best (or, with
    ``quant``, the control's reading at the same positions)."""
    ref, c = cell.ref, cell.config
    arch = ref.arch(sizes(c, smoke))
    draw = Draw(seed, ref.leaves(arch), c["tie_word_embeddings"])
    out = [ref.gaps(arch, draw, p, s, quant) for p, s in sample]
    del draw
    return np.concatenate(out) if out else np.zeros(0)


def checks(c: dict, drv: Driver, gaps: np.ndarray) -> dict:
    """Each number compared, with its limit. A finished request must hold
    at least the tokens it asked for; one that holds more is counted on the
    log (the engine serves two tokens to a request that asks for one)."""
    short = sum(1 for rid, toks in drv.finished.items()
                if len(toks) < drv.timeline[rid].max_new)
    extra = sum(1 for rid, toks in drv.finished.items()
                if len(toks) > drv.timeline[rid].max_new)
    say(f"finished requests holding more tokens than asked: {extra}")
    gap = float(gaps.max()) if gaps.size else float("inf")
    return {"logit_gap": {"value": gap, "limit": c["correct"]["max_logit_gap"]},
            "short_requests": {"value": short, "limit": 0},
            "tokens_compared": {"value": int(gaps.size), "limit": 1}}


def passed(chk: dict) -> bool:
    """Every compared number is within its limit (a count of compared
    tokens must reach its limit). A configuration without a limit on the
    gap has none that separates the program from its control, and cannot
    be correct."""
    return (chk["logit_gap"]["limit"] is not None
            and chk["logit_gap"]["value"] <= chk["logit_gap"]["limit"]
            and chk["short_requests"]["value"] <= chk["short_requests"]["limit"]
            and chk["tokens_compared"]["value"] >= chk["tokens_compared"]["limit"])


# ---------------------------------------------------------------- a run ---
@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: Cell
    ref: Any                    # the configuration's reference module
    arch: Any                   # ``ref.arch`` of the run's sizes
    peak: dict | None
    window: Window
    timeline: list[e2e.ReqTimeline]
    ticks: list[tuple[float, float, int]]
    setup: dict[str, float]
    engine_events: list[dict]
    dispatch: list[dict]
    trace: trace_reduce.Reduced | None


class Watch:
    """What the host spent on other things than serving while on: JAX's
    traces and backend compilations (its monitoring events, count and
    seconds; a program the persistent cache holds is traced but not
    compiled), and the garbage collector's passes."""

    def __init__(self):
        self.on = False
        self.seconds = {"trace": 0.0, "compile": 0.0, "gc": 0.0}
        self.count = {"trace": 0, "compile": 0, "gc": 0}
        self._gc_start = 0.0

    def jax_event(self, event: str, duration: float, *_a, **_k) -> None:
        kind = ("compile" if "backend_compile" in event else
                "trace" if "jaxpr_trace" in event else None)
        if self.on and kind:
            self.count[kind] += 1
            self.seconds[kind] += duration

    def gc_event(self, phase: str, _info: dict) -> None:
        if not self.on:
            return
        if phase == "start":
            self._gc_start = time.perf_counter()
        else:
            self.count["gc"] += 1
            self.seconds["gc"] += time.perf_counter() - self._gc_start

    def __str__(self) -> str:
        return ", ".join(f"{k} {self.count[k]} ({self.seconds[k]!r}s)"
                         for k in self.count)


def engine_counts(eng) -> dict[str, int]:
    """The engine's own counts of pool-dry evictions and blocked
    admissions."""
    return {"preemptions": int(eng.metrics.get("requests_preempted").total()),
            "admissions blocked on the page pool": int(
                eng.metrics.get("scheduler_decisions").get(kind="admit_blocked_pool"))}


def device_record(chips: int) -> dict:
    """Platform, kind and count of the devices as JAX reports them."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


@dataclasses.dataclass
class Setup:
    """The system under test, set up for one run."""

    cell: Cell
    seed: int
    smoke: bool
    eng: Any
    traffic: loadgen.Traffic
    drv: Driver
    phases: dict[str, float]
    dispatch: list[dict]
    tracer: Any
    sender: Sender | None


def set_up(cell: Cell, seed: int, seconds: float, smoke: bool = False,
           trace: bool = False) -> Setup:
    """Weights from the seed, calibration, the engine, the warm-up of the
    cell's buckets and decode step, and then the traffic's own set-up: in a
    closed loop every slot busy, in an open loop its lead-in (of a schedule
    that goes on for a window of ``seconds``)."""
    import jax
    from repro import obs

    c = cell.config
    s = sizes(c, smoke)
    eng_sz = {k: s[k] for k in ("slots", "max_context", "page_size", "num_pages")}
    phases: dict[str, float] = {}
    cfg = program_config(c, smoke, cell.ref)
    leaves = cell.ref.leaves(cell.ref.arch(s))
    check_leaves(leaves, param_leaves(cfg))
    params = program_params(cfg, leaves, seed, c["tie_word_embeddings"])
    jax.block_until_ready(params)
    if c.get("spiking"):
        t = time.perf_counter()
        cfg, params = calibrate(cfg, params, c, seed)
        jax.block_until_ready(params)
        phases["calibrate_s"] = time.perf_counter() - t
    tracer = obs.Tracer(obs.ListSink(), wall_time=True) if trace else None
    eng = make_engine(cfg, params, eng_sz, seed, tracer)
    del params
    traffic = loadgen.Traffic(
        cell.traffic, seed, cfg.vocab,
        max_prompt=eng_sz["max_context"] // 2 if smoke else None,
        max_new=eng_sz["max_context"] // 8 if smoke else None)
    lo, hi = traffic.prompt_bounds()
    sink = obs.ListSink()
    obs.set_tracer(obs.Tracer(sink))
    t = time.perf_counter()
    try:
        warm_up(eng, buckets(lo, hi, eng_sz["max_context"]), cfg.vocab)
    finally:
        obs.set_tracer(None)
    phases["warmup_s"] = time.perf_counter() - t
    if tracer is not None:
        tracer.sink.records.clear()
    drv = Driver(eng)
    sender = None
    if traffic.closed:
        fill_closed(drv, traffic)
    else:
        t = time.perf_counter()
        sender = lead_in(drv, traffic, seconds)
        phases["lead_in_s"] = time.perf_counter() - t
    return Setup(cell, seed, smoke, eng, traffic, drv, phases,
                 [r for r in sink.records if r["kind"] == "dispatch"], tracer,
                 sender)


def measure(st: Setup, seconds: float, trace: bool = False
            ) -> tuple[Window, trace_reduce.Reduced | None, Watch]:
    """The measured window (traced with ``trace``). Returns the window, the
    reduced trace, and what the host did besides serving in the window."""
    import jax

    watch = Watch()
    jax.monitoring.register_event_duration_secs_listener(watch.jax_event)
    gc.callbacks.append(watch.gc_event)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            trace_reduce.start(trace_dir)
        watch.on = True
        try:
            win = (run_closed(st.drv, st.traffic, seconds) if st.traffic.closed
                   else run_open(st.sender, seconds))
        finally:
            watch.on = False
            if trace:
                jax.profiler.stop_trace()
        reduced = None
        if trace:
            t = time.perf_counter()
            reduced = trace_reduce.reduce_dir(trace_dir, st.cell.chips)
            say(f"trace read and reduced in {time.perf_counter() - t!r}s")
    finally:
        gc.callbacks.remove(watch.gc_event)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return win, reduced, watch


def release(st: Setup) -> list[tuple[np.ndarray, list[int]]]:
    """Draw the reference's sample, then free the program's state (engine,
    weights, caches) so that the reference fits beside nothing else."""
    sample = pick_sample(st.drv, st.seed, st.cell.ref.padded_len)
    st.eng = st.drv.eng = None
    gc.collect()
    return sample


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        smoke: bool = False) -> dict:
    """One run of ``cell``: set-up, window, metrics, check. Returns the
    result object (the contract's last line)."""
    st = set_up(cell, seed, seconds, smoke, trace)
    setup_s = time.perf_counter() - t_start
    say(f"setup: {setup_s!r}s ({', '.join(f'{k} {v!r}s' for k, v in st.phases.items())})")
    n_before = engine_counts(st.eng)
    win, reduced, watch = measure(st, seconds, trace)
    drv = st.drv
    dev = device_record(cell.chips)
    dev["memory_peak_bytes"] = memory_peak(cell.chips)
    timeline = list(drv.timeline.values())
    in_window = [r for r in timeline if win.t0 <= r.due < win.t_close]
    failed = sum(1 for r in in_window if not r.stamps)
    late = drv.lateness
    win_ticks = [t for t in drv.ticks if win.t0 <= t[0] < win.t_close]
    say(f"window: {win.t1 - win.t0!r}s, {len(win_ticks)} ticks, requests sent "
        f"{len(timeline)} (due in window {len(in_window)}), finished "
        f"{len(drv.finished)}, failed {failed}; generator lateness max "
        f"{max(late, default=0.0)!r}s mean {float(np.mean(late)) if late else 0.0!r}s; "
        f"peak HBM {dev['memory_peak_bytes']}")
    say(f"in window: {watch}; "
        + ", ".join(f"{k} {v - n_before[k]}" for k, v in engine_counts(drv.eng).items()))
    say("longest ticks in window (s, busy slots): " + ", ".join(
        f"{t1 - t0!r} {n}" for t0, t1, n in sorted(
            win_ticks, key=lambda t: t[0] - t[1])[:3]))
    for d in {(r["site"], tuple(r["shape"]), r["impl"], r["reason"]): r
              for r in st.dispatch}.values():
        say(f"dispatch {d['site']} M={d['shape'][0]} K={d['shape'][1]} "
            f"N={d['shape'][2]} -> {d['impl']} ({d['reason']})")

    if trace:
        ctx = Context(cell=cell, ref=cell.ref,
                      arch=cell.ref.arch(sizes(cell.config, smoke)),
                      peak=None if smoke else peaks.peaks(dev["kind"]),
                      window=win, timeline=timeline, ticks=drv.ticks,
                      setup=st.phases, engine_events=list(st.tracer.sink.records),
                      dispatch=st.dispatch, trace=reduced)
        metrics = {}
        for m in cell.per_layer:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
    else:
        vals = e2e.metrics(timeline, win.t0, win.t1, win.t_close)
        vals["setup_s"] = setup_s
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in vals}

    sample = release(st)
    t = time.perf_counter()
    gaps = reference_gaps(cell, seed, sample, smoke)
    say(f"reference: {len(sample)} requests, {gaps.size} served tokens, "
        f"{time.perf_counter() - t!r}s")
    chk = checks(sizes(cell.config, smoke), drv, gaps)
    result: dict[str, Any] = {
        "correct": passed(chk),
        "attempted": len(timeline) if st.traffic.closed else len(in_window),
        "failed": failed, "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = chk
    for name, v in chk.items():
        say(f"check {name}: {v['value']!r} (limit {v['limit']!r})")
    return result
