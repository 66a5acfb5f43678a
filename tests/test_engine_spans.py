"""The serve engine's phase spans (docs/observability.md).

A traced engine tiles every tick with ``schedule``/``pages``/``inputs``/
``step``/``finish`` spans inside one ``tick`` span, ends each ``prefill``
span once the first token is on the host, opens a profiler annotation
``engine.<kind>`` for every span, and serves exactly the tokens an
untraced engine serves.
"""
import glob
import itertools
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.sharding import init_params
from repro.models import model
from repro.obs import ListSink, Tracer
from repro.obs import trace as obs_trace
from repro.serve.engine import Engine, Request

PHASES = {"schedule", "pages", "inputs", "step", "finish"}


class RecordingSink(ListSink):
    """Keeps, for each ``prefill`` record, the slot's tokens at the moment
    the record was written."""

    def __init__(self):
        super().__init__()
        self.eng = None
        self.first_at_write = {}

    def write(self, record):
        if record["kind"] == "prefill":
            self.first_at_write[record["rid"]] = list(
                self.eng.out_tokens[record["slot"]])
        super().write(record)


def _serve(cfg, params, tracer=None, wall_time=False):
    eng = Engine(cfg, params, batch_slots=2, max_context=32, paged=True,
                 page_size=8, record_logits=True, tracer=tracer,
                 wall_time=wall_time)
    if tracer is not None:
        tracer.sink.eng = eng
    rng = np.random.default_rng(5)
    for i, plen in enumerate((7, 12, 5)):
        eng.submit(Request(rid=i, tokens=[int(t) for t in
                                          rng.integers(3, cfg.vocab, plen)],
                           max_new_tokens=3 + i, temperature=0.0))
    eng.run()
    return eng


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One untraced run, and the same run traced on a step clock (each read
    advances it by one second) under the profiler."""
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(0))
    plain = _serve(cfg, params)
    clock = itertools.count()
    tracer = Tracer(RecordingSink(), wall_time=True,
                    clock=lambda: float(next(clock)))
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(trace_dir)
    try:
        traced = _serve(cfg, params, tracer, wall_time=True)
    finally:
        jax.profiler.stop_trace()
    return plain, traced, tracer.sink, trace_dir


def _ticks(records):
    """(tick record, the records its block emitted) in order: nested spans
    close, and emit, before the tick that holds them."""
    out, inner = [], []
    for r in records:
        if r["kind"] == "tick":
            out.append((r, inner))
            inner = []
        else:
            inner.append(r)
    return out


def test_every_tick_is_tiled_by_its_phase_spans(runs):
    _, traced, sink, _ = runs
    ticks = _ticks(sink.records)
    busy = [(t, inner) for t, inner in ticks if t["active"]]
    assert len(busy) == traced.ticks > 0
    for t, inner in busy:
        kinds = {r["kind"] for r in inner if r.get("tick") == t["tick"]}
        assert PHASES <= kinds, (t, kinds)
        phases = [r for r in inner if r["kind"] in PHASES]
        assert sorted(r["kind"] for r in phases) == sorted(PHASES)
        assert sum(r["dur_ms"] for r in phases) <= t["dur_ms"]
    # the decode event still comes once per busy tick, with its fields
    decodes = [r for r in sink.records if r["kind"] == "decode"]
    assert [d["active"] for d in decodes] == [t["active"] for t, _ in busy]


def test_prefill_spans_nest_in_schedule_and_end_on_the_first_token(runs):
    _, traced, sink, _ = runs
    for t, inner in _ticks(sink.records):
        sched = next(r for r in inner if r["kind"] == "schedule")
        pre = [r for r in inner if r["kind"] == "prefill"]
        assert sum(r["dur_ms"] for r in pre) <= sched["dur_ms"]
        for r in pre:
            assert {"rid", "slot", "bucket", "prompt_len", "tick"} <= set(r)
    first = {r.rid: r.tokens[0] for r in traced.results}
    # written once the slot holds its first token as a host integer
    assert sink.first_at_write == {rid: [tok] for rid, tok in first.items()}


def test_traced_engine_serves_the_untraced_tokens_bitwise(runs):
    plain, traced, _, _ = runs
    assert {r.rid: r.tokens for r in plain.results} == \
        {r.rid: r.tokens for r in traced.results}
    for rid, trace in plain.logit_trace.items():
        assert len(trace) == len(traced.logit_trace[rid])
        for a, b in zip(trace, traced.logit_trace[rid]):
            assert np.array_equal(a, b)


def test_wall_time_engine_observes_one_tick_ms_per_decoding_tick(runs):
    plain, traced, _, _ = runs
    hist = traced.metrics.get("tick_ms")
    assert hist.count() == traced.ticks > 0
    assert plain.metrics.get("tick_ms").count() == 0


def test_spans_reach_the_profiler_trace_as_engine_annotations(runs):
    from jax.profiler import ProfileData

    *_, trace_dir = runs
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host")
             for line in plane.lines for e in line.events}
    assert {"engine." + k for k in PHASES | {"tick", "prefill"}} <= names


def test_a_span_opens_an_annotation_named_engine_kind(monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Annotation)
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(0))
    sink = ListSink()
    eng = Engine(cfg, params, batch_slots=2, max_context=32, paged=True,
                 page_size=8, tracer=Tracer(sink))
    with eng._span("step"):
        opened.append(("body", None))
    assert opened == [("enter", "engine.step"), ("body", None),
                      ("exit", "engine.step")]
    assert sink.records == [{"kind": "step", "seq": 0, "tick": 0}]
    # a bare tracer span is annotated by its kind, with no attributes
    opened.clear()
    with Tracer(ListSink()).span("other", rid=3):
        pass
    assert opened == [("enter", "other"), ("exit", "other")]


def test_decode_events_count_the_pages_attention_reads():
    """A paged engine's ``decode`` event carries ``kv_pages``, the pages
    holding positions 0..pos of every active slot at that step, and
    ``kv_pages_view``, the slots × logical pages of the whole-context
    view."""
    cfg = get_config("olmo_1b", smoke=True)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(0))
    tracer = Tracer(ListSink())
    eng = Engine(cfg, params, batch_slots=2, max_context=32, paged=True,
                 page_size=8, tracer=tracer)
    seen = []
    step = eng._decode_paged

    def recording_step(params, last, pos, pools, tables):
        live = np.asarray(pos)[eng.active]
        seen.append(sum(-(-(int(p) + 1) // 8) for p in live))
        return step(params, last, pos, pools, tables)

    eng._decode_paged = recording_step
    rng = np.random.default_rng(3)
    for i, plen in enumerate((7, 16, 3)):
        eng.submit(Request(rid=i, tokens=[int(t) for t in
                                          rng.integers(3, cfg.vocab, plen)],
                           max_new_tokens=4, temperature=0.0))
    eng.run()
    decodes = [r for r in tracer.sink.records if r["kind"] == "decode"]
    assert [d["kv_pages"] for d in decodes] == seen
    assert max(seen) >= 3                 # a slot past its second page
    assert {d["kv_pages_view"] for d in decodes} == {2 * 32 // 8}
