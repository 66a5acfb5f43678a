"""The open loop's lead-in, on a stand-in engine and a stand-in clock: the
window opens on an engine that the schedule has already loaded, goes on
with the same schedule, and counts only the requests due inside it."""
import numpy as np
import pytest

from chipbench import harness, loadgen


class Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


class Engine:
    """Serves one token per busy slot per tick of 0.1 s."""

    def __init__(self, clock, slots=4):
        self.clock, self.B = clock, slots
        self.queue, self.results = [], []
        self.slot_req = [None] * slots
        self.out_tokens = [[] for _ in range(slots)]
        self.active = np.zeros(slots, bool)

    def submit(self, req):
        self.queue.append(req)

    def tick(self):
        for b in range(self.B):
            if not self.active[b] and self.queue:
                req = self.queue.pop(0)
                req.prefix = []
                self.slot_req[b], self.out_tokens[b], self.active[b] = req, [], True
        for b in range(self.B):
            if self.active[b]:
                self.out_tokens[b].append(7)
                req = self.slot_req[b]
                if len(self.out_tokens[b]) == req.max_new_tokens:
                    self.results.append(type("R", (), {"rid": req.rid,
                                                       "tokens": self.out_tokens[b]}))
                    self.slot_req[b], self.active[b] = None, False
        self.clock.t += 0.1


@pytest.fixture
def driven(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(harness.time, "sleep", lambda s: setattr(clock, "t", clock.t + s))
    params = {"loop": "open", "arrival": {"process": "poisson", "rate": 2.0},
              "lead_in_s": 10,
              "prompt": {"dist": "uniform", "min": 4, "max": 8},
              "output": {"dist": "uniform", "min": 5, "max": 15}}
    traffic = loadgen.Traffic(params, 3, 100)
    drv = harness.Driver(Engine(clock), clock=clock)
    sender = harness.lead_in(drv, traffic, 20.0)
    return clock, drv, sender


def test_the_window_opens_on_a_loaded_engine(driven):
    clock, drv, sender = driven
    assert clock.t >= sender.origin + 10.0
    assert 10 <= sender.i < len(sender.sched) == 60     # 2/s over 10 + 20 s
    assert drv.eng.active.any()
    assert all(r.due < clock.t for r in drv.timeline.values())


def test_the_window_counts_what_falls_due_in_it(driven):
    clock, drv, sender = driven
    sent_before = len(drv.timeline)
    win = harness.run_open(sender, 20.0)
    # it opens where the schedule says, even where the lead-in's last tick
    # ran past that point
    assert win.t0 == sender.origin + 10.0 < clock.t
    assert win.t_close == pytest.approx(win.t0 + 20.0)
    assert sender.i == len(sender.sched)                 # the schedule ran out
    due = [r for r in drv.timeline.values() if win.t0 <= r.due < win.t_close]
    assert len(due) == len(drv.timeline) - sent_before
    assert all(r.stamps for r in due)
    # the requests sent in the lead-in finish in the window, and count there
    # as tokens and not as requests due
    assert any(r.due < win.t0 and r.stamps[-1] > win.t0
               for r in list(drv.timeline.values())[:sent_before])
