"""The open-loop load loop's time to first token and gaps between tokens:
anchored at the due time, so a late generator shows; a failed request
counts as missing."""
import math
import types

import numpy as np
import pytest

from chipbench import loadloop, stats
from chipbench.traffic import Req


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeServer:
    """Admits from its queue into free slots, and per step gives every
    active request one token (its first once its prompt is in); a request
    with an empty prompt fails at admission.  Each step and each submit
    takes fixed clock time."""

    def __init__(self, clock, slots=2, step_s=0.1, submit_s=0.0):
        self.clock, self.slots = clock, slots
        self.step_s, self.submit_s = step_s, submit_s
        self.queue, self.active, self.completed_reqs = [], {}, []

    def submit_wire(self, buf):
        rid, plen, max_new = buf
        self.clock.t += self.submit_s
        self.queue.append(types.SimpleNamespace(
            req_id=rid, prompt=[1] * plen, max_new=max_new, generated=[],
            prefilled=0, state=types.SimpleNamespace(value="QUEUED")))

    def step(self):
        self.clock.t += self.step_s
        for slot in range(self.slots):
            if slot not in self.active and self.queue:
                r = self.queue.pop(0)
                if not r.prompt:
                    r.state.value = "FAILED"
                    self.completed_reqs.append(r)
                    continue
                r.state.value = "DECODE"
                self.active[slot] = r
        for slot, r in list(self.active.items()):
            r.prefilled = len(r.prompt)
            r.generated.append(7)
            if len(r.generated) >= r.max_new:
                r.state.value = "DONE"
                self.completed_reqs.append(self.active.pop(slot))


def _reqs(dues, plen=4, max_new=3):
    return [Req(i, d, np.ones(plen, np.int32), max_new)
            for i, d in enumerate(dues)]


def _wires(reqs, plens=None):
    plens = plens or [len(r.prompt) for r in reqs]
    return [(r.req_id, p, r.max_new) for r, p in zip(reqs, plens)]


def test_ttft_and_gaps_on_time():
    clk = Clock()
    srv = FakeServer(clk)
    reqs = _reqs([0.0, 0.05])
    d = loadloop.LoadLoop(srv, reqs, _wires(reqs), clk(), clock=clk,
                      sleep=clk.sleep)
    w0 = clk()
    d.run(w0 + 10)
    r0, r1 = d.recs[0], d.recs[1]
    # request 0 submitted at once; its first token after one 0.1 s step
    assert r0.first_t - r0.due == pytest.approx(0.1)
    # request 1 is due at +0.05, submitted after that first step
    assert r1.submit_t == pytest.approx(w0 + 0.1)
    assert r1.first_t - r1.due == pytest.approx(0.15)
    assert stats.ttfts(d.recs.values(), w0, w0 + 1) == \
        pytest.approx([0.1, 0.15])
    assert stats.gaps(d.recs.values(), w0, w0 + 10) == \
        pytest.approx([0.1] * 4)
    assert stats.tokens_in(d.recs.values(), w0, w0 + 10) == 6
    assert [n for n, *_ in d.spans].count("step") == 4


def test_late_generator_counts_from_the_due_time():
    clk = Clock()
    srv = FakeServer(clk, slots=4, submit_s=0.5)    # a slow submit path
    reqs = _reqs([0.0, 0.0, 0.0])
    d = loadloop.LoadLoop(srv, reqs, _wires(reqs), clk(), clock=clk,
                      sleep=clk.sleep)
    w0 = clk()
    d.run(w0 + 10)
    lag = [r.submit_t - r.due for r in d.recs.values()]
    assert lag == pytest.approx([0.0, 0.5, 1.0])
    # all three get their first token after the one step that follows
    # the three submits: 1.5 s of submits + 0.1 s of step, from due 0
    assert stats.ttfts(d.recs.values(), w0, w0 + 1) == \
        pytest.approx([1.6, 1.6, 1.6])


def test_failed_request_counts_as_missing():
    clk = Clock()
    srv = FakeServer(clk, slots=4)
    reqs = _reqs([0.0] * 10)
    wires = _wires(reqs, plens=[4] * 9 + [0])        # the last one fails
    d = loadloop.LoadLoop(srv, reqs, wires, clk(), clock=clk, sleep=clk.sleep)
    w0 = clk()
    d.run(w0 + 10)
    assert d.recs[9].failed and d.recs[9].first_t is None
    t = stats.ttfts(d.recs.values(), w0, w0 + 1)
    assert len(t) == 10 and math.isinf(max(t))
    assert stats.pct(t, 90) < math.inf      # 1 of 10 is beyond p90
    assert math.isinf(stats.pct(t, 95))


def test_window_bounds():
    clk = Clock()
    srv = FakeServer(clk)
    reqs = _reqs([0.0, 0.0, 2.0])
    d = loadloop.LoadLoop(srv, reqs, _wires(reqs), clk(), clock=clk,
                      sleep=clk.sleep)
    w0 = clk()
    d.run(w0 + 10)
    # request 2 waits for its due time in a wait_arrival span
    waits = stats.in_window(d.spans, "wait_arrival", w0, w0 + 10)
    assert len(waits) == 1 and waits[0] == pytest.approx(1.7)
    assert stats.due_in(d.recs.values(), w0, w0 + 1) == \
        [d.recs[0], d.recs[1]]
    assert stats.tokens_in(d.recs.values(), w0, w0 + 0.2) == 4


def test_backlog_keeps_the_queue_full():
    clk = Clock()
    srv = FakeServer(clk, slots=2)
    reqs = _reqs([0.0] * 20, max_new=5)
    d = loadloop.LoadLoop(srv, reqs, _wires(reqs), clk(), clock=clk,
                      sleep=clk.sleep)
    seen = []
    orig = srv.step

    def step():
        seen.append(len(srv.queue))
        orig()
    srv.step = step
    d.run(clk() + 1.0, pending=3)
    assert min(seen) >= 3


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.pct(xs, 90) == 90 and stats.pct(xs, 95) == 95
    assert stats.pct([5.0], 90) == 5.0
    assert stats.pct([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        stats.pct([], 50)
