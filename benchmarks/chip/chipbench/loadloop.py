"""The benchmark's single-threaded load loop around one ``BatchServer``.

Each pass submits every request that is due (wire bytes, through
``submit_wire``), then calls ``step()``, the two calls that
``AsyncBatchServer.run_engine`` makes.  An empty engine sleeps until the
next due time.  After each ``step()`` the loop stamps every token that the
step emitted with the host clock, and notes how far each prompt's prefill
moved.  A request's due time, not its submit time, anchors its time to
first token, so a late loop shows up as latency and as generator lag.

Host spans: ``submit``, ``step`` and ``wait_arrival``, kept in memory as
``(name, start, end)``; with ``annotate`` set they are also written into
the profiler's trace, where they label the device's idle gaps.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

@dataclasses.dataclass
class Rec:
    """What the client side saw of one request."""
    req_id: int
    due: float                 # host clock
    prompt_len: int
    max_new: int
    submit_t: float = float("nan")
    stamps: List[float] = dataclasses.field(default_factory=list)
    prefilled: int = 0
    failed: bool = False

    @property
    def first_t(self) -> Optional[float]:
        return self.stamps[0] if self.stamps else None


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    # req_id -> (prefilled before, prefilled after) for prompts that moved
    prefill: Dict[int, Tuple[int, int]]


def _no_annotation(_name):
    return contextlib.nullcontext()


class LoadLoop:
    """Open loop (requests at their due times) or backlog (``pending``
    requests kept queued) over one server."""

    def __init__(self, server, reqs: Sequence, wires: Sequence[bytes],
                 t_origin: float, *,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 annotate: Callable = _no_annotation):
        self.server = server
        self.reqs = list(reqs)
        self.wires = list(wires)
        self.t_origin = t_origin
        self.clock, self.sleep, self.annotate = clock, sleep, annotate
        self.next = 0                      # index of the next to submit
        self.recs: Dict[int, Rec] = {}
        self.live: Dict[int, object] = {}  # req_id -> the engine's Request
        self.spans: List[Tuple[str, float, float]] = []
        self.ticks: List[Tick] = []
        self._n_done = len(server.completed_reqs)   # earlier runs' requests

    # --------------------------------------------------------------- spans
    @contextlib.contextmanager
    def _span(self, name: str):
        t0 = self.clock()
        with self.annotate(name):
            yield
        self.spans.append((name, t0, self.clock()))

    # -------------------------------------------------------------- submit
    def _submit(self, due: Optional[float] = None):
        req = self.reqs[self.next]
        rec = Rec(req.req_id,
                  self.t_origin + req.due if due is None else due,
                  len(req.prompt), req.max_new)
        with self._span("submit"):
            rec.submit_t = self.clock()
            self.server.submit_wire(self.wires[self.next])
        self.recs[req.req_id] = rec
        self.next += 1

    def _submit_due(self, now: float):
        while self.next < len(self.reqs) and \
                self.t_origin + self.reqs[self.next].due <= now:
            self._submit()

    def _refill(self, pending: int):
        while self.next < len(self.reqs) and \
                len(self.server.queue) < pending:
            self._submit(due=self.clock())

    def next_due(self) -> Optional[float]:
        if self.next >= len(self.reqs):
            return None
        return self.t_origin + self.reqs[self.next].due

    # ---------------------------------------------------------------- step
    def busy(self) -> bool:
        return bool(len(self.server.queue) or self.server.active)

    def _observe(self, t: float, tick: Tick):
        """Stamp the tokens and prefill progress of the step just done."""
        done = self.server.completed_reqs
        for req in done[self._n_done:]:
            self.live[req.req_id] = req
        seen = list(self.server.active.values()) + done[self._n_done:]
        self._n_done = len(done)
        for req in seen:
            rec = self.recs.get(req.req_id)
            if rec is None:
                continue
            self.live[req.req_id] = req
            n = len(req.generated)
            if n > len(rec.stamps):
                rec.stamps.extend([t] * (n - len(rec.stamps)))
            if req.prefilled != rec.prefilled:
                tick.prefill[req.req_id] = (rec.prefilled, req.prefilled)
                rec.prefilled = req.prefilled
            if getattr(req.state, "value", req.state) == "FAILED":
                rec.failed = True

    def step(self):
        t0 = self.clock()
        with self._span("step"):
            self.server.step()
        t1 = self.clock()
        tick = Tick(t0, t1, {})
        self._observe(t1, tick)
        self.ticks.append(tick)

    # ----------------------------------------------------------------- run
    def run(self, t_stop: float, *, pending: int = 0,
            until: Optional[Callable[[], bool]] = None):
        """Drive until the clock passes ``t_stop`` (a step under way
        finishes first), ``until()`` holds, or nothing is left to do.
        Requests that fell due during the last step are submitted before
        returning, so none due before ``t_stop`` waits for a later loop."""
        while True:
            now = self.clock()
            if now >= t_stop or (until is not None and until()):
                if not pending:
                    self._submit_due(min(now, t_stop))
                return
            if pending:
                self._refill(pending)
            else:
                self._submit_due(now)
            if self.busy():
                self.step()
                continue
            nxt = self.next_due()
            if nxt is None or pending:
                return
            with self._span("wait_arrival"):
                self.sleep(max(0.0, min(nxt, t_stop) - now))
