"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The trace is read with ``jax.profiler.ProfileData`` into plain events
``(plane, line, name, start_ns, dur_ns, stats)``; everything after that
works on those tuples, so a small recorded trace checks the reduction.

* Device operations: events on the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane.  Busy time is the union of their intervals
  inside the window, averaged over the chips used.
* The window: the host span ``window`` that the harness opens around the
  measured steps.
* Operations nest (a layer scan's ``while`` holds its body's ops), so
  the top operations are ranked by self time, each under the program
  that ran it and with its output shape.
* Kernels: an operation whose own HLO name holds the kernel's name
  (``%paged_attention.4 = ... custom-call(...)``; a consumer's text names
  the kernel too, so only the part before `` = `` counts).  The server
  jits anonymous lambdas, so a program (an ``XLA Modules`` event) is named
  by the kernel it runs: ``chunk_prefill`` holds
  ``paged_prefill_attention``, ``decode`` holds ``paged_attention``.
* Idle gaps: stretches of the window with no device operation, labelled
  with the harness's host span that overlaps them most.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_SPANS = ("submit", "step", "wait_arrival")
# the prefill kernel's name contains the decode kernel's: test it first
KERNELS = (("paged_prefill_attention", re.compile(r"paged_prefill_attention")),
           ("paged_attention", re.compile(r"(?<!prefill_)paged_attention")))


@dataclasses.dataclass(frozen=True)
class Ev:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def text(self) -> str:
        return " ".join([self.name] + [v for _, v in self.stats])


def load(trace_dir: str) -> List[Ev]:
    """Every event of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                stats = tuple((str(k), str(v)) for k, v in e.stats)
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns),
                              stats))
    return out


def op_name(ev: Ev) -> str:
    """``%copy.60 = bf16[12,4609,...]{...} copy(...)`` -> ``copy.60``."""
    return ev.name.split(" = ", 1)[0].lstrip("%")


def op_shape(ev: Ev) -> str:
    """The output shape of an HLO op event, without its layout."""
    rest = ev.name.split(" = ", 1)
    if len(rest) < 2:
        return ""
    shape = rest[1].split(" ", 1)[0]
    return "tuple" if shape.startswith("(") else shape.split("{", 1)[0]


def kernel_of(ev: Ev) -> Optional[str]:
    name = op_name(ev)
    for kernel, pat in KERNELS:
        if pat.search(name):
            return kernel
    return None


def self_times(ops: Sequence[Ev]) -> List[float]:
    """Each op's duration less its directly nested ops' (one line)."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start_ns, -ops[i].dur_ns))
    child = [0.0] * len(ops)
    stack: List[int] = []
    for i in order:
        e = ops[i]
        while stack and ops[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            child[stack[-1]] += overlap(e.start_ns, e.end_ns,
                                        ops[stack[-1]].start_ns,
                                        ops[stack[-1]].end_ns)
        stack.append(i)
    return [max(0.0, e.dur_ns - c) for e, c in zip(ops, child)]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                        # mean over the chips used
    kernel_s: Dict[str, float]           # kernel -> device seconds
    prefill_program_s: float             # device seconds of those programs
    device_ops: List[Tuple[str, float]]  # top operations by self seconds
    idle_gaps: List[Tuple[str, float]]   # longest gaps, by host span
    n_ops: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_of(evs: Sequence[Ev]) -> Tuple[float, float]:
    ws = [e for e in evs if e.name == "window"
          and not DEVICE_PLANE.match(e.plane)]
    if len(ws) != 1:
        raise RuntimeError(f"expected one 'window' host span, found {len(ws)}")
    return ws[0].start_ns, ws[0].end_ns


def reduce(evs: Sequence[Ev], top: int = 10) -> Reduced:
    w0, w1 = window_of(evs)
    ops = [e for e in evs if DEVICE_PLANE.match(e.plane)
           and e.line == OPS_LINE and e.end_ns > w0 and e.start_ns < w1]
    mods = [e for e in evs if DEVICE_PLANE.match(e.plane)
            and e.line == MODULES_LINE and e.end_ns > w0 and e.start_ns < w1]
    chips = sorted({e.plane for e in ops})
    if not ops:
        raise RuntimeError("no device operation inside the traced window")

    busy_by_chip = {}
    for c in chips:
        iv = clip(union((e.start_ns, e.end_ns) for e in ops if e.plane == c),
                  w0, w1)
        busy_by_chip[c] = iv
    busy_ns = sum(b - a for iv in busy_by_chip.values()
                  for a, b in iv) / len(chips)

    kernel_ns: Dict[str, float] = defaultdict(float)
    marks = defaultdict(list)                     # plane -> (start, kernel)
    for e in ops:
        k = kernel_of(e)
        if k is not None:
            kernel_ns[k] += overlap(e.start_ns, e.end_ns, w0, w1)
            marks[e.plane].append((e.start_ns, k))
    for v in marks.values():
        v.sort()

    # each program execution named by the kernel it runs
    progs = defaultdict(list)            # plane -> (start, end, label)
    prefill_prog_ns = 0.0
    for m in mods:
        v = marks.get(m.plane, [])
        i = bisect.bisect_left(v, (m.start_ns, ""))
        inside = set()
        while i < len(v) and v[i][0] < m.end_ns:
            inside.add(v[i][1])
            i += 1
        label = ("chunk_prefill" if "paged_prefill_attention" in inside
                 else "decode" if "paged_attention" in inside else "other")
        progs[m.plane].append((m.start_ns, m.end_ns, label))
        if label == "chunk_prefill":
            prefill_prog_ns += overlap(m.start_ns, m.end_ns, w0, w1)
    prefill_prog_ns /= len(chips)
    for v in progs.values():
        v.sort()

    def program_of(e: Ev) -> str:
        v = progs.get(e.plane, [])
        i = bisect.bisect_right(v, (e.start_ns, float("inf"), "")) - 1
        return v[i][2] if i >= 0 and e.start_ns < v[i][1] else "other"

    by_name: Dict[str, float] = defaultdict(float)
    for c in chips:
        on = [e for e in ops if e.plane == c]
        for e, st in zip(on, self_times(on)):
            if e.dur_ns <= 0:
                continue
            share = overlap(e.start_ns, e.end_ns, w0, w1) / e.dur_ns
            key = f"{program_of(e)}/{op_name(e)} {op_shape(e)}".strip()
            by_name[key] += st * share / len(chips)

    host = [e for e in evs if e.name in HOST_SPANS
            and not DEVICE_PLANE.match(e.plane)]
    gaps = []
    for c in chips:
        iv = busy_by_chip[c]
        edges = [w0] + [x for a, b in iv for x in (a, b)] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                label, best = "other", 0.0
                for h in host:
                    o = overlap(a, b, h.start_ns, h.end_ns)
                    if o > best:
                        label, best = h.name, o
                gaps.append((label, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ops_top = sorted(((n, t * 1e-9) for n, t in by_name.items()),
                     key=lambda x: -x[1])[:top]
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9,
                   kernel_s={k: v * 1e-9 for k, v in kernel_ns.items()},
                   prefill_program_s=prefill_prog_ns * 1e-9,
                   device_ops=ops_top, idle_gaps=gaps[:top], n_ops=len(ops))

