"""What one run saw, handed to every metric reader (``metrics/<name>.py``).

A reader is a module with ``read(w: Window) -> float | None``; it returns
``None`` where the run gives it nothing to read, and the harness then
leaves the metric out of the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from typing import Dict, List, Optional, Tuple

from chipbench import counts
from chipbench.dims import Dims
from chipbench.spec import BENCH_DIR, Cell


@dataclasses.dataclass
class Window:
    cell: Cell
    dims: Dims
    peak: Dict
    setup_s: float
    w0: float                      # host clock: window start
    w1: float                      # host clock: end of the last step
    due_s: float                   # requests due in [w0, w0 + due_s) count
    recs: Dict                     # req_id -> loadloop.Rec
    ticks: List                    # loadloop.Tick, every tick of the run
    spans: List[Tuple[str, float, float]]
    stats0: Dict                   # BatchServer.stats at w0
    stats1: Dict                   # BatchServer.stats at w1
    slots: int
    chunk_buckets: Tuple[int, ...]
    trace: Optional[object] = None  # trace.Reduced of a --trace 1 run

    @property
    def seconds(self) -> float:
        return self.w1 - self.w0

    def delta(self, key: str) -> float:
        return self.stats1[key] - self.stats0[key]

    def window_ticks(self) -> List:
        return [t for t in self.ticks if self.w0 < t.end <= self.w1]

    def decode_tokens(self) -> List[Tuple[int, int]]:
        """(prompt_len, token index >= 1) of every token that a decode
        step emitted inside the window."""
        out = []
        for r in self.recs.values():
            for i, t in enumerate(r.stamps):
                if i >= 1 and self.w0 < t <= self.w1:
                    out.append((r.prompt_len, i))
        return out

    def prefill_spans(self) -> List[Tuple[int, int, int]]:
        """(p0, p1, prompt_len) per request: the prompt positions that
        the window's chunk steps prefilled."""
        span: Dict[int, List[int]] = {}
        for t in self.window_ticks():
            for rid, (a, b) in t.prefill.items():
                if rid in span:
                    span[rid][1] = b
                else:
                    span[rid] = [a, b]
        return [(a, b, self.recs[rid].prompt_len)
                for rid, (a, b) in span.items()]

    def prefill_rows(self) -> Tuple[int, int]:
        """(prompt tokens prefilled, rows computed): a chunk step computes
        every slot at the tick's bucket, the smallest that holds its
        largest chunk."""
        used = rows = 0
        for t in self.window_ticks():
            if not t.prefill:
                continue
            moved = [b - a for a, b in t.prefill.values()]
            used += sum(moved)
            rows += self.slots * next(c for c in self.chunk_buckets
                                      if c >= max(moved))
        return used, rows

    def model_flops(self) -> float:
        """Useful model FLOPs of the window: decoded tokens and prefilled
        prompt positions."""
        m = self.dims
        f = sum(counts.token_flops(m, p + i - 1, True)
                for p, i in self.decode_tokens())
        f += sum(counts.prefill_flops(m, a, b, p)
                 for a, b, p in self.prefill_spans())
        return float(f)


def reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
