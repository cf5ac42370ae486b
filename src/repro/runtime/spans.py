"""Host spans of the serving tick, in ``stats`` and on the profiler's clock.

A span times one stage of ``BatchServer.step`` two ways at once: it adds
its elapsed ``time.perf_counter()`` seconds to a flat key of the engine's
``stats`` (``span_key``), and it is a ``jax.profiler.TraceAnnotation`` of
the same name, so that under a running profiler it lands on the host
timeline, the clock of the device's ``XLA Ops``.  With no profiler
running a span costs one clock pair, one dict add and one inactive TraceMe.

Keys stay flat (``decode_select_wall_s``, not ``stats["decode"]["select"]``)
so that a ``dict(stats)`` snapshot copies every value.

A span can be split into contiguous stages: ``stage(name)`` ends the
running stage and starts the next at one clock reading, the first stage
starts where the span did and the last ends where it does, so the span's
time is exactly the sum of its stages' (``decode_wall_s`` is prep +
dispatch + wait + select).
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import jax

#: every span the engine opens; ``stats`` holds a ``span_key`` for each
SPANS = ("tick", "admit", "chunk", "chunk.prep", "chunk.dispatch",
         "chunk.wait", "chunk.select", "decode", "decode.prep",
         "decode.dispatch", "decode.wait", "decode.select", "harvest",
         "niccost")


def span_key(name: str) -> str:
    """``"decode.select"`` -> ``"decode_select_wall_s"``."""
    return name.replace(".", "_") + "_wall_s"


_KEYS = {name: span_key(name) for name in SPANS}


class Span:
    """One timed span over ``stats`` (a context manager); see the module
    docstring."""

    __slots__ = ("_stats", "_name", "_ann", "_t0", "_stage", "_stage_ann",
                 "_stage_t0")

    def __init__(self, stats: Dict, name: str):
        self._stats = stats
        self._name = name
        self._stage: Optional[str] = None
        self._stage_ann = None

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = self._stage_t0 = time.perf_counter()
        return self

    def stage(self, name: str):
        """End the running stage, if any, and start ``name`` now (the
        first stage starts at the span's own start)."""
        t = time.perf_counter()
        if self._stage is not None:
            self._end_stage(t)
        self._stage = name
        self._stage_ann = jax.profiler.TraceAnnotation(name)
        self._stage_ann.__enter__()

    def _end_stage(self, t: float):
        self._stage_ann.__exit__(None, None, None)
        self._stats[_KEYS[self._stage]] += t - self._stage_t0
        self._stage_t0 = t

    def __exit__(self, *exc):
        t = time.perf_counter()
        if self._stage is not None:
            self._end_stage(t)
        self._stats[_KEYS[self._name]] += t - self._t0
        self._ann.__exit__(*exc)
        return False


def zeroed() -> Dict[str, float]:
    """A ``stats`` entry of 0.0 for every span."""
    return {key: 0.0 for key in _KEYS.values()}
