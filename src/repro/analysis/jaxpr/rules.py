"""J1-J5: rules over captured jit cache entries (jaxpr level).

The AST rules (R1-R9) see source text; these see what XLA actually
compiled.  Each rule maps to a hazard this repo has already paid for
dynamically:

==== ==============================================================
J1   donation-miss: an arg in ``donate_argnums`` whose buffers
     cannot alias any output (shape/dtype mismatch) — XLA silently
     copies instead of updating in place; for the KV arena that is
     a full-arena copy per tick (the hazard PR-3's donation exists
     to prevent).
J2   host callback reachable from a hot graph (``debug_print``,
     ``pure_callback``, ``io_callback``): a device->host round trip
     per dispatch, the dynamic R4 class but inside XLA.
J3   duplicate traces: two cache entries whose canonical jaxprs are
     identical — jit keyed them apart (weak-type promotion, a
     shape-like Python arg left non-static) and one compile was
     pure waste (the PR-4 bucket-ladder bug class).
J4   large closure-captured constant baked into a graph: an
     arena-sized literal balloons the executable and silently pins
     a second copy of the data.
J5   trace-contract: any cache entry created after ``mark_warm()``
     (a serving-time compile stall), plus manifest drift handled by
     :mod:`repro.analysis.jaxpr.harness`.
==== ==============================================================
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.analysis.jaxpr.capture import (
    TraceEntry, canonical_jaxpr, iter_eqns,
)

#: primitives that round-trip through the host when executed
CALLBACK_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "outside_call", "host_callback_call", "callback",
}

#: default J4 threshold — bigger than any legitimate small table
#: (RoPE frequencies, iota masks), far below any KV arena / param slab
LARGE_CONST_BYTES = 1 << 16


@dataclasses.dataclass(frozen=True, order=True)
class TraceFinding:
    """One jaxpr-level finding.  ``fingerprint`` is line-free like the
    AST linter's, keyed by (config, fn, rule, message)."""
    config: str
    fn: str
    rule: str
    message: str

    @property
    def fingerprint(self) -> str:
        return f"{self.config}::{self.fn}::{self.rule}::{self.message}"

    def to_dict(self) -> dict:
        return {"config": self.config, "fn": self.fn, "rule": self.rule,
                "message": self.message}


# ------------------------------------------------------------------ J1
def check_donation(entry: TraceEntry) -> Iterable[TraceFinding]:
    """A donated buffer aliases an output only when some output has the
    same shape+dtype (XLA's matching rule).  Flattened leaf-level check:
    every donated invar aval must find a distinct matching output aval."""
    if entry.jaxpr is None or not entry.donate_argnums:
        return
    # leaf avals, stripped of weak-type decoration (aliasing ignores it)
    outs = Counter(a.rstrip("~w") for a in entry.out_avals)
    unmatched: List[str] = []
    # donate_argnums is recorded in flattened dynamic-leaf space (what
    # jax's Traced reports), i.e. indices straight into in_avals
    donated = [entry.in_avals[i] for i in entry.donate_argnums
               if i < len(entry.in_avals)]
    for aval in donated:
        key = aval.rstrip("~w")
        if outs[key] > 0:
            outs[key] -= 1
        else:
            unmatched.append(aval)
    if unmatched:
        yield TraceFinding(
            entry.config, entry.label, "J1",
            f"donate_argnums={list(entry.donate_argnums)} but "
            f"{len(unmatched)} donated buffer(s) {unmatched[:4]} match "
            f"no output shape/dtype — XLA cannot alias them and will "
            f"silently copy; drop the donation or return the updated "
            f"buffer")


# ------------------------------------------------------------------ J2
def check_callbacks(entry: TraceEntry) -> Iterable[TraceFinding]:
    if entry.jaxpr is None:
        return
    seen = set()
    for eqn in iter_eqns(entry.jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES and name not in seen:
            seen.add(name)
            yield TraceFinding(
                entry.config, entry.label, "J2",
                f"hot graph contains host callback primitive `{name}` — "
                f"every dispatch round-trips through Python; strip the "
                f"debug hook or move it behind an interpret-mode flag")


# ------------------------------------------------------------------ J3
def check_duplicates(entries: Sequence[TraceEntry]
                     ) -> Iterable[TraceFinding]:
    """Within one (config, fn): cache entries with identical canonical
    jaxprs were keyed apart for nothing — name the key bits that differ."""
    groups: Dict[Tuple[str, str], List[TraceEntry]] = {}
    for e in entries:
        if e.jaxpr is not None:
            groups.setdefault((e.config, e.label), []).append(e)
    for (config, label), group in sorted(groups.items()):
        by_canon: Dict[str, List[TraceEntry]] = {}
        for e in group:
            by_canon.setdefault(canonical_jaxpr(e.jaxpr), []).append(e)
        for dupes in by_canon.values():
            if len(dupes) < 2:
                continue
            yield TraceFinding(
                config, label, "J3",
                f"{len(dupes)} cache entries compile the identical "
                f"graph, keyed apart by {_key_diff(dupes)} — each extra "
                f"entry is a wasted compile; normalize the input dtype/"
                f"weak-type or declare the Python arg static")


def _key_diff(dupes: Sequence[TraceEntry]) -> str:
    bits = []
    if len({e.static_args for e in dupes}) > 1:
        bits.append(f"static args "
                    f"{sorted({e.static_args for e in dupes})!r}")
    if len({e.in_avals for e in dupes}) > 1:
        bits.append(f"input avals "
                    f"{sorted({','.join(e.in_avals) for e in dupes})!r}")
    return " and ".join(bits) or "an invisible key component"


# ------------------------------------------------------------------ J4
def check_large_consts(entry: TraceEntry,
                       threshold: int = LARGE_CONST_BYTES
                       ) -> Iterable[TraceFinding]:
    if entry.jaxpr is None:
        return
    import numpy as np
    for const in entry.jaxpr.consts:
        nbytes = getattr(const, "nbytes", None)
        if nbytes is None:
            try:
                nbytes = np.asarray(const).nbytes
            except (TypeError, ValueError):
                continue
        if nbytes >= threshold:
            shape = tuple(getattr(const, "shape", ()))
            dtype = getattr(const, "dtype", type(const).__name__)
            yield TraceFinding(
                entry.config, entry.label, "J4",
                f"closure-captured constant {dtype}{list(shape)} "
                f"({nbytes} bytes >= {threshold}) is baked into the "
                f"graph — pass it as an argument (donated if mutated) "
                f"instead of capturing it")


# ------------------------------------------------------------------ J5
def check_post_warm(entries: Sequence[TraceEntry]
                    ) -> Iterable[TraceFinding]:
    for e in entries:
        if e.post_warm:
            yield TraceFinding(
                e.config, e.label, "J5",
                f"new trace AFTER warmup (in={','.join(e.in_avals)} "
                f"static={e.static_args or '-'}) — a serving-time "
                f"compile stall; cover this shape in warmup buckets or "
                f"kill the retrace")


def run_rules(entries: Sequence[TraceEntry], *,
              large_const_bytes: int = LARGE_CONST_BYTES,
              rules: Optional[Sequence[str]] = None
              ) -> List[TraceFinding]:
    """Run all J-rules over a batch of captured entries."""
    want = set(rules) if rules is not None else None
    out: List[TraceFinding] = []

    def on(rule):
        return want is None or rule in want

    for e in entries:
        if on("J1"):
            out.extend(check_donation(e))
        if on("J2"):
            out.extend(check_callbacks(e))
        if on("J4"):
            out.extend(check_large_consts(e, large_const_bytes))
    if on("J3"):
        out.extend(check_duplicates(entries))
    if on("J5"):
        out.extend(check_post_warm(entries))
    return sorted(set(out))
