"""R4 — host syncs inside scheduler-tick-reachable functions.

A device->host materialization (``np.asarray`` on a traced output,
``.item()``, ``float()``, ``jax.block_until_ready``) inside the tick
loop serializes the async engine's dispatch overlap: every tick waits
for the device instead of queueing the next step.  The server keeps a
small set of *intentional* sync points (the wait for the logits and the
argmax that feeds sampled tokens back into Python) — those
carry inline ``# repro-lint: disable=R4 -- reason`` suppressions, which
is this rule's explicit allowlist.

Hot set = functions reachable from the seeds below through same-file
calls (``self.f(...)`` or bare ``f(...)``), computed per hot module.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set

from repro.analysis.engine import (
    FileContext, Finding, Rule, call_name, register,
)

# module -> scheduler-tick entry points (the per-tick loop and the
# engine coroutines that drive it)
HOT_MODULES: Dict[str, tuple] = {
    "src/repro/runtime/server.py": ("step", "run_until_drained",
                                    "run_engine"),
    "src/repro/runtime/scheduler.py": ("admit", "advance", "release",
                                       "release_behind", "bind",
                                       "claim_ticket", "pop_admissible"),
}

_SYNC_CALLS = {"jax.block_until_ready", "jax.device_get"}
_ASARRAY = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
            "jax.device_get"}
_SYNC_METHODS = {"item", "block_until_ready"}


def _function_index(tree: ast.Module) -> Dict[str, ast.AST]:
    return {n.name: n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def _callees(fn: ast.AST) -> Set[str]:
    """Names this function calls as ``self.X(...)`` or ``X(...)``."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name):
            out.add(f.id)
        elif isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name) and f.value.id == "self":
            out.add(f.attr)
    return out


@register
class HostSyncRule(Rule):
    id = "R4"
    title = "host sync on the scheduler-tick hot path"

    def applies(self, rel: str) -> bool:
        return rel in HOT_MODULES

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        index = _function_index(ctx.tree)
        hot: Set[str] = set()
        frontier = [s for s in HOT_MODULES[ctx.rel] if s in index]
        while frontier:
            name = frontier.pop()
            if name in hot:
                continue
            hot.add(name)
            frontier.extend(c for c in _callees(index[name])
                            if c in index and c not in hot)
        out: List[Finding] = []
        for name in sorted(hot):
            out.extend(self._check_fn(ctx, name, index[name]))
        return out

    def _check_fn(self, ctx: FileContext, fname: str,
                  fn: ast.AST) -> Iterable[Finding]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            where = f"`{fname}` is reachable from the scheduler tick"
            if name in _SYNC_CALLS:
                yield ctx.finding(
                    self.id, node,
                    f"{name}() blocks on the device; {where} — move it "
                    f"off the tick loop or suppress with a reason if the "
                    f"sync is intentional")
            elif name in _ASARRAY and len(node.args) == 1 \
                    and not node.keywords and isinstance(
                        node.args[0], (ast.Name, ast.Attribute)):
                # np.asarray(x) on a bare name is the device-fetch idiom;
                # host-side conversions pass a dtype or build from lists
                yield ctx.finding(
                    self.id, node,
                    f"{name}({ast.unparse(node.args[0])}) materializes a "
                    f"device value on host; {where} — keep it async or "
                    f"suppress with a reason at an intentional sync point")
            elif name == "float" and node.args and isinstance(
                    node.args[0], (ast.Name, ast.Attribute,
                                   ast.Subscript, ast.Call)):
                yield ctx.finding(
                    self.id, node,
                    f"float(...) forces a scalar device read; {where}")
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _SYNC_METHODS and not node.args:
                yield ctx.finding(
                    self.id, node,
                    f".{node.func.attr}() blocks on the device; {where}")
