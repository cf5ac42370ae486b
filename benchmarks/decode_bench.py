"""Decode-path benchmark: paged KV data plane vs the dense-cache engine.

The dense engine provisions every slot's cache at the engine's worst-case
``max_len`` and pays for it on every decode step (attention over the full
padded length + a full-cache copy per step + a full-cache splice per
admission wave).  The paged engine reads/writes only the blocks each slot
actually holds through the pager's block table, donates the arena (in-place
updates), and admits per-slot.  Emitted to ``BENCH_decode.json``
(``make bench-decode`` / ``make bench-decode-fast``):

* per (context, slots) cell: decode tokens/sec for both engines and the
  paged/dense speedup;
* admission cost: total admission (prefill included) ms/request;
* methodology record (model, engine capacity, measurement protocol).

Acceptance (full mode): >= 2x decode tokens/sec at 2048-token contexts.

Methodology: both engines run the same reduced dense-family model with the
same engine capacity ``max_len`` (the worst case they must support) and the
same request set (``slots`` requests of ``ctx`` prompt tokens, greedy
decode for ``max_new`` tokens).  A full warmup drain compiles every shape
first; the measured drain then reads the engine's own step-level counters
(``decode_wall_s``/``decode_tokens``: jit dispatch + device sync + argmax;
``admit_wall_s``: admission, prefill included).  CPU timings.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import numpy as np

ENGINE_MAX_FULL = 4096
ENGINE_MAX_FAST = 1024


def _build_model(seed: int):
    import jax
    from repro.configs import get_config, reduced
    from repro.models.model import build_model

    cfg = reduced(get_config("mistral-nemo-12b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def _requests(n: int, ctx: int, max_new: int, vocab: int, seed: int,
              id0: int = 0):
    from repro.runtime.server import encode_request
    rng = np.random.RandomState(seed + ctx)
    return [encode_request(id0 + i,
                           rng.randint(1, vocab - 1, size=ctx).tolist(),
                           max_new)
            for i in range(n)]


def _measure(server, wires, warm_wires):
    """Warm drain (compiles every shape), then a measured drain read off
    the engine's step-level counters."""
    for w in warm_wires:
        server.submit_wire(w)
    server.run_until_drained()
    base = dict(server.stats)
    t0 = time.perf_counter()
    for w in wires:
        server.submit_wire(w)
    server.run_until_drained()
    wall = time.perf_counter() - t0
    d = {k: server.stats[k] - base[k] for k in
         ("decode_tokens", "decode_wall_s", "decode_steps",
          "admit_wall_s", "admitted", "completed")}
    assert d["completed"] == len(wires), "undrained"
    return {
        "decode_tokens": d["decode_tokens"],
        "decode_steps": d["decode_steps"],
        "decode_tokens_per_s": round(d["decode_tokens"]
                                     / max(d["decode_wall_s"], 1e-9), 1),
        "decode_wall_s": round(d["decode_wall_s"], 4),
        "admit_ms_per_req": round(
            d["admit_wall_s"] / max(d["admitted"], 1) * 1e3, 3),
        "wall_s": round(wall, 4),
    }


def run_cell(model, params, *, ctx: int, slots: int, engine_max: int,
             max_new: int, seed: int):
    from repro.runtime.server import BatchServer

    # bounded prefill group size: grouped-prefill attention scratch is
    # O(group * ctx^2)
    pfb = max(1, min(slots, 8192 // max(ctx, 1)))
    cell = {"ctx": ctx, "slots": slots, "engine_max_len": engine_max,
            "max_new": max_new, "prefill_batch": pfb}
    for name, paged in (("dense", False), ("paged", True)):
        # one-shot prefill on both engines: this bench measures the decode
        # hot path and the admission cost under identical prefill
        # semantics — the chunked pipeline's trace/TTFT wins are measured
        # by serve_bench's ragged phase
        srv = BatchServer(model, batch_slots=slots, max_len=engine_max,
                          params=params, nic_cost=None, paged_kv=paged,
                          prefill_batch=pfb, prefill_chunk=0)
        # one prefill group warms every jit shape the measured drain hits
        # (decode batch is always `slots`-wide; admission groups are pfb)
        warm = _requests(pfb, ctx, max_new, model.cfg.vocab, seed,
                         id0=10_000)
        wires = _requests(slots, ctx, max_new, model.cfg.vocab, seed)
        cell[name] = _measure(srv, wires, warm)
        if paged:
            cell["kv_blocks_allocated"] = srv.kv_stats()["blocks_allocated"]
            assert cell["kv_blocks_allocated"] > 0
    cell["decode_speedup_x"] = round(
        cell["paged"]["decode_tokens_per_s"]
        / max(cell["dense"]["decode_tokens_per_s"], 1e-9), 2)
    return cell


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_decode.json")
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller contexts/engine, no 2x gate")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.fast:
        engine_max, contexts, slot_counts, max_new = \
            ENGINE_MAX_FAST, (128, 512), (8,), 8
        # anchor cell with full-mode identity (ctx, slots, engine_max,
        # max_new) so tools/bench_check.py has a like-for-like decode
        # metric to compare against the committed full-mode baseline
        grid = [(128, 8, ENGINE_MAX_FULL, 16)]
    else:
        engine_max, contexts, slot_counts, max_new = \
            ENGINE_MAX_FULL, (128, 512, 2048), (8, 32), 16
        grid = []
    grid = [(ctx, slots, engine_max, max_new)
            for ctx in contexts for slots in slot_counts] + grid

    cfg, model, params = _build_model(args.seed)
    cells = []
    t0 = time.perf_counter()
    for ctx, slots, emax, mnew in grid:
        t = time.perf_counter()
        cell = run_cell(model, params, ctx=ctx, slots=slots,
                        engine_max=emax, max_new=mnew,
                        seed=args.seed)
        cell["cell_wall_s"] = round(time.perf_counter() - t, 2)
        cells.append(cell)
        print(f"ctx={ctx:5d} slots={slots:3d}: "
              f"dense {cell['dense']['decode_tokens_per_s']:9.1f} tok/s"
              f" | paged {cell['paged']['decode_tokens_per_s']:9.1f}"
              f" tok/s | {cell['decode_speedup_x']:5.2f}x decode")

    top_ctx = max(contexts)
    top = [c for c in cells if c["ctx"] == top_ctx]
    ok = args.fast or all(c["decode_speedup_x"] >= 2.0 for c in top)
    report = {
        "bench": "decode",
        "fast": args.fast,
        "arch": cfg.name,
        "methodology": {
            "model": f"{cfg.name} (reduced: {cfg.n_layers}L d{cfg.d_model} "
                     f"{cfg.n_heads}h/{cfg.n_kv_heads}kv hd{cfg.head_dim})",
            "engine_max_len": engine_max,
            "protocol": "per cell: warm drain compiles all shapes, then a "
                        "measured drain of `slots` requests of `ctx` prompt "
                        "tokens, greedy `max_new`; decode tok/s from the "
                        "engine's step counters (jit dispatch + sync + "
                        "argmax); admission from the admit span; CPU "
                        "timings",
            "baseline": "PR-2 dense engine (paged_kv=False): shared-write-"
                        "index (slots, max_len) cache, admission splice, "
                        "equal-length admission waves",
            "acceptance": ">= 2x decode tokens/sec at the largest context "
                          "(full mode)",
        },
        "cells": cells,
        "wall_s": round(time.perf_counter() - t0, 2),
    }
    Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    print(json.dumps(report["cells"][-1], indent=2))
    print(f"\nDECODE BENCH {'OK' if ok else 'BELOW BAR'}: " +
          ", ".join(f"{c['decode_speedup_x']}x @ ctx={c['ctx']}/"
                    f"slots={c['slots']}" for c in cells))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
