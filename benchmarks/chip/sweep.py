#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate the engine
sustains without a growing queue.  One process, one engine, one warm-up;
each rate runs the cell's traffic (its lengths, its strata) at that rate
for ``--seconds`` from an empty engine, then the engine drains.

    python3 benchmarks/chip/sweep.py --workload <online cell> --seed <n> \\
        --rates 0.5,1,2 --seconds 40

Prints one JSON line per rate: requests offered and finished, the queue
(waiting plus still prefilling) at the middle and at the end of the
window, its growth per second over the second half, and the tails of time
to first token and of the gaps between tokens.  The benchmark's own runs
never sweep: a cell's rate is fixed in its traffic file.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]


def backlog_of(srv) -> int:
    """Requests waiting for a slot or still prefilling."""
    return len(srv.queue) + sum(1 for r in srv.active.values()
                                if r.state.value in ("PREFILL", "PREFILLING"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)

    from chipbench import spec
    cell = spec.resolve(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import run
    from chipbench import engine, loadloop, stats, traffic

    m, srv = run.build_engine(cell, args.seed)
    clock = time.perf_counter
    rates = [float(r) for r in args.rates.split(",")]
    for k, rate in enumerate(rates):
        tr = dict(cell.traffic, rate_rps=rate, warm_s=0.0)
        reqs = traffic.generate(tr, m.vocab, args.seed + k, args.seconds,
                                0.0)
        w0 = clock()
        drv = loadloop.LoadLoop(srv, reqs, engine.wires(reqs), w0)
        samples = []
        orig_step = drv.step

        def step():
            orig_step()
            samples.append((clock() - w0, backlog_of(srv)))
        drv.step = step
        drv.run(w0 + args.seconds)
        w1 = clock()
        half = [(t, b) for t, b in samples if t >= args.seconds / 2]
        slope = 0.0
        if len(half) > 2:
            ts = [t for t, _ in half]
            bs = [b for _, b in half]
            mt, mb = sum(ts) / len(ts), sum(bs) / len(bs)
            den = sum((t - mt) ** 2 for t in ts)
            slope = sum((t - mt) * (b - mb) for t, b in zip(ts, bs)) / den
        recs = list(drv.recs.values())
        ttft = [x for x in stats.ttfts(recs, w0, w0 + args.seconds)
                if x < math.inf]
        mid = w0 + args.seconds / 2
        done_late = sum(1 for r in recs if len(r.stamps) == r.max_new
                        and mid <= r.stamps[-1] <= w1)
        gaps = stats.gaps(recs, w0, w1)
        out = {
            "rate_rps": rate,
            "offered": len(recs),
            "first_token": len(ttft),
            "finished": sum(1 for r in recs if len(r.stamps) == r.max_new),
            "backlog_mid": min((b for t, b in samples
                                if t >= args.seconds / 2), default=None),
            "backlog_end": samples[-1][1] if samples else None,
            "backlog_slope_per_s": slope,
            "ttft_p50_ms": 1e3 * stats.pct(ttft, 50) if ttft else None,
            "ttft_p90_ms": 1e3 * stats.pct(ttft, 90) if ttft else None,
            "itl_p50_ms": 1e3 * stats.pct(gaps, 50) if gaps else None,
            "itl_p95_ms": 1e3 * stats.pct(gaps, 95) if gaps else None,
            "out_tok_per_s": stats.tokens_in(recs, w0, w1) / (w1 - w0),
            "completed_per_s_second_half": done_late / (w1 - mid),
            "ticks": len(drv.ticks),
        }
        print(json.dumps(out), flush=True)
        if k + 1 < len(rates):
            t = clock()
            while drv.busy() and clock() - t < 90:
                srv.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
