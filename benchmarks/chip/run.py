#!/usr/bin/env python3
"""Chip benchmark of the serving engine: one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1> [--control 1]

From the root of a checkout, on a machine with the TPU chips the cell
asks for (it exits 2, with no result, where JAX finds fewer).  One run:

1. set-up (``setup_s``): weights from the seed in one jitted call, the
   engine as ``launch/serve.py`` builds it, every decode and chunk bucket
   compiled (or loaded from the compilation cache in ``.jax_cache`` in
   the checkout), then the traffic's warm-in: a backlog cell fills every
   slot (with ``staggered``, each at another stage of its answer, as in
   a steady state) and decodes once all are past their prefill; an
   open-loop cell runs its arrivals for ``warm_s`` seconds;
2. the window: ``--seconds`` of the benchmark's own load loop driving
   ``submit_wire`` and ``step``; with ``--trace 1`` under the profiler;
3. an open-loop cell keeps its arrivals going until every request due
   in the window has its first token (at most 60 s);
4. the peak device memory is read, the engine's state freed, and a
   sample of the served requests is compared with the plain reference
   (``chipbench/check.py``); ``--control 1`` also reads the float8
   control in the program's place and holds it to the same limits
   (``control_correct``, which has to come out false; for calibrating
   limits, the benchmark's own runs never do);
5. the last line of standard output is the result: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
   or with ``--trace 1`` its per-layer metrics), ``device``, with
   ``--trace 1`` a ``breakdown``, with ``--control 1`` the control's
   verdict and numbers, and last ``checks``: each number compared with
   its limit, also printed as the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse                                            # noqa: E402
import contextlib                                          # noqa: E402
import gc                                                  # noqa: E402
import json                                                # noqa: E402
import math                                                # noqa: E402
import os                                                  # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402
import tempfile                                            # noqa: E402
from pathlib import Path                                   # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
# the compilation cache lives in the checkout, at a fixed path
CACHE_DIR = ROOT / ".jax_cache"

DRAIN_S = 60.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the float8 control and hold it to "
                         "the limits (limit calibration)")
    return ap.parse_args(argv)


class CompileCounter:
    """Counts XLA compilations (cache hits do not compile)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_):
        if event == COMPILE_EVENT:
            self.n += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def _annotate(enabled: bool):
    if not enabled:
        return lambda _name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def build_engine(cell, seed: int, model_hook=None, compiles=None):
    """Weights from the seed, the engine, every bucket warmed."""
    import jax

    from chipbench import dims, engine
    from repro.models.model import build_model

    m = dims.from_config(cell.config)
    cfg = engine.program_config(cell.config)
    model = build_model(cfg)
    if model_hook is not None:
        model = model_hook(model)
    t = time.time()
    params = engine.make_params(cfg, m, model, seed)
    jax.block_until_ready(params)
    log(f"weights: {time.time() - t:.3f} s")
    srv = engine.build(cfg, params, cell.traffic, model)
    del params
    t = time.time()
    calls = engine.warm(srv)
    log(f"warm-up: {calls} bucket calls, {engine.compiled_programs(srv)} "
        f"programs, {compiles.n if compiles else '?'} XLA compiles, "
        f"{time.time() - t:.3f} s")
    return m, srv


def run_cell(cell, seed: int, seconds: float, trace: bool = False,
             control: bool = False, *, peak=None, model_hook=None,
             t_start: float = T_START):
    """One run of ``cell``; returns the result line as a dict."""
    import jax

    from chipbench import check, engine, loadloop, spec, stats, traffic, window
    from chipbench import trace as trace_mod

    dev = jax.devices()[0]
    peak = peak if peak is not None else spec.peak_for(dev.device_kind)
    compiles = CompileCounter()
    m, srv = build_engine(cell, seed, model_hook, compiles)
    reqs = traffic.generate(cell.traffic, m.vocab, seed, seconds, DRAIN_S)
    wires = engine.wires(reqs)

    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0         # host spans only, no call tree
        jax.profiler.start_trace(tdir, profiler_options=opts)
    clock = time.perf_counter
    tr = cell.traffic
    if cell.kind == "batch":
        drv = loadloop.LoadLoop(srv, reqs, wires, clock(),
                            annotate=_annotate(trace))
        pending = int(tr["pending"])
        slots = srv.slots

        def full():
            act = list(srv.active.values())
            return len(act) == slots and all(
                r.state.value == "DECODE" for r in act)
        drv.run(math.inf, pending=pending, until=full)
        w0 = clock()
    else:
        pending = 0
        w0 = clock() + float(tr["warm_s"])
        drv = loadloop.LoadLoop(srv, reqs, wires, w0,
                                annotate=_annotate(trace))
        drv.run(w0)
    setup_s = time.time() - t_start - (clock() - w0)
    stats0 = dict(srv.stats)
    progs0, compiles0 = engine.compiled_programs(srv), compiles.n
    with _annotate(trace)("window"):
        drv.run(w0 + seconds, pending=pending)
    w1 = clock()
    stats1 = dict(srv.stats)
    progs_in = engine.compiled_programs(srv) - progs0
    compiles_in = compiles.n - compiles0
    compiles.close()
    log(f"window: {w1 - w0:.3f} s, {len(drv.ticks)} ticks in all, "
        f"{stats1['decode_steps'] - stats0['decode_steps']} decode steps, "
        f"{stats1['prefill_chunks'] - stats0['prefill_chunks']} chunk "
        f"steps; programs compiled in the window: {progs_in} traced, "
        f"{compiles_in} XLA compiles")

    due_end = w0 + seconds
    if cell.kind == "online":
        # arrivals go on until every request due in the window has its
        # first token; the trace is read after, so that nothing stalls it
        window_recs = [r for r in drv.recs.values() if w0 <= r.due < due_end]
        drv.run(w1 + DRAIN_S, until=lambda: all(
            r.first_t is not None or r.failed for r in window_recs))
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        t = time.time()
        evs = trace_mod.load(tdir)
        reduced = trace_mod.reduce(evs)
        del evs
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: {reduced.n_ops} device ops in the window, read in "
            f"{time.time() - t:.3f} s")
    mem = getattr(dev, "memory_stats", lambda: None)() or {}
    mem_peak = int(mem.get("peak_bytes_in_use", 0))

    w = window.Window(
        cell=cell, dims=m, peak=peak, setup_s=setup_s, w0=w0, w1=w1,
        due_s=seconds, recs=drv.recs, ticks=drv.ticks, spans=drv.spans,
        stats0=stats0, stats1=stats1, slots=srv.slots,
        chunk_buckets=tuple(srv.chunk_buckets), trace=reduced)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for metric in wanted:
        v = window.reader(metric["name"])(w)
        if v is not None:
            metrics[metric["name"]] = {"value": float(v),
                                       "unit": metric["unit"]}

    if cell.kind == "batch":
        served = {r.req_id for r in drv.recs.values()
                  if any(w0 < s <= w1 for s in r.stamps)}
        fails = [r for r in drv.recs.values() if r.failed]
        attempted = len(served) + len(fails)
    else:
        due = stats.due_in(drv.recs.values(), w0, due_end)
        fails = [r for r in due if r.failed or r.first_t is None]
        attempted = len(due)

    # the served sample, then the engine's state is freed for the reference
    done_only = cell.kind == "online"
    live = drv.live
    cands = {}
    for rid, req in live.items():
        st_name = req.state.value
        if st_name == "FAILED" or not req.generated:
            continue
        if done_only and st_name != "DONE":
            continue
        cands[rid] = (len(req.prompt), len(req.generated))
    ids = check.pick(cands, int(tr["sample_requests"]), seed)
    seqs = [(list(live[i].prompt), list(live[i].generated)) for i in ids]
    short = sum(1 for r in live.values() if r.state.value == "DONE"
                and len(r.generated) != r.max_new)
    engine.release(srv)
    del srv, drv, live
    gc.collect()
    t = time.time()
    ref = check.reference_module(cell.config["reference"])
    read = check.readings(m, ref, seed, seqs, int(tr["max_len"]), control)
    log(f"reference: {len(seqs)} requests, {read['served_tokens']} served "
        f"tokens, reference argmax share {read['reference_argmax_share']!r}"
        f", {time.time() - t:.3f} s")
    log(f"served tokens: widest gap {read['served_logit_gap']!r}, mean gap "
        f"{read['served_logit_gap_mean']!r}")

    # the numbers this cell's limits file compares, then the two that
    # every cell holds at 0
    checks = {k: (read[k], float(lim)) for k, lim in cell.limits.items()}
    checks.update(bad_token_ids=(read["bad_token_ids"], 0),
                  short_answers=(short, 0))
    correct = bool(seqs) and all(v <= lim for v, lim in checks.values())
    if control:
        # the control in the program's place, held to the same limits
        ctl = {k: (read["control_" + k.removeprefix("served_")], lim)
               for k, (_, lim) in checks.items() if k in cell.limits}
        control_correct = all(v <= lim for v, lim in ctl.values())
        for k, (v, lim) in ctl.items():
            log(f"control (float8 e4m3) {k}: {v!r} (limit {lim!r})")
        log(f"control correct: {control_correct}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    if reduced is not None:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    out = {"correct": correct, "attempted": attempted, "failed": len(fails),
           "metrics": metrics, "device": device}
    if reduced is not None:
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced.device_ops],
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps]}
    if control:
        out["control_correct"] = control_correct
        out["control_checks"] = {k: {"value": v, "limit": lim}
                                 for k, (v, lim) in ctl.items()}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import spec
    cell = spec.resolve(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform!r} device(s). "
              f"This benchmark never runs on the CPU.", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = devs[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devs)}; compilation cache {CACHE_DIR}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   bool(args.control))
    print(f"correct: {out['correct']}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
