"""Chip smoke: the serving engine's main path on one TPU, at full width.

    python chip_smoke.py

Runs in one process, in this order, and exits non-zero if any phase fails:

1. refuses to run without a TPU (no CPU fallback);
2. prints the device kind, the device count and the compile-cache
   directory;
3. compares the compiled paged decode and chunked-prefill attention kernels
   against their jnp oracles at mistral-nemo-12b widths (32 heads, 8 KV
   heads, head_dim 128, 16-token pages, bf16) on ragged lengths;
4. checks that the kernels, not the oracles, are what the paged decode and
   chunk-prefill steps lower to (``tpu_custom_call``);
5. serves 8 wire-encoded requests through ``repro.launch.serve.main`` with
   the published mistral-nemo-12b widths cut to 12 of its 40 layers
   (random weights from ``--seed``), and checks that all 8 drain with 16
   tokens each and none fails.

The last line of standard output is one JSON object naming the device.
``LIBTPU_INIT_ARGS`` is left as the environment sets it.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro.configs import get_config                        # noqa: E402
from repro.core import rpc as wire                          # noqa: E402
from repro.kernels import dispatch as kd                    # noqa: E402
from repro.kernels import ops as kops                       # noqa: E402
from repro.kernels import ref                               # noqa: E402
from repro.launch import serve                              # noqa: E402
from repro.launch.compile_cache import use_compile_cache    # noqa: E402
from repro.models import transformer as tr                  # noqa: E402
from repro.models.model import build_model                  # noqa: E402

ARCH = "mistral-nemo-12b"
LAYERS = 12
H, K, HD, BT, CHUNK = 32, 8, 128, 16, 64
REQUESTS, SLOTS, PROMPT, MAX_NEW = 8, 4, 200, 16
# bf16 outputs of attention averages (|out| up to ~5): one bf16 rounding
# of either side is up to 2^-8 relative; a kernel that reads the wrong page
# or mask is off by O(1).  Same bound as the interpret-mode bf16 test.
ATOL = RTOL = 2e-2
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def _pool(rng, B, nb, lens, dtype):
    """Random (P, K, bt, hd) arenas and a shuffled block table covering
    ``lens`` tokens per slot (unused entries -1)."""
    P = B * nb + 1
    kp = jnp.asarray(rng.randn(P, K, BT, HD), dtype)
    vp = jnp.asarray(rng.randn(P, K, BT, HD), dtype)
    perm = rng.permutation(P - 1)
    btab = np.full((B, nb), -1, np.int32)
    j = 0
    for b, n in enumerate(lens):
        for i in range(-(-n // BT)):
            btab[b, i] = perm[j]
            j += 1
    return kp, vp, jnp.asarray(btab), jnp.asarray(lens, jnp.int32)


def _compare(label, got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want)
    excess = float(np.max(err - (ATOL + RTOL * np.abs(want))))
    log(f"{label}: max abs err vs oracle {float(err.max())!r} "
        f"(bound {ATOL} + {RTOL} * |oracle|, worst margin {-excess!r})")
    if not np.isfinite(got).all() or excess > 0:
        raise SystemExit(f"{label}: kernel disagrees with its oracle")


def check_kernels():
    """Compiled kernels vs the oracles (run at full f32 matmul precision)
    on ragged lengths: empty slot, page boundary, mid-page, full table."""
    rng = np.random.RandomState(0)
    bf = jnp.bfloat16
    B, nb = 4, 14
    lens = [0, BT, 5 * BT + 7, nb * BT - 1]

    kp, vp, btab, sl = _pool(rng, B, nb, lens, bf)
    q = jnp.asarray(rng.randn(B, H, HD), bf)
    kn = jnp.asarray(rng.randn(B, K, HD), bf)
    vn = jnp.asarray(rng.randn(B, K, HD), bf)
    for window in (0, 40):
        got = kops.paged_attention(q, kp, vp, btab, sl, kn, vn,
                                   window=window, backend="tpu")
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.paged_attention, static_argnames="window")(
                q, kp, vp, btab, sl, kn, vn, window=window)
        _compare(f"paged_attention (decode) window={window}", got, want)

    lens = [0, BT, 3 * BT + 5, nb * BT - CHUNK]
    kp, vp, btab, cl = _pool(rng, B, nb, lens, bf)
    q = jnp.asarray(rng.randn(B, CHUNK, H, HD), bf)
    kn = jnp.asarray(rng.randn(B, CHUNK, K, HD), bf)
    vn = jnp.asarray(rng.randn(B, CHUNK, K, HD), bf)
    for window in (0, 40):
        got = kops.paged_prefill_attention(q, kp, vp, btab, cl, kn, vn,
                                           window=window, backend="tpu")
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.paged_prefill_attention,
                           static_argnames="window")(
                q, kp, vp, btab, cl, kn, vn, window=window)
        _compare(f"paged_prefill_attention (chunk {CHUNK}) "
                 f"window={window}", got, want)


def check_kernels_in_graph(cfg):
    """The serving steps must lower to the Pallas kernels on this chip."""
    for name in ("paged_attention", "paged_prefill_attention"):
        backend = kd.default_backend(name)
        if backend != "tpu":
            raise SystemExit(f"{name} dispatches to {backend!r}, not the "
                             f"compiled TPU kernel")
    model = build_model(cfg)
    params = model.abstract_params()
    max_len = PROMPT + MAX_NEW + 2
    nb = tr.paged_blocks(max_len, BT)
    pages = jax.eval_shape(lambda: model.init_paged_cache(SLOTS, max_len, BT))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)        # noqa: E731
    steps = {
        "paged decode step": jax.jit(model.paged_decode_step).lower(
            params, pages, i32(SLOTS, 1), i32(SLOTS, nb), i32(SLOTS)),
        "chunk prefill step": jax.jit(model.paged_prefill_chunk).lower(
            params, pages, i32(SLOTS, CHUNK), i32(SLOTS, nb), i32(SLOTS),
            i32(SLOTS)),
    }
    for label, lowered in steps.items():
        n = lowered.as_text().count("tpu_custom_call")
        log(f"{label}: {n} tpu_custom_call op(s) in the lowered graph")
        if n == 0:
            raise SystemExit(f"the {label} does not contain the Pallas "
                             f"kernel (tpu_custom_call)")


def _union_seconds(spans):
    """Length of the union of (start, end) intervals: nested jits record
    spans inside their callers' spans."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def run_serve():
    spans = []

    def on_span(event, start, end, **_):
        if event in COMPILE_EVENTS:
            spans.append((start, end))

    argv = ["--arch", ARCH, "--full-config", "--layers", str(LAYERS),
            "--requests", str(REQUESTS), "--slots", str(SLOTS),
            "--prompt-len", str(PROMPT), "--max-new", str(MAX_NEW),
            "--seed", "0"]
    log(f"serve: repro.launch.serve.main {' '.join(argv)}")
    jax.monitoring.register_event_time_span_listener(on_span)
    t0 = time.perf_counter()
    try:
        responses = serve.main(argv)    # exits non-zero on a failed request
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
    wall = time.perf_counter() - t0
    vocab = get_config(ARCH).vocab
    got = {}
    for buf in responses:
        msg = wire.decode(buf, serve.RESP)
        got[msg[1]] = np.frombuffer(msg[2], np.int32)
    drained = sum(len(t) == MAX_NEW and bool(((t >= 0) & (t < vocab)).all())
                  for t in got.values())
    log(f"serve: {drained}/{REQUESTS} requests drained with {MAX_NEW} "
        f"in-vocab tokens each")
    if drained != REQUESTS or sorted(got) != list(range(REQUESTS)):
        raise SystemExit(f"serve drained {drained}/{REQUESTS} requests")
    compile_total = _union_seconds(spans)
    log(f"serve wall seconds: {wall!r}")
    log(f"compile seconds: {compile_total!r} (tracing, lowering and XLA "
        f"compilation inside the serve call)")
    log(f"steady-state seconds: {wall - compile_total!r} (serve wall time "
        f"outside compilation)")


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this script never falls back to the "
              f"CPU", file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    log(f"device kind: {dev.device_kind}")
    log(f"device count: {len(jax.devices())}")
    log(f"compile cache: {cache_dir}")
    cfg = get_config(ARCH)
    log(f"model: {ARCH} at published widths (d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads, {cfg.n_kv_heads} kv heads, head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}), depth cut "
        f"{cfg.n_layers} -> {LAYERS} layers")
    check_kernels()
    check_kernels_in_graph(cfg.replace(n_layers=LAYERS))
    run_serve()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
