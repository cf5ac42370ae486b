"""The readers of the program's own host spans (``runtime.spans``), on a
synthetic window whose numbers are reckoned by hand, and the spans on the
profiler's host timeline, read back as the harness reads a trace."""
import jax
import numpy as np
import pytest

from chipbench import trace, window

# the window's deltas: 4 ticks, 4 decode steps; tick 0.8 s, of it waits
# on the device 0.6 s (decode) and 0.04 s (chunk); selection 0.04 s; the
# NIC cost model 0.3 ms
STATS0 = {"ticks": 10, "decode_steps": 8, "decode_wall_s": 2.0,
          "tick_wall_s": 1.0, "decode_wait_wall_s": 0.5,
          "chunk_wait_wall_s": 0.1, "decode_select_wall_s": 0.02,
          "niccost_wall_s": 0.001}
STATS1 = {"ticks": 14, "decode_steps": 12, "decode_wall_s": 2.7,
          "tick_wall_s": 1.8, "decode_wait_wall_s": 1.1,
          "chunk_wait_wall_s": 0.14, "decode_select_wall_s": 0.06,
          "niccost_wall_s": 0.0013}
# a program without the spans has only the counters and the older timers
BARE = ("ticks", "decode_steps", "decode_wall_s")


def _window(stats0, stats1):
    return window.Window(
        cell=None, dims=None, peak=None, setup_s=0.0, w0=0.0, w1=1.0,
        due_s=1.0, recs={}, ticks=[], spans=[], stats0=stats0,
        stats1=stats1, slots=4, chunk_buckets=(64,))


@pytest.mark.parametrize("name, value", [
    ("token_select_ms.batch", 1e3 * 0.04 / 4),
    ("host_ms_per_tick.batch", 1e3 * (0.8 - 0.6 - 0.04) / 4),
    ("host_ms_per_tick.online", 1e3 * (0.8 - 0.6 - 0.04) / 4),
    ("niccost_us_per_tick.online", 1e6 * 0.0003 / 4),
])
def test_span_readers(name, value):
    read = window.reader(name)
    assert read(_window(STATS0, STATS1)) == pytest.approx(value)
    # a program without the span, and a window without a tick, give none
    bare = [{k: s[k] for k in BARE} for s in (STATS0, STATS1)]
    assert read(_window(*bare)) is None
    assert read(_window(STATS0, STATS0)) is None


def test_program_spans_are_on_the_profiler_host_timeline(tmp_path):
    from repro.configs import get_config, reduced
    from repro.models.model import build_model
    from repro.runtime.server import BatchServer, Request

    cfg = reduced(get_config("mistral-nemo-12b")).replace(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=64, vocab=128)
    model = build_model(cfg)
    srv = BatchServer(model, batch_slots=2, max_len=32, prefill_chunk=16,
                      params=model.init(jax.random.PRNGKey(0)))
    rng = np.random.RandomState(0)
    for i in range(2):
        srv.submit(Request(i, rng.randint(1, 127, size=8).tolist(), 8))
    srv.step()                        # compiles the chunk and decode steps
    srv.step()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            srv.step()
    host = [e for e in trace.load(str(tmp_path))
            if not trace.DEVICE_PLANE.match(e.plane)]
    ticks = [e for e in host if e.name == "tick"]
    selects = [e for e in host if e.name == "decode.select"]
    assert len(ticks) == 3 and len(selects) == 3
    # each selection lies inside a tick
    for s in selects:
        assert any(t.start_ns <= s.start_ns and s.end_ns <= t.end_ns
                   for t in ticks)
