"""The reduction from a trace to busy time, kernel time, the chunk-prefill
programs' time and labelled idle gaps: on a hand-made trace whose numbers
are counted by hand, and on a slice of a trace recorded on a TPU v5e."""
import json

import pytest

from chipbench import trace
from chipbench.spec import BENCH_DIR

HOST, DEV, DEV1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE


def ev(plane, line, name, a, b, **stats):
    return trace.Ev(plane, line, name, float(a), float(b - a),
                    tuple(stats.items()))


HAND = [
    ev(HOST, "python", "window", 1000, 11000),
    ev(HOST, "python", "submit", 900, 1000),
    ev(HOST, "python", "step", 1000, 5000),
    ev(HOST, "python", "wait_arrival", 5000, 6500),
    ev(HOST, "python", "step", 6500, 11000),
    ev(DEV, OPS, "%while.1 = (s32[], bf16[32,1,5120]) while(%t)", 1450, 3050),
    ev(DEV, OPS, "%fusion.1 = bf16[32,5120]{1,0} fusion(%a)", 1500, 2000),
    ev(DEV, OPS, "%paged_attention.4 = bf16[32,8,4,128]{3,2,1,0} "
       "custom-call(%b)", 2000, 3000),
    ev(DEV, OPS, "%paged_prefill_attention.2 = bf16[32,8,64,4,128]{4,3,2,1,0}"
       " custom-call(%c)", 4000, 4500),
    ev(DEV, OPS, "%fusion.2 = f32[32,131072]{1,0} fusion(%paged_attention.4)",
       7000, 8000),
    ev(DEV, OPS, "%fusion.3 = bf16[8]{0} fusion(%d)", 10500, 12000),
    ev(DEV, OPS, "%fusion.0 = bf16[8]{0} fusion(%e)", 0, 900),  # before
    ev(DEV, MODS, "jit__lambda(1)", 1400, 3100),
    ev(DEV, MODS, "jit__lambda(2)", 3900, 4600),
]


def test_hand_counted_trace():
    r = trace.reduce(HAND)
    assert r.window_s == pytest.approx(10000e-9)
    # [1450, 3050] + [4000, 4500] + [7000, 8000] + [10500, 11000]
    assert r.busy_s == pytest.approx(3600e-9)
    assert r.idle_share == pytest.approx(0.64)
    # fusion.2 names the kernel as its operand: it is not the kernel
    assert r.kernel_s == pytest.approx({"paged_attention": 1000e-9,
                                        "paged_prefill_attention": 500e-9})
    # only the second program runs the prefill kernel
    assert r.prefill_program_s == pytest.approx(700e-9)
    assert r.n_ops == 6
    # self time: the while loop keeps only what its body does not cover
    assert dict(r.device_ops) == pytest.approx({
        "decode/paged_attention.4 bf16[32,8,4,128]": 1000e-9,
        "other/fusion.2 f32[32,131072]": 1000e-9,
        "decode/fusion.1 bf16[32,5120]": 500e-9,
        "chunk_prefill/paged_prefill_attention.2 bf16[32,8,64,4,128]":
            500e-9,
        "other/fusion.3 bf16[8]": 500e-9,
        "decode/while.1 tuple": 100e-9})
    # gaps: [1000,1450] [3050,4000] [4500,7000] [8000,10500]; the third
    # overlaps the wait for an arrival most (1500 of its 2500 ns)
    assert r.idle_gaps == [("wait_arrival", pytest.approx(2500e-9)),
                           ("step", pytest.approx(2500e-9)),
                           ("step", pytest.approx(950e-9)),
                           ("step", pytest.approx(450e-9))]


def test_busy_is_averaged_over_chips():
    evs = HAND + [ev(DEV1, OPS, "fusion.9", 0, 20000)]
    r = trace.reduce(evs)
    assert r.busy_s == pytest.approx((3600e-9 + 10000e-9) / 2)


def test_kernel_names():
    dec = ev(DEV, OPS, "%paged_attention.3 = bf16[4] custom-call(%x)", 0, 1)
    pre = ev(DEV, OPS, "%paged_prefill_attention = bf16[4] custom-call()",
             0, 1)
    other = ev(DEV, OPS, "%fusion.7 = bf16[4] fusion(%paged_attention.3)",
               0, 1)
    assert trace.kernel_of(dec) == "paged_attention"
    assert trace.kernel_of(pre) == "paged_prefill_attention"
    assert trace.kernel_of(other) is None


def test_no_window_or_no_device_op_is_an_error():
    with pytest.raises(RuntimeError):
        trace.reduce([e for e in HAND if e.name != "window"])
    with pytest.raises(RuntimeError):
        trace.reduce([e for e in HAND if e.plane == HOST])


def _recorded():
    path = BENCH_DIR / "fixtures" / "trace_nemo12_decode.json"
    return [trace.Ev(p, ln, n, a, d, tuple(tuple(s) for s in st))
            for p, ln, n, a, d, st in json.loads(path.read_text())]


def test_recorded_trace_slice():
    evs = _recorded()
    r = trace.reduce(evs)
    w0, w1 = trace.window_of(evs)
    ops = [e for e in evs if e.plane == DEV and e.line == OPS]
    # busy by a second method: sweep the sorted op edges
    edges = sorted([(max(e.start_ns, w0), 1) for e in ops] +
                   [(min(e.end_ns, w1), -1) for e in ops])
    busy, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert r.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < r.busy_s <= r.window_s
    # the decode kernel runs once per layer per decode step
    assert r.kernel_s["paged_attention"] > 0
    decode = [e for e in ops if trace.kernel_of(e) == "paged_attention"]
    assert len(decode) % 12 == 0
    assert r.kernel_s["paged_attention"] == pytest.approx(
        sum(min(e.end_ns, w1) - max(e.start_ns, w0) for e in decode) * 1e-9)
    assert sum(s for _, s in r.idle_gaps) <= r.window_s - r.busy_s + 1e-12
