"""Host spans of the serving tick (``runtime.spans``), the programs'
stable names and the expert FFN's name scope, on tiny engines on the CPU."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models.model import build_model
from repro.runtime import spans
from repro.runtime.server import (
    AsyncBatchServer, BatchServer, DisaggEngine, Request,
)

F32 = dict(param_dtype="float32", cache_dtype="float32")
# ragged prompts: single- and multi-chunk, completing on different ticks
TRACE = [(5, 4), (9, 3), (20, 4), (3, 2), (40, 3)]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _model(cfg_name, **over):
    cfg = reduced(get_config(cfg_name)).replace(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=64, vocab=128, **F32, **over)
    return build_model(cfg)


def _requests():
    rng = np.random.RandomState(7)
    return [Request(i, rng.randint(1, 127, size=n).tolist(), m)
            for i, (n, m) in enumerate(TRACE)]


def _run_async(srv):
    async def go():
        eng = asyncio.ensure_future(srv.run_engine())
        await asyncio.gather(*[srv.submit_async(r) for r in _requests()])
        srv.close()
        await eng
    asyncio.run(go())


def _serve(kind):
    """(engine, the program names it compiled) after serving TRACE."""
    names = []

    def on(event, _secs, **kw):
        if event == COMPILE_EVENT:
            names.append(kw.get("fun_name"))
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        model = _model("qwen3-moe-235b-a22b", moe_routing="dropless") \
            if kind == "moe" else _model("mistral-nemo-12b")
        params = model.init(jax.random.PRNGKey(3))
        kw = dict(max_len=48, params=params, prefill_chunk=16)
        if kind == "async":
            srv = AsyncBatchServer(model, batch_slots=3, **kw)
            _run_async(srv)
        else:
            if kind == "disagg":
                srv = DisaggEngine(model, batch_slots=2, prefill_slots=2,
                                   **kw)
            else:
                srv = BatchServer(model, batch_slots=3, **kw)
            for r in _requests():
                srv.submit(r)
            srv.run_until_drained()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert srv.stats["completed"] == len(TRACE)
    return srv, names


@pytest.fixture(scope="module")
def served():
    cache = {}

    def get(kind):
        if kind not in cache:
            cache[kind] = _serve(kind)
        return cache[kind]
    return get


KINDS = ["dense", "moe", "async", "disagg"]


@pytest.mark.parametrize("kind", KINDS)
def test_every_span_key_is_present_and_nonnegative(served, kind):
    st = served(kind)[0].stats
    # each span ran: the chunk wait and select run on the ticks where a
    # prompt completes, which every prompt here does
    for name in spans.SPANS:
        assert st[spans.span_key(name)] > 0.0, name


@pytest.mark.parametrize("kind", KINDS)
def test_decode_and_chunk_are_the_sums_of_their_stages(served, kind):
    st = served(kind)[0].stats
    for parent in ("decode", "chunk"):
        stages = sum(st[spans.span_key(f"{parent}.{s}")]
                     for s in ("prep", "dispatch", "wait", "select"))
        assert st[spans.span_key(parent)] == pytest.approx(stages, abs=1e-6)


@pytest.mark.parametrize("kind", KINDS)
def test_tick_covers_its_direct_children(served, kind):
    st = served(kind)[0].stats
    children = sum(st[spans.span_key(n)]
                   for n in ("admit", "chunk", "decode", "harvest"))
    assert st["tick_wall_s"] >= children > 0


def test_span_names_keys_and_stages():
    assert spans.span_key("decode.select") == "decode_select_wall_s"
    stats = spans.zeroed()
    assert set(stats) == {spans.span_key(n) for n in spans.SPANS}
    with spans.Span(stats, "decode") as sp:
        sp.stage("decode.prep")
        sp.stage("decode.wait")
    assert stats["decode_dispatch_wall_s"] == 0.0
    assert stats["decode_wall_s"] == pytest.approx(
        stats["decode_prep_wall_s"] + stats["decode_wait_wall_s"], abs=1e-12)
    with pytest.raises(ValueError), spans.Span(stats, "tick"):
        raise ValueError("the span ends and lets the error through")
    assert stats["tick_wall_s"] > 0.0


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_programs_compile_under_their_registry_names(served, kind):
    srv, names = served(kind)
    ran = [n for n, c in srv.trace_counts().items() if c]
    assert {"paged_decode", "chunk_prefill"} <= set(ran)
    for name in ran:
        assert f"jit({name})" in names, (name, names)


def test_moe_layer_ops_carry_the_moe_ffn_scope(served):
    srv = served("moe")[0]
    B, C = srv.slots, max(srv.chunk_buckets)
    zeros = jnp.zeros((B,), jnp.int32)
    lowered = srv.jit_fns()["chunk_prefill"].lower(
        srv.params, srv.pages, jnp.zeros((B, C), jnp.int32),
        jnp.asarray(np.asarray(srv.pager.block_table())), zeros, zeros + 1)
    assert lowered.as_text().startswith("module @jit_chunk_prefill")
    # the expert matmuls are in the scope, under the program's name
    hlo = lowered.compile().as_text()
    dots = [ln for ln in hlo.splitlines()
            if "/moe_ffn/" in ln and " dot(" in ln]
    assert dots and all('op_name="jit(chunk_prefill)/' in ln for ln in dots)
