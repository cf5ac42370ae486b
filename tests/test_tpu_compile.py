"""The serving hot path compiles for a TPU v5e chip at mistral-nemo-12b
widths (and the decode kernel at the benchmark cells' shapes), with no
chip attached.

The TPU compiler is installed with jax and compiles for a described
``v5e:2x2`` topology.  It refuses what interpret mode accepts: block shapes
whose two minor dimensions are not tile-aligned, too much VMEM, programs
larger than the device.  Nothing runs here, so these tests say nothing
about results or times.

Only one process at a time may load the TPU library, and it keeps it until
it exits, so the topology is described inside a fixture (never at import)
and every such compile lives in this one file.
"""
import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_prefill_attention import paged_prefill_attention
from repro.models import transformer as tr

# mistral-nemo-12b attention widths, the serving engine's 16-token pages
# and 64-token prefill chunk, 4 slots of 14 pages (218-token contexts)
H, K, HD, BT, CHUNK = 32, 8, 128, 16, 64
B, NB = 4, 14
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A deviceless compile can be written to the persistent cache but not
    read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _sds(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _arena(sharding, *lead):
    return _sds(sharding, (*lead, K, BT, HD))


@pytest.mark.parametrize("window", [0, 40])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                              window):
    P = B * NB + 1
    args = (_sds(one_chip, (B, H, HD)), _arena(one_chip, P),
            _arena(one_chip, P), _sds(one_chip, (B, NB), jnp.int32),
            _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (B, K, HD)),
            _sds(one_chip, (B, K, HD)))
    f = functools.partial(paged_attention, window=window, interpret=False)
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _trace_kernels():
    """The benchmark's kernel-name patterns (``chipbench/trace.py``)."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "chip", "chipbench", "trace.py")
    spec = importlib.util.spec_from_file_location("_chipbench_trace", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod             # its dataclasses look it up
    spec.loader.exec_module(mod)
    return dict(mod.KERNELS)


# the decode kernel at the benchmark cells' widest shapes: mistral-nemo-12b
# (32 slots, 144-page tables) and granite-moe-3b-a800m (16 slots, 136 pages,
# 24/8 heads of 64)
@pytest.mark.parametrize("window", [0, 40])
@pytest.mark.parametrize("b,nb,h,hd,pages", [(32, 144, 32, 128, 4609),
                                             (16, 136, 24, 64, 2177)],
                         ids=["nemo12", "granite"])
def test_paged_decode_kernel_compiles_at_cell_shapes(
        one_chip, no_persistent_cache, b, nb, h, hd, pages, window):
    arena = _sds(one_chip, (pages, K, BT, hd))
    args = (_sds(one_chip, (b, h, hd)), arena, arena,
            _sds(one_chip, (b, nb), jnp.int32),
            _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (b, K, hd)),
            _sds(one_chip, (b, K, hd)))
    f = functools.partial(paged_attention, window=window, interpret=False)
    text = jax.jit(f).lower(*args).compile().as_text()
    # the trace finds the kernel by the HLO name of its custom call
    names = [line.split(" = ")[0].split()[-1] for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    kernels = _trace_kernels()
    assert any(kernels["paged_attention"].search(n) for n in names), names
    assert not any(kernels["paged_prefill_attention"].search(n)
                   for n in names), names


@pytest.mark.parametrize("window", [0, 40])
def test_paged_prefill_kernel_compiles_for_v5e(one_chip,
                                               no_persistent_cache, window):
    P = B * NB + 1
    args = (_sds(one_chip, (B, CHUNK, H, HD)), _arena(one_chip, P),
            _arena(one_chip, P), _sds(one_chip, (B, NB), jnp.int32),
            _sds(one_chip, (B,), jnp.int32),
            _sds(one_chip, (B, CHUNK, K, HD)),
            _sds(one_chip, (B, CHUNK, K, HD)))
    f = functools.partial(paged_prefill_attention, window=window,
                          interpret=False)
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kv_migrate_compiles_for_v5e(one_chip, no_persistent_cache):
    """One fused near<->far event over 12-layer arenas: both arenas are
    donated, so the compiled program aliases them instead of copying."""
    L, P_near, P_far, D = 12, 29, 29, 8
    near = {"kp": _arena(one_chip, L, P_near),
            "vp": _arena(one_chip, L, P_near)}
    far = {"kp": _arena(one_chip, L, P_far),
           "vp": _arena(one_chip, L, P_far)}
    idx = _sds(one_chip, (D,), jnp.int32)
    compiled = jax.jit(tr.lm_kv_migrate, donate_argnums=(0, 1)).lower(
        near, far, idx, idx, idx, idx).compile()
    out_near, out_far = compiled.out_info
    assert out_near["kp"].shape == (L, P_near, K, BT, HD)
    assert out_far["vp"].shape == (L, P_far, K, BT, HD)
    arena_bytes = 2 * (P_near + P_far) * L * K * BT * HD * 2
    assert compiled.memory_analysis().alias_size_in_bytes == arena_bytes
