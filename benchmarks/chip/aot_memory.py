#!/usr/bin/env python3
"""Compile each cell's decode and chunk-prefill steps, and the weight
generator, for a described TPU v5e chip with no chip attached, and print
``memory_analysis()`` of each.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/aot_memory.py [cell ...]

The step programs are compiled at the cell's slots, its largest decode
bucket and its 64-token chunk, with the Pallas kernels (the TPU's
compiler refuses what does not fit).  Nothing runs: this says what one
program needs, not what the process holds.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parents[1] / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import dims, engine, spec, weights
    from repro.kernels import dispatch as kd
    from repro.models.model import build_model

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    kd.default_backend = lambda name: "tpu"      # the kernels, not the oracles
    bench = spec.read_json(spec.ROOT / "BENCHMARK.json")
    names = argv or [w["name"] for w in bench["workloads"]]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def put(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    def lower_step(fn, *args):
        # the arena is donated, as the engine's jitted steps donate it
        return jax.jit(fn, donate_argnums=(1,)).lower(*args)

    out = {}
    for name in names:
        cell = spec.resolve(name)
        m = dims.from_config(cell.config)
        cfg = engine.program_config(cell.config)
        model = build_model(cfg)
        B, L = int(cell.traffic["slots"]), int(cell.traffic["max_len"])
        params = put(model.abstract_params())
        pages = put(jax.eval_shape(lambda: model.init_paged_cache(B, L, 16)))
        nb = -(-L // 16)
        i32 = jnp.int32
        progs = {
            "paged_decode": lower_step(
                model.paged_decode_step, params, pages, sds((B, 1), i32),
                sds((B, nb), i32), sds((B,), i32)),
            "chunk_prefill": lower_step(
                model.paged_prefill_chunk, params, pages, sds((B, 64), i32),
                sds((B, nb), i32), sds((B,), i32), sds((B,), i32)),
            "weights": weights.maker(m, engine.arrange_for(cfg)).lower(
                sds((2,), jnp.uint32)),
        }
        res = {}
        for label, lowered in progs.items():
            ma = lowered.compile().memory_analysis()
            res[label] = {k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes")}
            print(name, label, res[label], flush=True)
        out[name] = res
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
