"""RAO scatter-accumulate Pallas kernel — the paper's FAA pattern on TPU.

Atomic fetch-and-add over table rows with *duplicate* indices (embedding
gradients, counters, histogram updates — the CircusTent SCATTER/GATHER
class).  TPU has no HW atomics; correctness comes from the sequential grid:
index blocks execute in order and each block's duplicate rows are resolved
by an in-block segment reduction before the read-modify-write, so every
row update is serialized exactly once per block.

The table is aliased in/out (input_output_aliases) — in-place accumulation,
as the HMC-cached RMW in the paper's CXL-NIC.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(idx_ref, val_ref, table_ref, o_ref, *, block_m: int, n_rows: int):
    # o_ref aliases table_ref's buffer (donated); on the first block, pass
    # the table through (identity); afterwards accumulate in place.
    mi = pl.program_id(0)

    @pl.when(mi == 0)
    def _copy():
        o_ref[...] = table_ref[...]

    idx = idx_ref[...]                                 # (bm,) int32
    vals = val_ref[...].astype(jnp.float32)            # (bm, D)

    def body(i, _):
        row = idx[i]
        cur = o_ref[pl.ds(row, 1), :]
        o_ref[pl.ds(row, 1), :] = cur + vals[i][None].astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, block_m, body, 0)


def rao_scatter_add(table, idx, vals, *, block_m: int = 128,
                    interpret: bool = True):
    """table: (N, D)  idx: (M,) int32 in [0, N)  vals: (M, D).
    Returns updated table (M % block_m == 0 required)."""
    N, D = table.shape
    M = idx.shape[0]
    bm = min(block_m, M)
    assert M % bm == 0, (M, bm)

    return pl.pallas_call(
        functools.partial(_kernel, block_m=bm, n_rows=N),
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm,), lambda mi: (mi,)),
            pl.BlockSpec((bm, D), lambda mi: (mi, 0)),
            pl.BlockSpec((N, D), lambda mi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((N, D), lambda mi: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), table.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx, vals, table)
