"""Paged decode-attention Pallas kernel (TPU): one query token per slot
attending over a block-table-indexed KV pool.

Grid (slot, kv_head, kv_block); the kv-block dimension is minor-most so the
TPU executes it sequentially and the online-softmax running statistics
(m, l, acc) live in VMEM scratch across blocks.  The block table and the
per-slot sequence lengths ride in scalar-prefetch slots
(``PrefetchScalarGridSpec``) so each step's BlockSpec index_map can pull the
right page of the pooled arena into VMEM — fine-grained coherent page reads
instead of a dense (slots, max_len) gather, the paper's block-granular
shared-pool access pattern.  Fully-dead blocks (past a slot's length, or
wholly outside its sliding window) are skipped via ``pl.when``.  The
current token's (k_new, v_new) — not yet written to the pool — is folded
into the softmax at the final block, so the pool write can happen after
attention as one fused scatter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(btab_ref, lens_ref, q_ref, kn_ref, vn_ref, kp_ref, vp_ref,
            o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, window: int, block_tokens: int):
    s = pl.program_id(0)
    bi = pl.program_id(2)
    nb = pl.num_programs(2)
    L = lens_ref[s]                                  # tokens in the pool

    @pl.when(bi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    first = bi * block_tokens
    live = first < L                                 # any valid position?
    if window:                                       # block inside window?
        live = jnp.logical_and(live, first + block_tokens > L - window)

    @pl.when(live)
    def _block():
        qb = q_ref[0, 0].astype(jnp.float32)         # (G, hd)
        kb = kp_ref[0, 0].astype(jnp.float32)        # (bt, hd)
        sc = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, bt)
        pos = first + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        mask = pos < L
        if window:
            mask = jnp.logical_and(mask, pos > L - window)
        sc = jnp.where(mask, sc, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        vb = vp_ref[0, 0].astype(jnp.float32)        # (bt, hd)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + \
            jax.lax.dot_general(p, vb, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(bi == nb - 1)
    def _finalize():
        # fold in the current token (its kv is pool-written after the call)
        qb = q_ref[0, 0].astype(jnp.float32)         # (G, hd)
        kn = kn_ref[0, 0].astype(jnp.float32)        # (1, hd)
        sn = jnp.sum(qb * kn, axis=-1) * scale       # (G,)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, sn)
        alpha = jnp.exp(m_prev - m_new)
        pn = jnp.exp(sn - m_new)
        l_fin = l_ref[...] * alpha + pn              # >= pn > 0: no 0-div
        vn = vn_ref[0, 0].astype(jnp.float32)        # (1, hd)
        acc = acc_ref[...] * alpha[:, None] + pn[:, None] * vn
        o_ref[0, 0] = (acc / l_fin[:, None]).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    k_new, v_new, *, window: int = 0,
                    interpret: bool = True):
    """Contract of ``kernels.ref.paged_attention`` (the test oracle).

    q: (B, H, hd); k_pages/v_pages: (P, K, bt, hd); block_tables: (B, nb)
    int32 (< 0 = unallocated); seq_lens: (B,) int32 tokens resident;
    k_new/v_new: (B, K, hd) current token.  Returns (B, H, hd).
    """
    B, H, hd = q.shape
    P, K, bt, _ = k_pages.shape
    nb = block_tables.shape[1]
    G = H // K
    scale = 1.0 / np.sqrt(hd)

    q4 = q.reshape(B, K, G, hd)
    kn = k_new.reshape(B, K, 1, hd)
    vn = v_new.reshape(B, K, 1, hd)
    btab = jnp.maximum(block_tables.astype(jnp.int32), 0)
    lens = seq_lens.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, nb),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda s, k, b, bt_, ln: (s, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, hd), lambda s, k, b, bt_, ln: (s, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, hd), lambda s, k, b, bt_, ln: (s, k, 0, 0)),
            pl.BlockSpec((1, 1, bt, hd),
                         lambda s, k, b, bt_, ln: (bt_[s, b], k, 0, 0)),
            pl.BlockSpec((1, 1, bt, hd),
                         lambda s, k, b, bt_, ln: (bt_[s, b], k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda s, k, b, bt_, ln: (s, k, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window,
                          block_tokens=bt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(btab, lens, q4, kn, vn, k_pages, v_pages)
    return out.reshape(B, H, hd)
