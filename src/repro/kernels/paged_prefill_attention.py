"""Chunked-prefill attention Pallas kernel (TPU): one C-token prompt chunk
per slot attending over a *partial* block-table-indexed KV pool plus the
chunk's own causal keys.

Multi-query sibling of ``kernels.paged_attention``: grid (slot, kv_head,
kv_block) with the kv-block dimension minor-most so the online-softmax
running statistics (m, l, acc — one row per (chunk position, query group))
live in VMEM scratch across blocks.  The raw block table and per-slot
context lengths ride in scalar-prefetch slots; the BlockSpec index_map
clamps released/unallocated entries (< 0) to page 0 and the kernel body
masks them dead — so partially-released sliding-window rows read garbage
pages but never attend over them.  The chunk's own (k_new, v_new) — not yet
written to the pool — is folded in at the final block with an in-chunk
causal (and window) mask, so the page scatter can happen after attention.
Chunk rows past a slot's valid length attend at least to themselves
(finite output); the caller routes their KV writes to the trash page.
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
            scale: float, window: int, block_tokens: int,
            chunk: int, group: int):
    s = pl.program_id(0)
    bi = pl.program_id(2)
    nb = pl.num_programs(2)
    L0 = lens_ref[s]                         # tokens already in the pool
    C, G = chunk, group
    R = C * G                                # softmax rows: (chunk pos, group)

    @pl.when(bi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    first = bi * block_tokens
    live = jnp.logical_and(first < L0, btab_ref[s, bi] >= 0)
    if window:
        # the earliest chunk query (absolute position L0) has the leftmost
        # window floor; later queries only mask harder (per-position below)
        live = jnp.logical_and(live, first + block_tokens > L0 - window)

    @pl.when(live)
    def _block():
        qb = q_ref[0, 0].astype(jnp.float32).reshape(R, -1)   # (R, hd)
        kb = kp_ref[0, 0].astype(jnp.float32)                 # (bt, hd)
        sc = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (R, bt)
        pos = first + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        mask = pos < L0
        if window:
            cq = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) // G
            mask = jnp.logical_and(mask, pos > L0 + cq - window)
        sc = jnp.where(mask, sc, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        vb = vp_ref[0, 0].astype(jnp.float32)                 # (bt, hd)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + \
            jax.lax.dot_general(p, vb, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(bi == nb - 1)
    def _finalize():
        # fold in the chunk's own keys with the in-chunk causal mask; the
        # diagonal (k == q) is always live, so l_fin > 0 for every row
        qb = q_ref[0, 0].astype(jnp.float32).reshape(R, -1)   # (R, hd)
        knb = kn_ref[0, 0].astype(jnp.float32)                # (C, hd)
        sn = jax.lax.dot_general(
            qb, knb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (R, C)
        cq = jax.lax.broadcasted_iota(jnp.int32, sn.shape, 0) // G
        cu = jax.lax.broadcasted_iota(jnp.int32, sn.shape, 1)
        mask = cu <= cq
        if window:
            mask = jnp.logical_and(mask, cu > cq - window)
        sn = jnp.where(mask, sn, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sn, axis=-1))
        pn = jnp.exp(sn - m_new[:, None])
        pn = jnp.where(mask, pn, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_fin = l_ref[...] * alpha + jnp.sum(pn, axis=-1)
        vnb = vn_ref[0, 0].astype(jnp.float32)                # (C, hd)
        acc = acc_ref[...] * alpha[:, None] + \
            jax.lax.dot_general(pn, vnb, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        out = acc / l_fin[:, None]
        o_ref[0, 0] = out.reshape(C, G, -1).astype(o_ref.dtype)


def paged_prefill_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                            k_new, v_new, *, window: int = 0,
                            interpret: bool = True):
    """Contract of ``kernels.ref.paged_prefill_attention`` (the oracle).

    q: (B, C, H, hd); k_pages/v_pages: (P, K, bt, hd); block_tables:
    (B, nb) int32 (< 0 = unallocated/released); ctx_lens: (B,) int32 tokens
    resident; k_new/v_new: (B, C, K, hd) the chunk's keys/values.
    Returns (B, C, H, hd).
    """
    B, C, H, hd = q.shape
    P, K, bt, _ = k_pages.shape
    nb = block_tables.shape[1]
    G = H // K
    scale = 1.0 / np.sqrt(hd)

    q5 = q.reshape(B, C, K, G, hd).transpose(0, 2, 1, 3, 4)  # (B,K,C,G,hd)
    knr = k_new.transpose(0, 2, 1, 3)                        # (B,K,C,hd)
    vnr = v_new.transpose(0, 2, 1, 3)
    btab = block_tables.astype(jnp.int32)                    # raw: kernel
    lens = ctx_lens.astype(jnp.int32)                        # masks < 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, nb),
        in_specs=[
            pl.BlockSpec((1, 1, C, G, hd),
                         lambda s, k, b, bt_, ln: (s, k, 0, 0, 0)),
            pl.BlockSpec((1, 1, C, hd),
                         lambda s, k, b, bt_, ln: (s, k, 0, 0)),
            pl.BlockSpec((1, 1, C, hd),
                         lambda s, k, b, bt_, ln: (s, k, 0, 0)),
            pl.BlockSpec((1, 1, bt, hd),
                         lambda s, k, b, bt_, ln:
                         (jnp.maximum(bt_[s, b], 0), k, 0, 0)),
            pl.BlockSpec((1, 1, bt, hd),
                         lambda s, k, b, bt_, ln:
                         (jnp.maximum(bt_[s, b], 0), k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C, G, hd),
                               lambda s, k, b, bt_, ln: (s, k, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * G,), jnp.float32),
            pltpu.VMEM((C * G,), jnp.float32),
            pltpu.VMEM((C * G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window,
                          block_tokens=bt, chunk=C, group=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, C, G, hd), q.dtype),
        interpret=interpret,
    )(btab, lens, q5, knr, vnr, k_pages, v_pages)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, C, H, hd)
