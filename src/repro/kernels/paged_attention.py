"""Paged decode-attention Pallas kernel (TPU): one query token per slot
attending over a block-table-indexed KV pool.

Grid ``(slot,)``; one grid step runs a slot's whole attention, for all its
KV heads, as a loop over compute blocks of ``ppb`` whole pages (``ppb``
from the page's shape).  A page of the ``(P, K, bt, hd)`` arena holds
every KV head contiguously, so each page is one DMA per arena, from HBM
into a double-buffered VMEM block of ``ppb`` pages, by page ids read from
the scalar-prefetched block table.  While one block is computed the next
is in flight, and the last block of a slot prefetches the next slot's
first: fine-grained coherent page reads instead of a dense
(slots, max_len) gather, the paper's block-granular shared-pool access
pattern.

The loop runs only a slot's live blocks: those before ``cdiv(len, ppb·bt)``
and, with a sliding window, not wholly before it.  Within a live block only
the live pages are fetched; the rest of the buffer keeps stale (finite)
pages that the mask zeroes.  The dots take the pages' own dtype with f32
accumulation, and the softmax statistics stay f32.  The current token's
(k_new, v_new) — not yet written to the pool — is folded into the softmax
at the end, so the pool write can happen after attention as one fused
scatter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
MIN_BLOCK_TOKENS = 128          # a compute block spans at least this many
MAX_BLOCK_TOKENS = 512          # ... and grows toward BLOCK_BYTES up to this
BLOCK_BYTES = 256 * 1024        # K bytes of one block, all heads
LANES = 128


def row_view(K: int, bt: int, hd: int):
    """How a ``(K, bt, hd)`` page is viewed as rows: ``(R, W)`` with ``R``
    tokens of one head side by side in a row of ``W`` lanes.  A head
    narrower than the lanes (hd 64: R 2) is packed lane-dense, since a DMA
    of a (bt, 64) tile from HBM is refused; else a row is one token."""
    if hd < LANES and LANES % hd == 0 and bt % (LANES // hd) == 0:
        return LANES // hd, LANES
    return 1, hd


def pages_per_block(bt: int, K: int, hd: int, dtype) -> int:
    """Pages in one compute block, from the page's shape: the fewest that
    span ``MIN_BLOCK_TOKENS``, doubled while the block's K bytes are below
    ``BLOCK_BYTES`` and it spans fewer than ``MAX_BLOCK_TOKENS``.  Its
    double-buffered K and V blocks take 4 x that in VMEM (1 MiB)."""
    page_bytes = K * bt * hd * np.dtype(dtype).itemsize
    ppb = max(1, MIN_BLOCK_TOKENS // bt)
    while ppb * page_bytes < BLOCK_BYTES and 2 * ppb * bt <= MAX_BLOCK_TOKENS:
        ppb *= 2
    return ppb


def _kernel(btab_ref, lens_ref, rhead_ref, chead_ref, coff_ref, q_ref,
            kn_ref, vn_ref, kp_hbm, vp_hbm, o_ref, kbuf, vbuf, sems, state,
            qx_ref, m_ref, l_ref, acc_ref, *,
            scale: float, window: int, bt: int, ppb: int, nb: int, R: int):
    """One slot, all heads.  Score rows are (kv head, q head in group);
    parity ``e`` scores row tokens ``R*i + e`` of every page row, with q in
    lanes ``[e*hd, (e+1)*hd)`` of ``qx_ref[e]``.  Columns are (page, row);
    a column of another kv head is masked, so each parity is one matmul
    over the whole block and keeps its own softmax statistics."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    KG, hd = q_ref.shape[1:]
    C, W = ppb * kbuf.shape[2], kbuf.shape[3]    # columns, lanes
    T = ppb * bt                                 # tokens a block spans

    def first_live(L):                           # first attended position
        return jnp.maximum(L - window + 1, 0) if window else 0

    def blocks(slot):                            # live blocks [lo, hi)
        L = lens_ref[slot]
        return jax.lax.div(first_live(L), T), jax.lax.div(L + T - 1, T)

    def each_live_page(slot, blk, buf, act):
        """``act`` on the K and V copies of each live page of the block.
        A loop, not unrolled ppb times at each call site: tracing and
        lowering run at every start-up (the compilation cache keys on
        their result), and unrolled they cost seconds per bucket."""
        L = lens_ref[slot]
        p0 = first_live(L)

        def page(j, carry):
            g = blk * ppb + j                    # page index in the slot
            pid = jnp.maximum(btab_ref[slot, jnp.minimum(g, nb - 1)], 0)

            @pl.when(jnp.logical_and(g * bt < L, (g + 1) * bt > p0))
            def _():
                act(pltpu.make_async_copy(kp_hbm.at[pid], kbuf.at[buf, j],
                                          sems.at[0, buf]))
                act(pltpu.make_async_copy(vp_hbm.at[pid], vbuf.at[buf, j],
                                          sems.at[1, buf]))
            return carry

        jax.lax.fori_loop(0, ppb, page, 0)

    def start(slot, blk, buf):
        each_live_page(slot, blk, buf, lambda c: c.start())

    def wait(slot, blk, buf):
        each_live_page(slot, blk, buf, lambda c: c.wait())

    @pl.when(s == 0)
    def _first_slot():
        # pages left unfetched in a block are masked, but must be finite:
        # 0 x NaN is NaN in the PV dot
        vbuf[...] = jnp.zeros_like(vbuf)
        state[0] = 0                             # buffer of the next block
        state[1] = 0                             # is it already in flight?

    L = lens_ref[s]
    p0 = first_live(L)
    lo, hi = blocks(s)
    prefetched = state[1]
    state[1] = 0

    @pl.when(jnp.logical_and(hi > lo, prefetched == 0))
    def _start_first():
        start(s, lo, state[0])

    q = q_ref[0].astype(jnp.float32)             # (KG, hd)
    qx_ref[...] = jnp.zeros_like(qx_ref)
    for e in range(R):
        qx_ref[e, :, e * hd:(e + 1) * hd] = q
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    own_head = rhead_ref[...] == chead_ref[...]  # (KG, C)

    def body(blk, buf):
        nxt = 1 - buf

        @pl.when(blk + 1 < hi)
        def _next_block():
            start(s, blk + 1, nxt)

        @pl.when(jnp.logical_and(blk + 1 == hi, s + 1 < n_slots))
        def _next_slot():
            ns = jnp.minimum(s + 1, n_slots - 1)
            nlo, nhi = blocks(ns)

            @pl.when(nhi > nlo)
            def _():
                start(ns, nlo, nxt)
                state[1] = 1

        wait(s, blk, buf)
        kb = kbuf[buf].reshape(C, W)
        vb = vbuf[buf].reshape(C, W)
        for e in range(R):
            pos = blk * T + coff_ref[...] + e    # (1, C)
            live = jnp.logical_and(own_head, pos < L)
            if window:
                live = jnp.logical_and(live, pos >= p0)
            sc = jax.lax.dot_general(
                qx_ref[e].astype(kb.dtype), kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (KG, C)
            sc = jnp.where(live, sc, NEG_INF)
            m_prev = m_ref[e]                    # (KG, 1)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.where(live, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[e] = l_ref[e] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[e] = acc_ref[e] * alpha + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (KG, W)
            m_ref[e] = m_new
        return nxt

    state[0] = jax.lax.fori_loop(lo, hi, body, state[0])

    # merge the parities and fold in the current token (its kv is
    # pool-written after the call)
    own = rhead_ref[...] == jax.lax.broadcasted_iota(
        jnp.int32, (1, kn_ref.shape[1]), 1)      # (KG, K): row's kv head
    kn = kn_ref[0]
    sn = jnp.sum(jnp.where(own, jax.lax.dot_general(
        q.astype(kn.dtype), kn, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32), 0.0),
        axis=-1, keepdims=True) * scale          # (KG, 1)
    vn = jax.lax.dot_general(
        own.astype(vn_ref.dtype), vn_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (KG, hd)
    m = sn
    for e in range(R):
        m = jnp.maximum(m, m_ref[e])
    pn = jnp.exp(sn - m)
    l_fin = pn                                   # >= pn > 0: no 0-div
    acc = pn * vn
    for e in range(R):
        alpha = jnp.exp(m_ref[e] - m)
        l_fin = l_fin + alpha * l_ref[e]
        acc = acc + alpha * acc_ref[e][:, e * hd:(e + 1) * hd]
    o_ref[0] = (acc / l_fin).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    k_new, v_new, *, window: int = 0,
                    interpret: bool = True):
    """Contract of ``kernels.ref.paged_attention`` (the test oracle).

    q: (B, H, hd); k_pages/v_pages: (P, K, bt, hd); block_tables: (B, nb)
    int32 (< 0 = unallocated); seq_lens: (B,) int32 tokens resident;
    k_new/v_new: (B, K, hd) current token.  Returns (B, H, hd).
    ``interpret`` runs the kernel on the TPU interpreter (DMAs and
    semaphores included), anywhere.
    """
    B, H, hd = q.shape
    P, K, bt, _ = k_pages.shape
    nb = block_tables.shape[1]
    G = H // K
    R, W = row_view(K, bt, hd)
    rows = K * bt // R                           # page rows
    ppb = min(pages_per_block(bt, K, hd, k_pages.dtype), nb)
    C = ppb * rows
    col = np.arange(C)
    r = col % rows
    chead = (r // (bt // R)).astype(np.int32)[None]            # (1, C)
    coff = (col // rows * bt + r % (bt // R) * R).astype(np.int32)[None]
    rhead = (np.arange(H) // G).astype(np.int32)[:, None]      # (H, 1)

    const = lambda s, *_: (0, 0)                 # noqa: E731
    slot = lambda s, *_: (s, 0, 0)               # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((H, 1), const),
            pl.BlockSpec((1, C), const),
            pl.BlockSpec((1, C), const),
            pl.BlockSpec((1, H, hd), slot),
            pl.BlockSpec((1, K, hd), slot),
            pl.BlockSpec((1, K, hd), slot),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, hd), slot),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, rows, W), k_pages.dtype),
            pltpu.VMEM((2, ppb, rows, W), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((R, H, W), jnp.float32),
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, W), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / np.sqrt(hd), window=window,
                          bt=bt, ppb=ppb, nb=nb, R=R),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        # one slot's state (buffer, prefetch) carries into the next step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_attention",
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      jnp.asarray(rhead), jnp.asarray(chead), jnp.asarray(coff), q,
      k_new, v_new, k_pages.reshape(P, rows, W), v_pages.reshape(P, rows, W))
