"""Pure-jnp oracles for every Pallas kernel (the source of truth in tests).

Each function mirrors the kernel contract exactly; kernels are validated
against these with assert_allclose over shape/dtype sweeps in
tests/test_kernels_*.py (interpret=True on CPU, per the brief).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None):
    """q,k,v: (B, H, S, hd) (kv already expanded to H heads)."""
    B, H, S, hd = q.shape
    T = k.shape[2]
    scale = scale or 1.0 / np.sqrt(hd)
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= kp > qp - window
    s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def _gather_pages(pages, block_tables):
    """(P, K, bt, hd) arena + (B, nb) table -> (B, nb * bt, K, hd) in
    position order; entries < 0 read page 0 (the caller masks them)."""
    B, nb = block_tables.shape
    _, K, bt, hd = pages.shape
    g = pages[jnp.maximum(block_tables, 0)]              # (B, nb, K, bt, hd)
    return g.transpose(0, 1, 3, 2, 4).reshape(B, nb * bt, K, hd)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    k_new, v_new, *, window: int = 0,
                    scale: float | None = None):
    """Single-query-per-slot decode attention over a block-table-indexed
    KV pool (jit-compatible dense gather; the oracle for the Pallas kernel).

    q: (B, H, hd) — one query token per slot, H % K == 0 (GQA).
    k_pages, v_pages: (P, K, bt, hd) pooled KV arena in ``bt``-token blocks.
    block_tables: (B, nb) int32 — page ids per slot in position order;
        entries < 0 are unallocated (their positions must be masked dead).
    seq_lens: (B,) int32 — tokens resident in the pages per slot; the query
        sits at position ``seq_lens`` and attends to pos < seq_lens plus the
        not-yet-paged current token (k_new, v_new): (B, K, hd).
    window: sliding window (0 = full); old position p is live iff
        p < seq_lens and p > seq_lens - window.
    Returns (B, H, hd) in q.dtype.
    """
    B, H, hd = q.shape
    P, K, bt, _ = k_pages.shape
    nb = block_tables.shape[1]
    G = H // K
    scale = scale or 1.0 / np.sqrt(hd)

    kg = _gather_pages(k_pages, block_tables)            # (B, T, K, hd)
    vg = _gather_pages(v_pages, block_tables)
    pos = jnp.arange(nb * bt)[None, :]                   # (1, T)
    live = pos < seq_lens[:, None]
    if window:
        live &= pos > (seq_lens[:, None] - window)

    qg = q.reshape(B, K, G, hd).astype(jnp.float32)
    s_old = jnp.einsum("bkgd,btkd->bkgt", qg,
                       kg.astype(jnp.float32)) * scale   # (B,K,G,T)
    s_old = jnp.where(live[:, None, None, :], s_old, -1e30)
    s_new = jnp.einsum("bkgd,bkd->bkg", qg,
                       k_new.astype(jnp.float32)) * scale
    s = jnp.concatenate([s_old, s_new[..., None]], axis=-1)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", w[..., :-1],
                     vg.astype(jnp.float32))
    out = out + w[..., -1:] * v_new[:, :, None, :].astype(jnp.float32)
    return out.reshape(B, H, hd).astype(q.dtype)


def paged_prefill_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                            k_new, v_new, *, window: int = 0,
                            scale: float | None = None):
    """Chunked-prefill attention over a *partial* paged context: the
    multi-query counterpart of ``paged_attention`` (and the oracle for its
    Pallas kernel).

    q: (B, C, H, hd) — one prompt chunk per slot, H % K == 0 (GQA).
    k_pages, v_pages: (P, K, bt, hd) pooled KV arena in ``bt``-token blocks.
    block_tables: (B, nb) int32 — page ids per slot in position order;
        entries < 0 are unallocated/released (masked dead).
    ctx_lens: (B,) int32 — tokens already resident in the pages; chunk
        query c sits at absolute position ``ctx_lens + c`` and attends to
        page positions p < ctx_lens plus the chunk's own keys k <= c
        (k_new, v_new: (B, C, K, hd), not yet paged).  Chunk rows past a
        slot's valid length still get finite output (they attend at least
        to themselves) — the caller routes their KV to the trash page and
        ignores their activations.
    window: sliding window over absolute positions (0 = full): key at
        absolute position p is live for query at absolute position qp iff
        p > qp - window.
    Returns (B, C, H, hd) in q.dtype.
    """
    B, C, H, hd = q.shape
    P, K, bt, _ = k_pages.shape
    nb = block_tables.shape[1]
    G = H // K
    scale = scale or 1.0 / np.sqrt(hd)

    kg = _gather_pages(k_pages, block_tables)            # (B, T, K, hd)
    vg = _gather_pages(v_pages, block_tables)
    pos = jnp.arange(nb * bt)[None, None, :]             # (1, 1, T)
    qpos = (ctx_lens[:, None]
            + jnp.arange(C)[None, :])[:, :, None]        # (B, C, 1)
    live = (pos < ctx_lens[:, None, None]) \
        & (block_tables >= 0).repeat(bt, axis=1)[:, None, :]
    if window:
        live = live & (pos > qpos - window)
    live = jnp.broadcast_to(live, (B, C, nb * bt))

    qg = q.reshape(B, C, K, G, hd).astype(jnp.float32)
    s_old = jnp.einsum("bckgd,btkd->bkgct", qg,
                       kg.astype(jnp.float32)) * scale   # (B,K,G,C,T)
    s_old = jnp.where(live[:, None, None, :, :], s_old, -1e30)
    s_new = jnp.einsum("bckgd,bukd->bkgcu", qg,
                       k_new.astype(jnp.float32)) * scale  # (B,K,G,C,C)
    cq = jnp.arange(C)[:, None]
    cu = jnp.arange(C)[None, :]
    self_mask = cu <= cq                                  # causal in-chunk
    if window:
        self_mask = self_mask & (cu > cq - window)
    s_new = jnp.where(self_mask[None, None, None], s_new, -1e30)
    s = jnp.concatenate([s_old, s_new], axis=-1)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgct,btkd->bckgd", w[..., : nb * bt],
                     vg.astype(jnp.float32))
    out = out + jnp.einsum("bkgcu,bukd->bckgd", w[..., nb * bt:],
                           v_new.astype(jnp.float32))
    return out.reshape(B, C, H, hd).astype(q.dtype)


def ssd_scan(x, Bm, Cm, dt, A):
    """Mamba2/SSD sequential oracle.
    x: (B,L,h,hd)  Bm,Cm: (B,L,S)  dt: (B,L,h)  A: (h,) negative.
    Returns y: (B,L,h,hd) (f32)."""
    Bsz, L, h, hd = x.shape
    S = Bm.shape[-1]

    def step(state, inp):
        xt, bt, ct, dtt = inp                       # (B,h,hd) (B,S) (B,S) (B,h)
        dec = jnp.exp(dtt * A)                      # (B,h)
        state = state * dec[..., None, None] + \
            jnp.einsum("bh,bhd,bs->bhds", dtt, xt, bt)
        y = jnp.einsum("bs,bhds->bhd", ct, state)
        return state, y

    init = jnp.zeros((Bsz, h, hd, S), jnp.float32)
    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(Bm.astype(jnp.float32), 1, 0),
          jnp.moveaxis(Cm.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0))
    _, ys = jax.lax.scan(step, init, xs)
    return jnp.moveaxis(ys, 0, 1)


def moe_gmm(xe, w):
    """Grouped expert matmul.  xe: (E,C,D)  w: (E,D,F) -> (E,C,F)."""
    return jnp.einsum("ecd,edf->ecf", xe.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(xe.dtype)


def rao_scatter_add(table, idx, vals):
    """Atomic scatter-accumulate (RAO FAA over rows).
    table: (N,D)  idx: (M,) int32  vals: (M,D)."""
    return table.at[idx].add(vals.astype(table.dtype))


def rmsnorm(x, w, eps: float = 1e-5):
    """x: (N, D), w: (D,)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return y.astype(x.dtype)
