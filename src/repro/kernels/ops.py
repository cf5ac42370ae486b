"""Jit'd public wrappers for the Pallas kernels, routed through
``kernels.dispatch`` (one registry, three backends: tpu / interpret / ref).

On TPU hardware every wrapper compiles the real kernel; off-TPU the
element-wise kernels execute in interpret mode (kernel body traced in
Python, numerics identical) while grid-heavy kernels (paged attention)
default to the jnp ref oracle so the serving hot path stays an XLA graph.
``use_pallas=False`` forces the ref oracle — the path used by the dry-run
lowering (GSPMD-friendly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch as kd
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.moe_gmm import moe_gmm as _gmm
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.paged_prefill_attention import (
    paged_prefill_attention as _paged_prefill,
)
from repro.kernels.rao_scatter import rao_scatter_add as _rao
from repro.kernels.rmsnorm import rmsnorm as _rms
from repro.kernels.ssd_scan import ssd_scan as _ssd

kd.register("flash_attention", pallas=_flash, ref=ref.flash_attention)
kd.register("paged_attention", pallas=_paged, ref=ref.paged_attention,
            prefer_interpret=False)     # serving hot path: ref off-TPU
kd.register("paged_prefill_attention", pallas=_paged_prefill,
            ref=ref.paged_prefill_attention,
            prefer_interpret=False)     # serving hot path: ref off-TPU
kd.register("ssd_scan", pallas=_ssd, ref=ref.ssd_scan)
kd.register("moe_gmm", pallas=_gmm, ref=ref.moe_gmm)
kd.register("rao_scatter_add", pallas=_rao, ref=ref.rao_scatter_add)
kd.register("rmsnorm", pallas=_rms, ref=ref.rmsnorm)


def _backend(use_pallas: bool):
    return None if use_pallas else "ref"


@functools.partial(jax.jit, static_argnames=("causal", "window", "use_pallas"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    use_pallas: bool = True):
    """q: (B,S,H,hd); k,v: (B,T,K,hd) GQA (K divides H). -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    # expand kv heads to H (GQA -> MHA layout for the kernel)
    rep = H // K
    kx = jnp.repeat(k, rep, axis=2).transpose(0, 2, 1, 3)   # (B,H,T,hd)
    vx = jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3)
    qx = q.transpose(0, 2, 1, 3)
    impl = kd.dispatch("flash_attention", _backend(use_pallas))
    out = impl(qx, kx, vx, causal=causal, window=window)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("window", "backend"))
def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    k_new, v_new, *, window: int = 0,
                    backend: str | None = None):
    """Single-token decode over a block-table-indexed KV pool (GQA).

    q: (B,H,hd); k_pages/v_pages: (P,K,bt,hd); block_tables: (B,nb) int32;
    seq_lens: (B,) int32; k_new/v_new: (B,K,hd).  See kernels.ref for the
    full contract.  ``backend=None`` -> Pallas kernel on TPU, ref oracle
    elsewhere (the kernel grid would be Python-stepped in interpret mode —
    off the serving hot path it lives in tests only).
    """
    impl = kd.dispatch("paged_attention", backend)
    return impl(q, k_pages, v_pages, block_tables, seq_lens,
                k_new, v_new, window=window)


@functools.partial(jax.jit, static_argnames=("window", "backend"))
def paged_prefill_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                            k_new, v_new, *, window: int = 0,
                            backend: str | None = None):
    """Chunked-prefill attention over a partial paged context (GQA).

    q: (B,C,H,hd); k_pages/v_pages: (P,K,bt,hd); block_tables: (B,nb)
    int32; ctx_lens: (B,) int32; k_new/v_new: (B,C,K,hd) the chunk's own
    keys/values (folded in causally, written to the pool by the caller
    afterwards).  See kernels.ref for the full contract.  ``backend=None``
    -> Pallas kernel on TPU, ref oracle elsewhere.
    """
    impl = kd.dispatch("paged_prefill_attention", backend)
    return impl(q, k_pages, v_pages, block_tables, ctx_lens,
                k_new, v_new, window=window)


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def ssd_scan(x, Bm, Cm, dt, A, *, chunk: int = 128, use_pallas: bool = True):
    impl = kd.dispatch("ssd_scan", _backend(use_pallas))
    if use_pallas:
        return impl(x, Bm, Cm, dt, A, chunk=chunk)
    return impl(x, Bm, Cm, dt, A)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def moe_gmm(xe, w, *, use_pallas: bool = True):
    return kd.dispatch("moe_gmm", _backend(use_pallas))(xe, w)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def rao_scatter_add(table, idx, vals, *, use_pallas: bool = True):
    return kd.dispatch("rao_scatter_add", _backend(use_pallas))(table, idx,
                                                               vals)


@functools.partial(jax.jit, static_argnames=("eps", "use_pallas"))
def rmsnorm(x, w, eps: float = 1e-5, *, use_pallas: bool = True):
    return kd.dispatch("rmsnorm", _backend(use_pallas))(x, w, eps)
