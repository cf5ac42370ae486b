"""Core layers + declarative parameter schemas.

Parameters are described by ``ParamDef`` trees (shape + logical axes + init).
From one schema we derive: abstract ShapeDtypeStructs (dry-run), logical axis
trees (sharding), and materialized init (tests/examples).  Models are pure
functions over these param pytrees — no framework dependency.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# Param schema machinery
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones
    scale: Optional[float] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def stack_schema(schema, n: int, axis_name: str = "layers"):
    """Prepend a stacking dim (for scan-over-layers) to every ParamDef."""
    def f(p: ParamDef) -> ParamDef:
        return ParamDef((n,) + p.shape, (axis_name,) + p.axes, p.init, p.scale)
    return jax.tree.map(f, schema, is_leaf=lambda x: isinstance(x, ParamDef))


def abstract_params(schema, dtype) -> Any:
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype),
        schema, is_leaf=lambda x: isinstance(x, ParamDef))


def logical_axes(schema) -> Any:
    return jax.tree.map(lambda p: p.axes, schema,
                        is_leaf=lambda x: isinstance(x, ParamDef))


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _normal_leaf(key, shape, scale, dtype):
    # one fused program: the float32 draw never materialises, so a stacked
    # leaf costs only its ``dtype`` bytes on the device
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(schema, key, dtype) -> Any:
    """Deterministic per-leaf init keyed by tree path.  The path salt is
    crc32, NOT Python's hash(): hash() is randomized per process
    (PYTHONHASHSEED), which made params — and therefore any greedy-argmax
    comparison near a logit tie — differ from run to run."""
    import zlib
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(
        schema, is_leaf=lambda x: isinstance(x, ParamDef))
    flat, treedef = leaves_with_paths

    out = []
    for path, p in flat:
        pstr = "/".join(str(k) for k in path)
        sub = jax.random.fold_in(
            key, np.uint32(zlib.crc32(pstr.encode()) & 0x7FFFFFFF))
        if p.init == "zeros":
            arr = jnp.zeros(p.shape, dtype)
        elif p.init == "ones":
            arr = jnp.ones(p.shape, dtype)
        else:
            fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
            scale = p.scale if p.scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
            arr = _normal_leaf(sub, tuple(p.shape), float(scale),
                               jnp.dtype(dtype))
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# Normalization
# --------------------------------------------------------------------------
def rmsnorm(x, w, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32)) + b.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE (incl. M-RoPE for qwen2-vl)
# --------------------------------------------------------------------------
def _rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.asarray(_rope_freqs(hd, theta))          # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                     # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def apply_m_rope(x, pos3, sections: Tuple[int, ...], theta: float):
    """qwen2-vl M-RoPE.  x: (B,S,H,hd); pos3: (B,S,3) int (t,h,w).

    `sections` partitions the half-dim; section i rotates with pos3[..., i].
    """
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, half)
    freqs = jnp.asarray(_rope_freqs(hd, theta))          # (half,)
    # per-frequency position selection
    sec_id = np.concatenate([np.full(s, i) for i, s in enumerate(sections)])
    sec_id = jnp.asarray(sec_id)                         # (half,)
    pos = jnp.take_along_axis(
        pos3.astype(jnp.float32),
        jnp.broadcast_to(sec_id[None, None, :], pos3.shape[:2] + (half,)).astype(jnp.int32),
        axis=-1)                                         # (B,S,half)
    ang = pos * freqs                                     # (B,S,half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def sinusoidal_positions(n: int, d: int):
    pos = np.arange(n)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * dim / d))
    out = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(out, jnp.float32)


# --------------------------------------------------------------------------
# Attention (GQA / causal / sliding-window / cross), XLA reference path
# --------------------------------------------------------------------------
def gqa_attention(q, k, v, *, q_pos=None, k_pos=None, k_valid=None,
                  causal: bool = True, window: int = 0,
                  softcap: float = 0.0):
    """q: (B,S,H,hd)  k,v: (B,T,K,hd) with H % K == 0.

    q_pos: (B,S) or (S,) query positions; k_pos: (B,T) or (T,) key positions.
    k_valid: optional (B,T) bool for unwritten cache slots.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scale = 1.0 / np.sqrt(hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap

    if q_pos is None:
        q_pos = jnp.arange(S)
    if k_pos is None:
        k_pos = jnp.arange(T)
    qp = jnp.asarray(q_pos)
    kp = jnp.asarray(k_pos)
    if qp.ndim == 1:
        qp = jnp.broadcast_to(qp[None], (B, S))
    if kp.ndim == 1:
        kp = jnp.broadcast_to(kp[None], (B, T))
    mask = jnp.ones((B, S, T), bool)
    if causal:
        mask &= kp[:, None, :] <= qp[:, :, None]
    if window:
        mask &= kp[:, None, :] > (qp[:, :, None] - window)
    if k_valid is not None:
        mask &= k_valid[:, None, :]
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w.astype(v.dtype), v)
    return out.reshape(B, S, H, hd)


def blocked_gqa_attention(q, k, v, *, q_pos=None, window: int = 0,
                          q_chunk: int = 2048, causal: bool = True,
                          unroll: bool = False):
    """Query-chunked attention: scans q in chunks of ``q_chunk`` so the score
    tensor is O(q_chunk·T) instead of O(S·T).  With a sliding window, only a
    (window + q_chunk)-sized KV slab is gathered per chunk (banded attention).
    Shapes as in ``gqa_attention``; requires S % q_chunk == 0.
    """
    B, S, H, hd = q.shape
    T = k.shape[1]
    assert S % q_chunk == 0, (S, q_chunk)
    nq = S // q_chunk
    if q_pos is None:
        q_pos = jnp.arange(S)
    qp = jnp.asarray(q_pos)
    if qp.ndim == 1:
        qp = jnp.broadcast_to(qp[None], (B, S))

    slab = window + q_chunk if (window and T >= window + q_chunk) else 0

    def body(_, i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, 1)
        qps = jax.lax.dynamic_slice_in_dim(qp, i * q_chunk, q_chunk, 1)
        if slab:
            start = jnp.clip(i * q_chunk + q_chunk - slab, 0, T - slab)
            ks = jax.lax.dynamic_slice_in_dim(k, start, slab, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, slab, 1)
            kps = start + jnp.arange(slab)
        else:
            ks, vs, kps = k, v, None
        out = gqa_attention(qs, ks, vs, q_pos=qps, k_pos=kps,
                            causal=causal, window=window)
        return None, out

    if unroll:  # cost-probe mode
        outs = jnp.stack([body(None, jnp.asarray(i))[1] for i in range(nq)])
    else:
        _, outs = jax.lax.scan(body, None, jnp.arange(nq))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)


# --------------------------------------------------------------------------
# Schemas for standard sub-blocks
# --------------------------------------------------------------------------
def attn_schema(cfg) -> Dict[str, ParamDef]:
    D, Q, KV, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.head_dim
    infer = cfg.infer_weight_layout
    emb_ax = None if infer else "embed"
    h_ax = "heads_j" if infer else "heads"
    kv_ax = "kv_heads_j" if infer else "kv_heads"
    s: Dict[str, ParamDef] = {
        "wq": ParamDef((D, Q), (emb_ax, h_ax)),
        "wk": ParamDef((D, KV), (emb_ax, kv_ax)),
        "wv": ParamDef((D, KV), (emb_ax, kv_ax)),
        "wo": ParamDef((Q, D), (h_ax, emb_ax)),
    }
    if cfg.use_bias:
        s["bq"] = ParamDef((Q,), ("heads",), "zeros")
        s["bk"] = ParamDef((KV,), ("kv_heads",), "zeros")
        s["bv"] = ParamDef((KV,), ("kv_heads",), "zeros")
    if cfg.use_qk_norm:
        s["q_norm"] = ParamDef((hd,), (None,), "zeros")
        s["k_norm"] = ParamDef((hd,), (None,), "zeros")
    return s


def mlp_schema(cfg, d_ff: Optional[int] = None) -> Dict[str, ParamDef]:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    infer = cfg.infer_weight_layout
    emb_ax = None if infer else "embed"
    f_ax = "ffn_j" if infer else "ffn"
    return {
        "wg": ParamDef((D, F), (emb_ax, f_ax)),
        "wu": ParamDef((D, F), (emb_ax, f_ax)),
        "wd": ParamDef((F, D), (f_ax, emb_ax)),
    }


def act_logical(cfg, width_dim=None):
    """(batch, seq, width) logical layout.

    "embed": width dims over 'model'; "seq" (Megatron-SP): sequence over
    'model' everywhere (GSPMD then picks the cheapest transitions around
    attention — measured better than forcing S-full inners, §Perf it.8
    refuted); "none": replicated.
    """
    mode = getattr(cfg, "act_shard", "embed")
    if not getattr(cfg, "seq_shard_activations", True):
        mode = "none"
    if mode == "seq":
        return ("batch", "act_seq", None)
    if mode == "none":
        return ("batch", None, None)
    return ("batch", None, width_dim or "act_embed")


def _pin(x, logical, cfg, mesh):
    """Pin an intermediate's layout (prevents GSPMD from floating
    activation-sized reshards between projections — §Perf it.6)."""
    import os
    if mesh is None or os.environ.get("REPRO_NO_PINS") or \
            not getattr(cfg, "pin_intermediates", True):
        return x
    from repro.parallel.sharding import constraint
    return constraint(x, logical, mesh)


def attn_apply(p, x, cfg, *, positions=None, pos3=None, kv=None,
               k_pos=None, k_valid=None, causal=True, cross=False,
               q_chunk: int = 0, mesh=None):
    """Standard pre-projected GQA attention.  If kv=(k,v) given, uses it
    (decode / cross-attn); else computes k,v from x.  q_chunk>0 selects the
    query-blocked path (long-sequence prefill/train)."""
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = _pin(q, act_logical(cfg, "heads"), cfg, mesh)
    q = q.reshape(B, S, H, hd)
    if kv is None:
        k = jnp.einsum("bsd,dq->bsq", x, p["wk"])
        v = jnp.einsum("bsd,dq->bsq", x, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k = _pin(k, act_logical(cfg, "kv_heads"), cfg, mesh)
        v = _pin(v, act_logical(cfg, "kv_heads"), cfg, mesh)
        k = k.reshape(B, S, K, hd)
        v = v.reshape(B, S, K, hd)
    else:
        k, v = kv
    if cfg.use_qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        if kv is None:
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if not cross and cfg.rope_theta > 0:
        if cfg.m_rope_sections and pos3 is not None:
            q = apply_m_rope(q, pos3, cfg.m_rope_sections, cfg.rope_theta)
            if kv is None:
                k = apply_m_rope(k, pos3, cfg.m_rope_sections, cfg.rope_theta)
        elif positions is not None:
            q = apply_rope(q, positions, cfg.rope_theta)
            if kv is None:
                k = apply_rope(k, positions, cfg.rope_theta)
    if (cfg.attention_impl == "pallas" and not cross and kv is None
            and causal and S == k.shape[1] and S % 128 == 0):
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True,
                                   window=cfg.sliding_window)
    elif q_chunk and S > q_chunk and S % q_chunk == 0 and not cross and kv is None:
        out = blocked_gqa_attention(
            q, k, v, q_pos=positions, window=cfg.sliding_window,
            q_chunk=q_chunk, causal=causal,
            unroll=not cfg.scan_layers)
    else:
        out = gqa_attention(
            q, k, v,
            q_pos=positions if positions is not None else None,
            k_pos=k_pos, k_valid=k_valid,
            causal=causal and not cross,
            window=cfg.sliding_window if not cross else 0)
    out = out.reshape(B, S, H * hd)
    out = _pin(out, act_logical(cfg, "heads"), cfg, mesh)
    proj = jnp.einsum("bsq,qd->bsd", out, p["wo"])
    proj = _pin(proj, act_logical(cfg), cfg, mesh)
    return proj, (k, v)


def paged_attn_apply(p, x, cfg, k_pages, v_pages, block_tables, seq_lens,
                     *, pos3=None, mesh=None):
    """Single-token decode attention against a block-table-indexed KV pool.

    x: (B, 1, D) — the current token's hidden state per slot;
    k_pages/v_pages: (P, K, bt, hd) pooled arena (one layer's pages);
    block_tables: (B, nb) int32; seq_lens: (B,) int32 tokens resident.
    The current token's k/v are projected here, folded into the softmax by
    the kernel, and returned (cast to the pool dtype) for the caller to
    scatter into the pool — so attention reads never race the pool write.
    Returns (attn_out (B, 1, D), (k_new, v_new) each (B, 1, K, hd)).
    """
    B, S, D = x.shape
    assert S == 1, "paged attention is a decode (single-query) path"
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qpos = seq_lens[:, None]                         # (B, 1) query positions
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = _pin(q, act_logical(cfg, "heads"), cfg, mesh)
    q = q.reshape(B, 1, H, hd)
    if cfg.use_qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
    if cfg.rope_theta > 0:
        if cfg.m_rope_sections and pos3 is not None:
            q = apply_m_rope(q, pos3, cfg.m_rope_sections, cfg.rope_theta)
        else:
            q = apply_rope(q, qpos, cfg.rope_theta)
    kn, vn = compute_kv(p, x, cfg,
                        positions=pos3 if cfg.m_rope_sections else qpos)
    # match the dense cache path bit-for-bit: kv is stored (and attended)
    # in the pool dtype
    kn = kn.astype(k_pages.dtype)
    vn = vn.astype(v_pages.dtype)
    from repro.kernels import ops as kops
    out = kops.paged_attention(q[:, 0], k_pages, v_pages, block_tables,
                               seq_lens, kn[:, 0], vn[:, 0],
                               window=cfg.sliding_window)
    out = out.reshape(B, 1, H * hd)
    out = _pin(out, act_logical(cfg, "heads"), cfg, mesh)
    proj = jnp.einsum("bsq,qd->bsd", out, p["wo"])
    proj = _pin(proj, act_logical(cfg), cfg, mesh)
    return proj, (kn, vn)


def paged_prefill_attn_apply(p, x, cfg, k_pages, v_pages, block_tables,
                             ctx_lens, *, pos3=None, mesh=None):
    """Chunk-resumable prefill attention against a block-table-indexed
    KV pool.

    x: (B, C, D) — one prompt chunk per slot, sitting at absolute positions
    ``ctx_lens + [0, C)``; k_pages/v_pages: (P, K, bt, hd) pooled arena
    (one layer's pages) holding the ``ctx_lens`` tokens of earlier chunks.
    The chunk's own k/v are projected here, folded into the softmax by the
    kernel with the in-chunk causal mask, and returned (cast to the pool
    dtype) for the caller to scatter into the pool — so attention reads
    never race the pool write.
    Returns (attn_out (B, C, D), (k_new, v_new) each (B, C, K, hd)).
    """
    B, C, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = ctx_lens[:, None] + jnp.arange(C)[None, :]   # (B, C)
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = _pin(q, act_logical(cfg, "heads"), cfg, mesh)
    q = q.reshape(B, C, H, hd)
    if cfg.use_qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
    if cfg.rope_theta > 0:
        if cfg.m_rope_sections and pos3 is not None:
            q = apply_m_rope(q, pos3, cfg.m_rope_sections, cfg.rope_theta)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
    kn, vn = compute_kv(p, x, cfg,
                        positions=pos3 if cfg.m_rope_sections else positions)
    # match the paged decode path: kv is stored (and attended) in the
    # pool dtype
    kn = kn.astype(k_pages.dtype)
    vn = vn.astype(v_pages.dtype)
    from repro.kernels import ops as kops
    out = kops.paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                       ctx_lens, kn, vn,
                                       window=cfg.sliding_window)
    out = out.reshape(B, C, H * hd)
    out = _pin(out, act_logical(cfg, "heads"), cfg, mesh)
    proj = jnp.einsum("bsq,qd->bsd", out, p["wo"])
    proj = _pin(proj, act_logical(cfg), cfg, mesh)
    return proj, (kn, vn)


def mlp_apply(p, x, cfg=None, mesh=None):
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    u = jnp.einsum("bsd,df->bsf", x, p["wu"])
    if cfg is not None:
        g = _pin(g, act_logical(cfg, "ffn"), cfg, mesh)
        u = _pin(u, act_logical(cfg, "ffn"), cfg, mesh)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    out = jnp.einsum("bsf,fd->bsd", h, p["wd"])
    if cfg is not None:
        out = _pin(out, act_logical(cfg), cfg, mesh)
    return out


def compute_kv(p, x, cfg, positions=None):
    """Project k,v for writing a KV cache (used by decode/prefill)."""
    B, S, _ = x.shape
    K, hd = cfg.n_kv_heads, cfg.head_dim
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    if cfg.use_qk_norm:
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_theta <= 0:
        pass
    elif positions is not None and not cfg.m_rope_sections:
        k = apply_rope(k, positions, cfg.rope_theta)
    elif positions is not None and cfg.m_rope_sections:
        pos3 = jnp.broadcast_to(
            jnp.asarray(positions)[..., None], k.shape[:2] + (3,)) \
            if jnp.asarray(positions).ndim <= 2 else positions
        k = apply_m_rope(k, pos3, cfg.m_rope_sections, cfg.rope_theta)
    return k, v
