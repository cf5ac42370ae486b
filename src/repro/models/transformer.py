"""Decoder-only LM assembly for dense / moe / vlm / hybrid / ssm families.

Uniform-block families (dense, moe, vlm) are stacked and scanned
(``jax.lax.scan``) with a configurable remat policy.  zamba2-style hybrids
scan groups of [shared-attention + N mamba layers]; xLSTM's 12 heterogeneous
layers are unrolled.  All entry points are pure functions of (params, batch).

Entry points: ``lm_schema``, ``lm_loss``, ``lm_prefill``, ``lm_decode_step``,
``lm_init_cache``, ``cache_logical_axes``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xl
from repro.models.layers import (
    ParamDef, act_logical, attn_apply, attn_schema, compute_kv, mlp_apply,
    mlp_schema, paged_attn_apply, paged_prefill_attn_apply, rmsnorm,
    stack_schema,
)
from repro.parallel.embed import embed_lookup
from repro.parallel.sharding import constraint

Q_CHUNK = 2048
BLOCKED_MIN_SEQ = 8192


# --------------------------------------------------------------------------
# Schema
# --------------------------------------------------------------------------
def _block_schema(cfg, use_moe: bool) -> Dict[str, Any]:
    D = cfg.d_model
    s: Dict[str, Any] = {
        "ln1": ParamDef((D,), (None,), "zeros"),
        "attn": attn_schema(cfg),
        "ln2": ParamDef((D,), (None,), "zeros"),
    }
    if use_moe:
        s["moe"] = moe_mod.moe_schema(cfg)
    else:
        s["mlp"] = mlp_schema(cfg)
    return s


def _mamba_block_schema(cfg) -> Dict[str, Any]:
    return {"norm": ParamDef((cfg.d_model,), (None,), "zeros"),
            **ssm_mod.mamba_schema(cfg)}


def hybrid_layout(cfg) -> Tuple[int, int, int]:
    """(n_groups, group_size, tail) for zamba2-style hybrids."""
    every = cfg.hybrid_attn_every
    n_groups = cfg.n_layers // every
    tail = cfg.n_layers - n_groups * every
    return n_groups, every, tail


def lm_schema(cfg) -> Dict[str, Any]:
    V, D = cfg.padded_vocab, cfg.d_model
    s: Dict[str, Any] = {
        "emb": ParamDef((V, D), ("vocab", None), scale=0.02),
        "final_norm": ParamDef((D,), (None,), "zeros"),
    }
    if not cfg.tie_embeddings:
        s["head"] = ParamDef((D, V), ("embed", "vocab"))
    fam = cfg.family
    if fam in ("dense", "vlm"):
        s["blocks"] = stack_schema(_block_schema(cfg, False), cfg.n_layers)
    elif fam == "moe":
        s["blocks"] = stack_schema(_block_schema(cfg, True), cfg.n_layers)
    elif fam == "hybrid":
        ng, every, tail = hybrid_layout(cfg)
        mb = _mamba_block_schema(cfg)
        if ng > 0:
            s["mamba_groups"] = stack_schema(stack_schema(mb, every), ng)
        if tail:
            s["mamba_tail"] = stack_schema(mb, tail)
        s["shared"] = _block_schema(cfg, False)
    elif fam == "ssm":
        layers = {}
        for i in range(cfg.n_layers):
            kind = "slstm" if i in cfg.slstm_layers else "mlstm"
            sch = xl.slstm_schema(cfg) if kind == "slstm" else xl.mlstm_schema(cfg)
            layers[f"l{i:02d}"] = {
                "kind_" + kind: ParamDef((1,), (None,), "zeros"),  # marker
                "norm": ParamDef((D,), (None,), "zeros"), **sch}
        s["layers"] = layers
    else:
        raise ValueError(f"lm_schema: unsupported family {fam}")
    return s


def _layer_kind(cfg, i: int) -> str:
    return "slstm" if i in cfg.slstm_layers else "mlstm"


def tree_slice(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def scan_or_unroll(cfg, body, carry, xs, length):
    """lax.scan when cfg.scan_layers else a python loop (cost probes).
    Both paths apply the same remat policy so probe costs match the
    deployed configuration (incl. backward recompute + re-gathers)."""
    body_r = _remat(cfg, body)
    if cfg.scan_layers:
        return jax.lax.scan(body_r, carry, xs)
    ys = []
    for i in range(length):
        carry, y = body_r(carry, tree_slice(xs, i))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def _remat(cfg, fn):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


# --------------------------------------------------------------------------
# Embedding / logits
# --------------------------------------------------------------------------
def _embed(params, cfg, batch, mesh):
    tokens = batch["tokens"]
    x = embed_lookup(params["emb"], tokens, mesh)
    if cfg.family == "vlm" and "vis_embeds" in batch:
        vis = batch["vis_embeds"].astype(x.dtype)
        P = vis.shape[1]
        x = jnp.concatenate([vis, x[:, P:]], axis=1)
    if mesh is not None:
        x = constraint(x, act_logical(cfg), mesh)
    return x


def _logits(params, cfg, x, mesh):
    if cfg.tie_embeddings:
        lg = jnp.einsum("bsd,vd->bsv", x, params["emb"])
    else:
        lg = jnp.einsum("bsd,dv->bsv", x, params["head"])
    if mesh is not None:
        lg = constraint(lg, ("batch", None, "vocab"), mesh)
    return lg


# --------------------------------------------------------------------------
# Forward (train / prefill) bodies
# --------------------------------------------------------------------------
def _attn_block(bp, x, cfg, mesh, positions, pos3, q_chunk, collect):
    h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
    attn_out, (k, v) = attn_apply(bp["attn"], h, cfg, positions=positions,
                                  pos3=pos3, q_chunk=q_chunk, mesh=mesh)
    x = x + attn_out
    return x, ((k, v) if collect else None)


def _ffn_block(bp, x, cfg, use_moe, mesh=None):
    h = rmsnorm(x, bp["ln2"], cfg.norm_eps)
    if use_moe:
        y, aux = moe_mod.moe_apply(bp["moe"], h, cfg, return_aux=True,
                                   mesh=mesh)
        aux_loss = (cfg.router_aux_weight * aux["load_balance"]
                    + 1e-4 * aux["router_z"])
    else:
        y, aux_loss = mlp_apply(bp["mlp"], h, cfg, mesh), 0.0
    return x + y, aux_loss


def _uniform_forward(params, cfg, x, mesh, positions, pos3,
                     collect_cache: bool):
    use_moe = cfg.family == "moe"
    S = x.shape[1]
    q_chunk = cfg.q_chunk or (Q_CHUNK if S >= BLOCKED_MIN_SEQ else 0)

    def body(carry, bp):
        x, aux = carry
        if mesh is not None:
            x = constraint(x, act_logical(cfg), mesh)
        x, kv = _attn_block(bp, x, cfg, mesh, positions, pos3, q_chunk,
                            collect_cache)
        x, aux_l = _ffn_block(bp, x, cfg, use_moe, mesh)
        return (x, aux + aux_l), kv

    (x, aux), caches = scan_or_unroll(cfg, body, (x, 0.0),
                                      params["blocks"], cfg.n_layers)
    return x, aux, caches


def _hybrid_forward(params, cfg, x, mesh, positions, collect_cache: bool):
    ng, every, tail = hybrid_layout(cfg)
    S = x.shape[1]
    q_chunk = cfg.q_chunk or (Q_CHUNK if S >= BLOCKED_MIN_SEQ else 0)
    shared = params["shared"]

    def mamba_body(x, mp):
        h = rmsnorm(x, mp["norm"], cfg.norm_eps)
        if collect_cache:
            y, st = ssm_mod.mamba_apply(mp, h, cfg, return_state=True)
        else:
            y, st = ssm_mod.mamba_apply(mp, h, cfg), None
        return x + y, st

    def group_body(x, gp):
        x, kv = _attn_block(shared, x, cfg, mesh, positions, None, q_chunk,
                            collect_cache)
        x, _ = _ffn_block(shared, x, cfg, False)
        x, sts = scan_or_unroll(cfg, mamba_body, x, gp, every)
        return x, (kv, sts)

    if ng > 0:
        x, (kvs, group_states) = scan_or_unroll(cfg, group_body, x,
                                                params["mamba_groups"], ng)
    else:
        kvs, group_states = None, None
    tail_states = None
    if tail:
        x, tail_states = scan_or_unroll(cfg, mamba_body, x,
                                        params["mamba_tail"], tail)
    return x, 0.0, (kvs, group_states, tail_states)


def _ssm_forward(params, cfg, x, mesh, collect_cache: bool):
    states = []
    for i in range(cfg.n_layers):
        lp = params["layers"][f"l{i:02d}"]
        h = rmsnorm(x, lp["norm"], cfg.norm_eps)
        if _layer_kind(cfg, i) == "slstm":
            y, st = xl.slstm_apply(lp, h, cfg)
        else:
            y, st = xl.mlstm_apply(lp, h, cfg)
        x = x + y
        states.append(st)
    return x, 0.0, states


def lm_hidden(params, cfg, batch, mesh=None, collect_cache: bool = False):
    x = _embed(params, cfg, batch, mesh)
    S = x.shape[1]
    positions = jnp.arange(S)
    pos3 = batch.get("pos_ids") if cfg.family == "vlm" else None
    if cfg.family in ("dense", "moe", "vlm"):
        x, aux, caches = _uniform_forward(params, cfg, x, mesh, positions,
                                          pos3, collect_cache)
    elif cfg.family == "hybrid":
        x, aux, caches = _hybrid_forward(params, cfg, x, mesh, positions,
                                         collect_cache)
    elif cfg.family == "ssm":
        x, aux, caches = _ssm_forward(params, cfg, x, mesh, collect_cache)
    else:
        raise ValueError(cfg.family)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux, caches


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------
def cross_entropy(logits, labels, vocab: int):
    """Stable CE in f32; labels<0 are masked.  logits: (B,S,V)."""
    V = logits.shape[-1]
    lg = logits.astype(jnp.float32)
    if vocab < V:  # mask padded vocab rows
        lg = jnp.where(jnp.arange(V) < vocab, lg, -1e30)
    m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1))
    true_lg = jnp.take_along_axis(
        lg, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - true_lg
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def lm_loss(params, cfg, batch, mesh=None):
    x, aux, _ = lm_hidden(params, cfg, batch, mesh)
    logits = _logits(params, cfg, x, mesh)
    ce = cross_entropy(logits, batch["labels"], cfg.vocab)
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------------
# KV-cache structure
# --------------------------------------------------------------------------
def kv_cache_len(cfg, seq_len: int) -> int:
    if cfg.sliding_window:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def lm_init_cache(cfg, batch: int, max_len: int, dtype=None):
    """Zero-initialized cache pytree for decode."""
    if dtype is None:
        dtype = jnp.dtype(getattr(cfg, "cache_dtype", "bfloat16"))
    K, hd = cfg.n_kv_heads, cfg.head_dim
    T = kv_cache_len(cfg, max_len)
    cur = jnp.zeros((), jnp.int32)
    if cfg.family in ("dense", "moe", "vlm"):
        c = {"k": jnp.zeros((cfg.n_layers, batch, T, K, hd), dtype),
             "v": jnp.zeros((cfg.n_layers, batch, T, K, hd), dtype),
             "cur": cur}
        if cfg.sliding_window:
            c["pos"] = jnp.full((T,), -1, jnp.int32)
        return c
    if cfg.family == "hybrid":
        ng, every, tail = hybrid_layout(cfg)
        h, hs, S = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        c = {"k": jnp.zeros((ng, batch, T, K, hd), dtype),
             "v": jnp.zeros((ng, batch, T, K, hd), dtype),
             "ssm": jnp.zeros((cfg.n_layers, batch, h, hs, S), jnp.float32),
             "conv": jnp.zeros((cfg.n_layers, batch, cfg.conv_width - 1,
                                cfg.d_inner), dtype),
             "cur": cur}
        return c
    if cfg.family == "ssm":
        states = {}
        for i in range(cfg.n_layers):
            if _layer_kind(cfg, i) == "slstm":
                states[f"l{i:02d}"] = xl.slstm_init_state(cfg, batch)
            else:
                states[f"l{i:02d}"] = xl.mlstm_init_state(cfg, batch)
        return {"states": states, "cur": cur}
    raise ValueError(cfg.family)


def cache_logical_axes(cfg, cache) -> Any:
    """Logical-axis tree matching lm_init_cache's structure.

    KV tensors: (L, B, T, K, hd) -> T sharded over 'model' when K isn't
    divisible (sequence-sharded cache), else heads over 'model'.
    """
    def leaf_axes(path, leaf):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        nd = getattr(leaf, "ndim", 0)
        if leaf.ndim == 0:
            return ()
        if name.endswith(("k", "v")) and nd == 5:
            return ("stack", "batch", "kv_seq", "kv_heads", None)
        if "ssm" in name and nd == 5:
            return ("stack", "batch", "inner", None, None)
        if "conv" in name and nd == 4:
            return ("stack", "batch", None, "inner")
        if name.endswith("/C") and nd == 4:      # mLSTM matrix memory
            return ("batch", "heads", None, None)
        if nd >= 2:
            return ("batch",) + (None,) * (nd - 1)
        return (None,) * nd
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf_axes(p, l) for p, l in flat])


# --------------------------------------------------------------------------
# Prefill
# --------------------------------------------------------------------------
def lm_prefill(params, cfg, batch, mesh=None, max_len: Optional[int] = None,
               valid_len=None):
    """Forward over the prompt, returning (last-position logits, cache).

    ``valid_len`` (traced scalar int32) marks the real prompt length when
    ``tokens`` is right-padded up to a bucket size (dense-plane bucketed
    prefill): logits come from position ``valid_len - 1`` and the cache's
    write index is ``valid_len``, so the pad columns are never attended
    by decode (``k_valid = k_pos <= cur``) and get overwritten by the
    first generated tokens.  Right-padding is exact for causal full
    attention — pads sit *after* every real query, so the causal mask
    kills them — but not for recurrent state (ssm/hybrid/audio), the SWA
    ring packing, or capacity-factor MoE (pads consume expert capacity),
    hence the family guard.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    max_len = max_len or S
    T = kv_cache_len(cfg, max_len)
    if valid_len is not None and (cfg.family not in ("dense", "moe", "vlm")
                                  or cfg.sliding_window
                                  or (cfg.family == "moe"
                                      and cfg.moe_routing != "dropless")):
        raise ValueError(
            f"bucketed prefill (valid_len) requires a causal-KV family "
            f"without a sliding window and pad-invariant routing, got "
            f"family={cfg.family!r} window={cfg.sliding_window}")
    x, aux, caches = lm_hidden(params, cfg, batch, mesh, collect_cache=True)
    if valid_len is None:
        x_last = x[:, -1:]
    else:
        last = jnp.clip(valid_len.astype(jnp.int32) - 1, 0, S - 1)
        x_last = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    logits = _logits(params, cfg, x_last, mesh)[:, 0]

    def pack_kv(kv_stacked):
        # (L,B,S,K,hd) -> sliced/padded to T, SWA keeps the last window
        k = kv_stacked
        if k.shape[2] > T:
            k = k[:, :, k.shape[2] - T:]
        elif k.shape[2] < T:
            k = jnp.pad(k, ((0, 0), (0, 0), (0, T - k.shape[2]),
                            (0, 0), (0, 0)))
        return k

    cur = jnp.asarray(S, jnp.int32) if valid_len is None \
        else valid_len.astype(jnp.int32)
    if cfg.family in ("dense", "moe", "vlm"):
        ks, vs = caches
        cache = {"k": pack_kv(ks), "v": pack_kv(vs), "cur": cur}
        if cfg.sliding_window:
            # positions held in the (ring) cache after prefill
            W = T
            pos = jnp.arange(S - min(S, W), S)
            pos = jnp.pad(pos, (0, W - pos.shape[0]), constant_values=-1)
            # ring invariant: slot i holds position p with p % W == i
            ring = jnp.full((W,), -1, jnp.int32)
            valid = pos >= 0
            ring = ring.at[jnp.where(valid, pos % W, W)].set(
                jnp.where(valid, pos, -1), mode="drop")
            # reorder k/v into ring slots
            src = jnp.where(ring >= 0, jnp.clip(ring - (S - min(S, W)), 0), 0)
            cache["k"] = cache["k"][:, :, src]
            cache["v"] = cache["v"][:, :, src]
            cache["pos"] = ring
        return logits, cache
    if cfg.family == "hybrid":
        (kvs, group_states, tail_states) = caches
        ng, every, tail = hybrid_layout(cfg)
        if ng > 0:
            ks, vs = kvs
            ssm_g = group_states["ssm"].reshape(
                ng * every, *group_states["ssm"].shape[2:])
            conv_g = group_states["conv"].reshape(
                ng * every, *group_states["conv"].shape[2:])
        else:
            K, hd = cfg.n_kv_heads, cfg.head_dim
            ks = jnp.zeros((0, B, S, K, hd), jnp.bfloat16)
            vs = ks
            ssm_g = jnp.zeros((0,) + tail_states["ssm"].shape[1:],
                              tail_states["ssm"].dtype)
            conv_g = jnp.zeros((0,) + tail_states["conv"].shape[1:],
                               tail_states["conv"].dtype)
        if tail:
            ssm_g = jnp.concatenate([ssm_g, tail_states["ssm"]], 0)
            conv_g = jnp.concatenate([conv_g, tail_states["conv"]], 0)
        cache = {"k": pack_kv(ks), "v": pack_kv(vs),
                 "ssm": ssm_g, "conv": conv_g, "cur": cur}
        return logits, cache
    if cfg.family == "ssm":
        states = {f"l{i:02d}": st for i, st in enumerate(caches)}
        return logits, {"states": states, "cur": cur}
    raise ValueError(cfg.family)


# --------------------------------------------------------------------------
# Paged KV data plane (block-table-indexed pool; uniform-block families)
# --------------------------------------------------------------------------
def lm_supports_paged(cfg) -> bool:
    """Families whose whole cache is a uniform (L, B, T, K, hd) KV stack."""
    return cfg.family in ("dense", "moe", "vlm")


def paged_blocks(max_len: int, block_tokens: int) -> int:
    """Blocks needed to cover ``max_len`` tokens."""
    return -(-max_len // block_tokens)


def lm_init_paged_cache(cfg, batch: int, max_len: int,
                        block_tokens: int = 16, dtype=None, frames=None):
    """Pooled KV arena: (L, P, K, bt, hd) pages shared by all slots through
    a block table.  Each (page, kv-head) block is a (bt, hd) tile — the two
    minor dimensions the TPU kernels' BlockSpecs address whole.  P = batch * max_blocks real pages + one trash page
    (index P-1) that soaks up writes from inactive slots.  The block table
    and per-slot lengths live host-side (runtime.scheduler.KVBlockPager)
    and ride into each decode step as arguments — the arena is the only
    device-carried decode state.

    ``frames`` overrides the real-page count: a tiered engine sizes its
    HBM-resident near arena below logical capacity (and a far arena with
    the rest) instead of the default one-arena batch * max_blocks."""
    if not lm_supports_paged(cfg):
        raise ValueError(f"family {cfg.family} has no paged-KV path")
    if dtype is None:
        dtype = jnp.dtype(getattr(cfg, "cache_dtype", "bfloat16"))
    K, hd = cfg.n_kv_heads, cfg.head_dim
    real = frames if frames is not None \
        else batch * paged_blocks(max_len, block_tokens)
    P = real + 1
    shape = (cfg.n_layers, P, K, block_tokens, hd)
    return {"kp": jnp.zeros(shape, dtype), "vp": jnp.zeros(shape, dtype)}


def lm_kv_migrate(near, far, dem_src, dem_dst, pro_src, pro_dst):
    """One fused near<->far migration event over two KV arenas.

    near/far: {"kp", "vp"} arenas (L, P_near/P_far, K, bt, hd);
    dem_src/dem_dst: (D,) int32 — demotions copy near frame dem_src[i]
    into far frame dem_dst[i]; pro_src/pro_dst: (U,) int32 — promotions
    copy far frame pro_src[i] into near frame pro_dst[i].  Pad ragged
    event sizes with trash->trash self-copies (trash frames are
    write-only, so junk there is harmless).

    Gather-first: promotion sources are read out of the far arena
    *before* demotions scatter into it, so a far frame freed by a
    promotion in this same event may be reused as a demotion destination
    (the swap case when both tiers are full).  Jit with
    ``donate_argnums=(0, 1)`` — both arenas update in place.
    """
    pk = far["kp"][:, pro_src]
    pv = far["vp"][:, pro_src]
    fkp = far["kp"].at[:, dem_dst].set(near["kp"][:, dem_src])
    fvp = far["vp"].at[:, dem_dst].set(near["vp"][:, dem_src])
    nkp = near["kp"].at[:, pro_dst].set(pk)
    nvp = near["vp"].at[:, pro_dst].set(pv)
    return {"kp": nkp, "vp": nvp}, {"kp": fkp, "vp": fvp}


def lm_paged_prefill_write(cfg, pages, k_rows, v_rows, block_ids,
                           prompt_len: int, skip_tokens: int = 0):
    """Scatter an admission group's prefilled KV into its pool pages.

    k_rows/v_rows: (L, G, T, K, hd) — G admitted batch rows of the prefill
    cache built with ``max_len=None`` (T = prompt_len, or the ring-packed
    window for sliding-window configs); block_ids: (G * nb,) int32 page
    ids, row-major (slot 0's nb blocks, then slot 1's, ...), each run in
    position order.  One fused scatter installs the whole group and only
    the admitted slots' pages are touched — the per-slot replacement for
    the full-cache admission splice.

    ``skip_tokens`` (static, block-aligned) drops the leading positions
    from the scatter: a prefix-cache hit maps those positions to pages
    shared with other requests, and shared pages are immutable — a
    re-write of bit-wise "the same" KV is not safe because XLA's low bits
    vary with the batch shape of the computing call, which would corrupt
    co-resident readers.  ``block_ids`` then covers only the tail blocks.
    """
    L, G, T, K, hd = k_rows.shape
    bt = pages["kp"].shape[3]
    nb = block_ids.shape[0] // G
    S = prompt_len
    W = cfg.sliding_window
    if skip_tokens:
        if W and S > T:
            raise ValueError("skip_tokens is incompatible with ring-packed "
                             "sliding-window prefill rows")
        if skip_tokens % bt or not 0 < skip_tokens < S:
            raise ValueError(f"skip_tokens must be a block-aligned count "
                             f"inside the prompt, got {skip_tokens}/{S}")
        k_rows = k_rows[:, :, skip_tokens:]
        v_rows = v_rows[:, :, skip_tokens:]
        S = S - skip_tokens
        T = T - skip_tokens
    if W and S > T:
        # prefill ring-packed the last T=min(window, S) positions: slot i
        # holds position p with p % T == i.  Unpermute to position order
        # and place at absolute positions [S-T, S); older positions stay
        # zero — the window mask keeps them dead.
        src = jnp.arange(S - T, S) % T
        tail_k, tail_v = k_rows[:, :, src], v_rows[:, :, src]
        k_rows = jnp.zeros((L, G, S, K, hd),
                           k_rows.dtype).at[:, :, S - T:].set(tail_k)
        v_rows = jnp.zeros((L, G, S, K, hd),
                           v_rows.dtype).at[:, :, S - T:].set(tail_v)
    pad = ((0, 0), (0, 0), (0, nb * bt - S), (0, 0), (0, 0))
    k_rows = jnp.pad(k_rows, pad).reshape(L, G * nb, bt, K, hd) \
        .swapaxes(2, 3)
    v_rows = jnp.pad(v_rows, pad).reshape(L, G * nb, bt, K, hd) \
        .swapaxes(2, 3)
    kp = pages["kp"].at[:, block_ids].set(k_rows.astype(pages["kp"].dtype))
    vp = pages["vp"].at[:, block_ids].set(v_rows.astype(pages["vp"].dtype))
    return {"kp": kp, "vp": vp}


def lm_paged_prefill_chunk(params, cfg, pages, tokens, block_tables,
                           ctx_lens, valid_lens, mesh=None):
    """Advance chunked prefill by one (bucket-padded) chunk per slot.

    tokens: (B, C) int32 — slot b's next ``valid_lens[b]`` prompt tokens,
    sitting at absolute positions [ctx_lens[b], ctx_lens[b] + valid);
    columns past ``valid`` are padding: they compute (finite, self-attended)
    but their KV routes to the trash page and their activations are never
    read.  pages: {"kp", "vp"} (L, P, K, bt, hd); block_tables: (B, nb)
    int32 — must cover ``ctx_lens + valid_lens`` tokens for slots in this
    chunk step; rows of slots *not* prefilling this step are < 0 (their
    writes all land on the trash page).  Returns (logits (B, V) at each
    slot's last valid position, pages with the chunk's KV scattered in —
    jit with ``donate_argnums`` on ``pages`` so the arena never copies).

    Exactness: at matching dtypes this reproduces one-shot prefill — RoPE
    is applied at absolute positions, earlier chunks' k/v are re-read from
    the pool in the pool dtype (exactly what decode attends over), and the
    in-chunk causal/window mask matches ``gqa_attention``'s.
    """
    if not lm_supports_paged(cfg):
        raise ValueError(f"family {cfg.family} has no paged-KV path")
    if cfg.family == "moe" and cfg.moe_routing != "dropless":
        # pad columns and chunk boundaries would shift capacity-factor
        # expert drops; only dropless routing is chunk/pad-invariant
        raise ValueError("chunked prefill for moe requires "
                         "cfg.moe_routing='dropless'")
    B, C = tokens.shape
    x = embed_lookup(params["emb"], tokens, mesh)
    ctx_lens = ctx_lens.astype(jnp.int32)
    valid_lens = valid_lens.astype(jnp.int32)
    positions = ctx_lens[:, None] + jnp.arange(C)[None, :]
    pos3 = (jnp.broadcast_to(positions[..., None], (B, C, 3))
            if cfg.m_rope_sections else None)
    use_moe = cfg.family == "moe"

    def body(x, inp):
        bp, kp_l, vp_l = inp
        h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
        attn_out, (kn, vn) = paged_prefill_attn_apply(
            bp["attn"], h, cfg, kp_l, vp_l, block_tables, ctx_lens,
            pos3=pos3, mesh=mesh)
        x = x + attn_out
        x, _ = _ffn_block(bp, x, cfg, use_moe, mesh)
        return x, (kn, vn)

    x, (kns, vns) = scan_or_unroll(
        cfg, body, x, (params["blocks"], pages["kp"], pages["vp"]),
        cfg.n_layers)

    # one fused scatter of all layers' chunk KV into the donated arena;
    # padding columns (and slots whose table row is masked) -> trash page
    P, bt = pages["kp"].shape[1], pages["kp"].shape[3]
    nb = block_tables.shape[1]
    blk = jnp.clip(positions // bt, 0, nb - 1)
    page_w = jnp.take_along_axis(block_tables, blk, axis=1)  # (B, C)
    valid = jnp.arange(C)[None, :] < valid_lens[:, None]
    page_w = jnp.where(valid & (page_w >= 0), page_w, P - 1)
    off = positions % bt
    # the split (page, offset) index puts its (B, C) dims first
    kp = pages["kp"].at[:, page_w, :, off].set(jnp.moveaxis(kns, 0, 2))
    vp = pages["vp"].at[:, page_w, :, off].set(jnp.moveaxis(vns, 0, 2))

    # logits at each slot's last valid position (the first generated token
    # when this chunk completes the prompt; ignored otherwise)
    last = jnp.clip(valid_lens - 1, 0, C - 1)
    x_last = x[jnp.arange(B), last][:, None]                 # (B, 1, D)
    x_last = rmsnorm(x_last, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, x_last, mesh)[:, 0]
    return logits, {"kp": kp, "vp": vp}


def lm_paged_decode_step(params, cfg, pages, tokens, block_tables, seq_lens,
                         mesh=None):
    """One decode step over the paged KV pool; per-slot ragged lengths.

    tokens: (B, 1) int32; pages: {"kp", "vp"} (L, P, K, bt, hd);
    block_tables: (B, nb) int32 (< 0 = unallocated; nb may be a bucket of
    the full table — it only needs to cover max(seq_lens) + 1 tokens);
    seq_lens: (B,) int32 tokens resident per slot (the new token lands at
    position seq_lens).  Returns (logits (B, V), pages with every layer's
    new KV scattered in by one fused in-place update per arena — jit this
    with ``donate_argnums`` on ``pages`` so the arena never copies).
    """
    if not lm_supports_paged(cfg):
        raise ValueError(f"family {cfg.family} has no paged-KV path")
    B = tokens.shape[0]
    x = embed_lookup(params["emb"], tokens, mesh)
    seq_lens = seq_lens.astype(jnp.int32)
    pos3 = (jnp.broadcast_to(seq_lens[:, None, None], (B, 1, 3))
            if cfg.m_rope_sections else None)
    use_moe = cfg.family == "moe"

    def body(x, inp):
        bp, kp_l, vp_l = inp
        h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
        attn_out, (kn, vn) = paged_attn_apply(
            bp["attn"], h, cfg, kp_l, vp_l, block_tables, seq_lens,
            pos3=pos3, mesh=mesh)
        x = x + attn_out
        x, _ = _ffn_block(bp, x, cfg, use_moe, mesh)
        return x, (kn, vn)

    x, (kns, vns) = scan_or_unroll(
        cfg, body, x, (params["blocks"], pages["kp"], pages["vp"]),
        cfg.n_layers)

    # one fused scatter of all layers' new KV into the donated arena
    P, bt = pages["kp"].shape[1], pages["kp"].shape[3]
    nb = block_tables.shape[1]
    blk = jnp.clip(seq_lens // bt, 0, nb - 1)
    page_w = block_tables[jnp.arange(B), blk]
    page_w = jnp.where(page_w >= 0, page_w, P - 1)   # inactive -> trash page
    off = seq_lens % bt
    # the split (page, offset) index puts its (B,) dim first
    kp = pages["kp"].at[:, page_w, :, off].set(
        jnp.moveaxis(kns[:, :, 0], 0, 1))
    vp = pages["vp"].at[:, page_w, :, off].set(
        jnp.moveaxis(vns[:, :, 0], 0, 1))

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, x, mesh)[:, 0]
    return logits, {"kp": kp, "vp": vp}


# --------------------------------------------------------------------------
# Decode
# --------------------------------------------------------------------------
def _decode_attn(bp, x, cfg, ck, cv, cur, write_idx, k_pos, k_valid, pos3):
    """One decode attention with cache update.  x: (B,1,D)."""
    B = x.shape[0]
    h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
    qpos = jnp.broadcast_to(cur[None, None], (B, 1))
    knew, vnew = compute_kv(bp["attn"], h, cfg,
                            positions=qpos if not cfg.m_rope_sections else
                            jnp.broadcast_to(cur[None, None, None], (B, 1, 3)))
    ck = jax.lax.dynamic_update_slice_in_dim(ck, knew.astype(ck.dtype),
                                             write_idx, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, vnew.astype(cv.dtype),
                                             write_idx, axis=1)
    attn_out, _ = attn_apply(
        bp["attn"], h, cfg, positions=qpos, pos3=pos3, kv=(ck, cv),
        k_pos=k_pos, k_valid=k_valid)
    return x + attn_out, ck, cv


def lm_decode_step(params, cfg, cache, tokens, mesh=None):
    """tokens: (B,1) int32 -> (logits (B,V), updated cache)."""
    B = tokens.shape[0]
    cur = cache["cur"]
    x = embed_lookup(params["emb"], tokens, mesh)
    pos3 = (jnp.broadcast_to(cur[None, None, None], (B, 1, 3))
            if cfg.m_rope_sections else None)

    if cfg.family in ("dense", "moe", "vlm"):
        T = cache["k"].shape[2]
        if cfg.sliding_window and "pos" in cache:
            write_idx = jnp.mod(cur, T)
            pos_arr = cache["pos"].at[write_idx].set(cur)
            k_pos = jnp.broadcast_to(pos_arr[None], (B, T))
            k_valid = jnp.broadcast_to((pos_arr >= 0)[None], (B, T))
        else:
            write_idx = cur
            pos_arr = None
            k_pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
            k_valid = k_pos <= cur

        use_moe = cfg.family == "moe"

        def body(x, inp):
            bp, ck, cv = inp
            x, ck, cv = _decode_attn(bp, x, cfg, ck, cv, cur, write_idx,
                                     k_pos, k_valid, pos3)
            x, _ = _ffn_block(bp, x, cfg, use_moe, mesh)
            return x, (ck, cv)

        x, (nk, nv) = scan_or_unroll(
            cfg, body, x, (params["blocks"], cache["k"], cache["v"]),
            cfg.n_layers)
        new_cache = {"k": nk, "v": nv, "cur": cur + 1}
        if pos_arr is not None:
            new_cache["pos"] = pos_arr

    elif cfg.family == "hybrid":
        ng, every, tail = hybrid_layout(cfg)
        T = cache["k"].shape[2]
        k_pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        k_valid = k_pos <= cur
        shared = params["shared"]
        ssm_g = cache["ssm"][:ng * every].reshape(ng, every,
                                                  *cache["ssm"].shape[1:])
        conv_g = cache["conv"][:ng * every].reshape(ng, every,
                                                    *cache["conv"].shape[1:])

        def mamba_body(x, inp):
            mp, st = inp
            h = rmsnorm(x, mp["norm"], cfg.norm_eps)
            y, st2 = ssm_mod.mamba_decode_step(mp, h, st, cfg)
            return x + y, st2

        def group_body(x, inp):
            gp, ck, cv, sts = inp
            x, ck, cv = _decode_attn(shared, x, cfg, ck, cv, cur, cur,
                                     k_pos, k_valid, None)
            x, _ = _ffn_block(shared, x, cfg, False)
            x, sts2 = scan_or_unroll(cfg, mamba_body, x, (gp, sts), every)
            return x, (ck, cv, sts2)

        if ng > 0:
            x, (nk, nv, gsts) = scan_or_unroll(
                cfg, group_body, x,
                (params["mamba_groups"], cache["k"], cache["v"],
                 {"ssm": ssm_g, "conv": conv_g}), ng)
            ssm_new = gsts["ssm"].reshape(ng * every, *gsts["ssm"].shape[2:])
            conv_new = gsts["conv"].reshape(ng * every,
                                            *gsts["conv"].shape[2:])
        else:
            nk, nv = cache["k"], cache["v"]
            ssm_new = cache["ssm"][:0]
            conv_new = cache["conv"][:0]
        if tail:
            tail_sts = {"ssm": cache["ssm"][ng * every:],
                        "conv": cache["conv"][ng * every:]}
            x, tsts = scan_or_unroll(cfg, mamba_body, x,
                                     (params["mamba_tail"], tail_sts), tail)
            ssm_new = jnp.concatenate([ssm_new, tsts["ssm"]], 0)
            conv_new = jnp.concatenate([conv_new, tsts["conv"]], 0)
        new_cache = {"k": nk, "v": nv, "ssm": ssm_new, "conv": conv_new,
                     "cur": cur + 1}

    elif cfg.family == "ssm":
        new_states = {}
        for i in range(cfg.n_layers):
            key = f"l{i:02d}"
            lp = params["layers"][key]
            st = cache["states"][key]
            h = rmsnorm(x, lp["norm"], cfg.norm_eps)
            if _layer_kind(cfg, i) == "slstm":
                y, st2 = xl.slstm_decode_step(lp, h, st, cfg)
            else:
                y, st2 = xl.mlstm_decode_step(lp, h, st, cfg)
            x = x + y
            new_states[key] = st2
        new_cache = {"states": new_states, "cur": cur + 1}
    else:
        raise ValueError(cfg.family)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, x, mesh)[:, 0]
    return logits, new_cache
