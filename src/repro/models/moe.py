"""Top-k MoE with hierarchical (group-local) sort-based dispatch.

Two memory/communication hazards shape this design (measured in the dry-run,
see EXPERIMENTS.md §Perf):

1. the classic one-hot dispatch einsum is O(T·E·C) — hundreds of GB at the
   assigned global batches;
2. a *global* sort-based dispatch keeps gather/scatter indices global, and
   the backward scatter-add materializes replicated (T, D) f32 temps under
   GSPMD (+17 GB/device on qwen3-235B).

So tokens are first reshaped into G dispatch groups aligned with the data
axis (G = pod·data); argsort/bincount/gather/scatter are then *group-local*
(vmapped over G), which GSPMD shards cleanly along the group dim — no
cross-shard index traffic, backward stays shard-local.  Per-group capacity
C_loc = ceil(k·T_loc/E · cf) (local drops, MaxText-style) under the
``capacity`` routing mode; ``cfg.moe_routing = "dropless"`` sets
C_loc = T_loc instead (top_k indices are distinct per token, so no
expert can ever receive more), so no assignment can ever be dropped and the
layer is a pure per-token function — the serving plane runs dropless so
chunked prefill and batched decode reproduce the sequential reference
token-for-token (capacity mode stays the training default).  The expert FFN
is a grouped matmul (``kernels.moe_gmm`` on TPU; einsum fallback here) with
experts sharded over 'model' (EP) when divisible — granite's 40 experts fall
back to sharding expert d_ff (adaptive rule).

The gather/scatter access pattern is exactly the paper's RAO SCATTER/GATHER
CircusTent patterns — fine-grained irregular updates, the access class
Cohet's coherent fabric accelerates (DESIGN.md §2).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import ParamDef


def moe_schema(cfg) -> Dict[str, ParamDef]:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    if cfg.infer_weight_layout:
        # serving layout: shard d_ff over 'data' instead of FSDP on d_model
        # -> the decode path reads expert weights gather-free (§Perf it.10)
        return {
            "router": ParamDef((D, E), (None, "experts"), scale=0.02),
            "wg": ParamDef((E, D, F), ("experts", None, "expert_ffn_d")),
            "wu": ParamDef((E, D, F), ("experts", None, "expert_ffn_d")),
            "wd": ParamDef((E, F, D), ("experts", "expert_ffn_d", None)),
        }
    return {
        "router": ParamDef((D, E), ("embed", "experts"), scale=0.02),
        "wg": ParamDef((E, D, F), ("experts", "embed", "expert_ffn")),
        "wu": ParamDef((E, D, F), ("experts", "embed", "expert_ffn")),
        "wd": ParamDef((E, F, D), ("experts", "expert_ffn", "embed")),
    }


def _capacity(cfg, n_tokens: int) -> int:
    """Per-group per-expert capacity.

    ``dropless``: C = Tl — top_k indices are distinct per token, so at
    most Tl of a group's assignments can land on any one expert and
    rank-in-expert tops out at Tl - 1 < C; ``slot < C`` always holds and
    routing is a pure per-token function (no drop can depend on
    co-resident tokens).

    ``capacity``: C = ceil(k*Tl/E * cf) with a top_k floor, clamped to
    Tl last — at most Tl tokens can ever rank into one expert, so any
    C > Tl is pure waste (the floor applied after the clamp used to
    yield C > Tl whenever top_k > Tl, e.g. tiny decode batches).
    """
    if cfg.moe_routing == "dropless":
        return n_tokens
    c = int(np.ceil(cfg.top_k * n_tokens / cfg.n_experts *
                    cfg.capacity_factor))
    return min(max(cfg.top_k, c), n_tokens)


def _n_groups(cfg, T: int, mesh) -> int:
    if mesh is None:
        return 1
    g = 1
    for ax in ("pod", "data"):
        g *= mesh.shape.get(ax, 1)
    return g if T % g == 0 else 1


@jax.named_scope("moe_ffn")
def moe_apply(p, x, cfg, return_aux: bool = False, mesh=None,
              n_groups: int = 0):
    """x: (B, S, D) -> (B, S, D) [, aux losses dict].

    The whole layer (router, dispatch, expert matmuls, combine) runs under
    the name scope ``moe_ffn``, so its ops carry it in their HLO metadata
    and a device trace can add up the expert FFN's time.

    ``cfg.moe_routing == "dropless"`` makes the layer a pure per-token
    function (capacity can never bind): the output for token t is exactly
    sum_k gate_k * FFN_{e_k}(x_t), invariant to token order, group count,
    chunk splits and pad rows.  ``n_groups`` overrides the mesh-derived
    dispatch group count (tests; must divide B*S).
    """
    from repro.parallel.sharding import constraint

    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    G = n_groups or _n_groups(cfg, T, mesh)
    assert T % G == 0, (T, G)
    Tl = T // G
    C = _capacity(cfg, Tl)

    infer = cfg.infer_weight_layout

    def shard(t, logical):
        if infer:
            # serving layout: expert buffers replicated over 'data' (tiny at
            # decode batch sizes); weights keep their gather-free sharding
            logical = tuple(("experts" if n == "experts" else
                             "expert_ffn_d" if n == "expert_ffn" else None)
                            for n in logical)
        return constraint(t, logical, mesh) if mesh is not None else t

    xf = x.reshape(G, Tl, D)
    if mesh is not None:
        xf = constraint(xf, ("batch", None, None) if infer
                        else ("batch", None, "act_embed"), mesh)

    logits = jnp.einsum("gtd,de->gte", xf, p["router"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (G,Tl,E) f32
    gates, eidx = jax.lax.top_k(probs, K)                      # (G,Tl,K)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)

    # ---- group-local sorted dispatch ----
    flat_e = eidx.reshape(G, Tl * K)
    order = jnp.argsort(flat_e, axis=-1, stable=True)          # (G,TlK)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)

    def _counts(fe):
        return jnp.zeros((E,), jnp.int32).at[fe].add(1)
    counts = jax.vmap(_counts)(flat_e)                         # (G,E)
    offsets = jnp.cumsum(counts, axis=-1) - counts             # (G,E)
    off_sorted = jnp.take_along_axis(offsets, sorted_e, axis=-1)
    slot = jnp.arange(Tl * K)[None] - off_sorted               # rank in expert
    keep = slot < C
    src_tok = order // K                                       # (G,TlK)
    dest = sorted_e * C + slot                                 # (G,TlK)

    def _table(dest_g, keep_g, src_g):
        return jnp.full((E * C,), Tl, jnp.int32).at[
            jnp.where(keep_g, dest_g, E * C)].set(
                src_g.astype(jnp.int32), mode="drop")
    table = jax.vmap(_table)(dest, keep, src_tok)              # (G,E*C)

    x_pad = jnp.concatenate([xf, jnp.zeros((G, 1, D), xf.dtype)], 1)
    xe = jnp.take_along_axis(
        x_pad, table[:, :, None].astype(jnp.int32), axis=1)    # (G,E*C,D)
    xe = shard(xe.reshape(G, E, C, D),
               ("batch", "experts", None, "act_embed"))

    # ---- grouped FFN (einsum fallback of kernels.moe_gmm) ----
    g_ = jnp.einsum("gecd,edf->gecf", xe, p["wg"])
    u_ = jnp.einsum("gecd,edf->gecf", xe, p["wu"])
    h = jax.nn.silu(g_.astype(jnp.float32)).astype(x.dtype) * u_
    h = shard(h, ("batch", "experts", None, "expert_ffn"))
    ye = jnp.einsum("gecf,efd->gecd", h, p["wd"])
    ye = shard(ye, ("batch", "experts", None, "act_embed"))
    ye = ye.reshape(G, E * C, D)

    # ---- combine: group-local scatter-add with gates ----
    gate_flat = jnp.take_along_axis(gates.reshape(G, Tl * K), order, axis=-1)

    def _gate_rows(dest_g, keep_g, gf):
        return jnp.zeros((E * C,), jnp.float32).at[
            jnp.where(keep_g, dest_g, E * C)].set(gf, mode="drop")
    gate_rows = jax.vmap(_gate_rows)(dest, keep, gate_flat)    # (G,E*C)

    def _combine(ye_g, tok_g, gr_g):
        contrib = ye_g * gr_g[:, None].astype(ye_g.dtype)
        return jnp.zeros((Tl + 1, D), ye_g.dtype).at[tok_g].add(
            contrib, mode="drop")[:Tl]
    y = jax.vmap(_combine)(ye, table, gate_rows)               # (G,Tl,D)
    y = shard(y, ("batch", None, "act_embed"))

    out = y.reshape(B, S, D)
    if not return_aux:
        return out
    me = probs.mean((0, 1))                                    # (E,)
    ce = (counts.sum(0) / jnp.maximum(1, T * K)).astype(jnp.float32)
    aux = {"load_balance": E * jnp.sum(me * ce),
           "router_z": jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)}
    return out, aux
