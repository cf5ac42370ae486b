"""Operations and bytes that the algorithm needs, whatever implements it.

Counts are per token or per call from the model's sizes: a kernel or a
routing scheme that computes more (dead table columns, padding rows,
every expert for every token) gets no credit for it, so a roofline or MFU
share from these counts cannot pass 100% unless the time is wrong.

* Model FLOPs of a token: 2 x the parameters that the token touches (the
  attention projections, the dense FFN or the router and its ``top_k``
  routed experts; the LM head only where a token is predicted) plus
  attention at the token's context length, 4 x H x hd per key.
* Decode attention, per layer and slot: the slot's resident K and V
  (seq_len x K x hd x 2 x 2 bytes), the new token's k and v, q and out;
  4 x H x hd x (seq_len + 1) FLOPs.
* Prefill attention, per layer, for prompt positions ``[p0, p1)``: the
  valid rows against their causal keys, 4 x H x hd x sum(i + 1) FLOPs;
  K and V of positions ``[0, p1)`` read once, q and out of the rows.
"""
from __future__ import annotations

from chipbench.dims import Dims

KV_BYTES = 2            # bfloat16 K/V pages (the configurations' cache type)
ACT_BYTES = 2           # bfloat16 q and attention output


def attn_params(m: Dims) -> int:
    return 2 * m.d * m.q_dim + 2 * m.d * m.kv_dim


def ffn_params_touched(m: Dims) -> int:
    if m.moe:
        return m.d * m.experts + m.top_k * 3 * m.d * m.expert_ffn
    return 3 * m.d * m.ffn


def head_params(m: Dims) -> int:
    return m.d * m.vocab


def layer_flops(m: Dims) -> int:
    """Matmul FLOPs of one token through one layer, attention excluded."""
    return 2 * (attn_params(m) + ffn_params_touched(m))


def attn_flops(m: Dims, keys: int) -> int:
    """One query row against ``keys`` keys, one layer."""
    return 4 * m.heads * m.head_dim * keys


def causal_pairs(p0: int, p1: int) -> int:
    """Query positions ``[p0, p1)``, each with its keys ``0 .. i``."""
    return (p1 * (p1 + 1) - p0 * (p0 + 1)) // 2


def token_flops(m: Dims, pos: int, predicts: bool) -> int:
    """Model FLOPs of the token at position ``pos`` (it attends to
    ``pos + 1`` keys); ``predicts``: its LM-head row is needed."""
    f = m.layers * (layer_flops(m) + attn_flops(m, pos + 1))
    return f + (2 * head_params(m) if predicts else 0)


def prefill_flops(m: Dims, p0: int, p1: int, prompt_len: int) -> int:
    """Model FLOPs of prefilling prompt positions ``[p0, p1)``; the LM
    head runs for the prompt's last position only."""
    f = m.layers * ((p1 - p0) * layer_flops(m)
                    + 4 * m.heads * m.head_dim * causal_pairs(p0, p1))
    return f + (2 * head_params(m) if p1 == prompt_len > p0 else 0)


def decode_attn(m: Dims, seq_len: int):
    """(FLOPs, bytes) of the decode kernel for one slot holding
    ``seq_len`` resident tokens, all layers."""
    kv = seq_len * m.kv_dim * 2 * KV_BYTES
    io = 2 * m.q_dim * ACT_BYTES + 2 * m.kv_dim * KV_BYTES
    return (m.layers * attn_flops(m, seq_len + 1), m.layers * (kv + io))


def prefill_attn(m: Dims, p0: int, p1: int):
    """(FLOPs, bytes) of prefill attention for positions ``[p0, p1)``,
    all layers."""
    flops = 4 * m.heads * m.head_dim * causal_pairs(p0, p1)
    kv = p1 * m.kv_dim * 2 * KV_BYTES
    io = (p1 - p0) * 2 * m.q_dim * ACT_BYTES
    return (m.layers * flops, m.layers * (kv + io))


def roofline_s(flops: float, nbytes: float, peak: dict):
    """(least seconds, the bound that sets it)."""
    tc = flops / float(peak["bf16_flops_per_s"])
    tm = nbytes / float(peak["hbm_bytes_per_s"])
    return (tc, "compute") if tc >= tm else (tm, "memory")
