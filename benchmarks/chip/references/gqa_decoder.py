"""Plain reference of a decoder-only language model with grouped-query
attention: the forward pass of Mistral-Nemo (SwiGLU FFN) and of
Granite-3.0-MoE (top-k routed SwiGLU experts), in float32 ``jax.numpy``
at ``Precision.HIGHEST``, one sequence and one layer at a time, with no
kernel, cache or batching.  It imports nothing of the program under test.

Per layer, for a sequence ``x`` of shape ``(S, D)`` at positions
``0 .. S-1``::

    h  = rmsnorm(x) * (1 + attn_norm)
    q, k, v = h @ wq, h @ wk, h @ wv          # (S, H, hd), (S, K, hd) x2
    q, k = rope(q), rope(k)                   # rotate-half, theta^(-2i/hd)
    a  = softmax(q k^T / sqrt(hd) + causal) v # query head j reads kv head
                                              # j // (H / K)
    x  = x + a @ wo
    h  = rmsnorm(x) * (1 + mlp_norm)
    x  = x + (silu(h @ w_gate) * (h @ w_up)) @ w_down            # dense
    x  = x + sum_e g_e(h) (silu(h @ e_gate) * (h @ e_up)) @ e_down  # MoE

where ``g(h)`` is the softmax over all experts of ``h @ router``, kept
on the ``top_k`` largest and renormalised to sum to 1 (the same as a
softmax over the top-k logits, as Granite has it).  The last layer's
output goes through ``rmsnorm * (1 + final_norm)`` and the LM head
(``head``, or the embedding's transpose when tied).

Departures from the published models, which the program shares: Granite's
scalar multipliers (embedding 12, attention 1/64, residual 0.22, logits
1/6) are 1, and attention is scaled by ``1/sqrt(hd)``; the configuration
files list these keys under ``reduced``.

``quant=True`` is the control: every matrix product takes its two inputs
rounded to float8 e4m3, each scaled by its absolute maximum along the
contracted axis (per token for activations, per output column for
weights), the step below the bfloat16 that the configurations state.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.dims import Dims

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
E4M3_MAX = 448.0
ROW_BLOCK = 256          # LM-head rows per call


def q8(x, axis):
    """Round ``x`` to float8 e4m3 under an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(spec: str, a, b, quant: bool, a_axis, b_axis):
    if quant:
        a, b = q8(a, a_axis), q8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HI, preferred_element_type=F32)


def rmsnorm(x, delta, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + delta.astype(F32))


def rope(x, theta: float):
    """x: (S, n, hd) at positions 0 .. S-1."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(m: Dims, w, h, quant):
    S = h.shape[0]
    H, K, hd = m.heads, m.kv_heads, m.head_dim
    q = mm("sd,dq->sq", h, w["wq"], quant, -1, 0).reshape(S, H, hd)
    k = mm("sd,dq->sq", h, w["wk"], quant, -1, 0).reshape(S, K, hd)
    v = mm("sd,dq->sq", h, w["wv"], quant, -1, 0).reshape(S, K, hd)
    q, k = rope(q, m.rope_theta), rope(k, m.rope_theta)
    kv_of = np.arange(H) // (H // K)
    k, v = k[:, kv_of], v[:, kv_of]                    # (S, H, hd)
    sc = mm("shd,thd->hst", q, k, quant, -1, -1) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    a = mm("hst,thd->shd", p, v, quant, -1, 0).reshape(S, H * hd)
    return mm("sq,qd->sd", a, w["wo"], quant, -1, 0)


def _ffn(m: Dims, w, h, quant):
    if not m.moe:
        g = mm("sd,df->sf", h, w["w_gate"], quant, -1, 0)
        u = mm("sd,df->sf", h, w["w_up"], quant, -1, 0)
        return mm("sf,fd->sd", jax.nn.silu(g) * u, w["w_down"], quant, -1, 0)
    logits = mm("sd,de->se", h, w["router"], quant, -1, 0)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, m.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)          # (S, E)
    g = mm("sd,edf->sef", h, w["e_gate"], quant, -1, 1)
    u = mm("sd,edf->sef", h, w["e_up"], quant, -1, 1)
    y = mm("sef,efd->sed", jax.nn.silu(g) * u, w["e_down"], quant, -1, 1)
    return jnp.einsum("se,sed->sd", gate, y, precision=HI)


LAYER_LEAVES = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo",
                "w_gate", "w_up", "w_down",
                "router", "e_gate", "e_up", "e_down")


def layer_fn(m: Dims, quant: bool):
    """jitted ``(x (S, D) f32, stacked leaves, l) -> x`` for layer ``l``."""
    def f(x, stacked, l):
        w = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
             .astype(F32) for k, v in stacked.items()}
        x = x + _attention(m, w, rmsnorm(x, w["attn_norm"], m.eps), quant)
        return x + _ffn(m, w, rmsnorm(x, w["mlp_norm"], m.eps), quant)
    return jax.jit(f)


def embed_fn(m: Dims, quant: bool):
    """jitted ``(tokens (S,), emb) -> (S, D)`` f32."""
    def f(tokens, emb):
        x = jnp.take(emb, tokens, axis=0).astype(F32)
        return q8(x, -1) if quant else x
    return jax.jit(f)


def final_fn(m: Dims):
    """jitted ``(x (S, D), final_norm) -> (S, D)``."""
    def f(x, gain):
        return rmsnorm(x, gain, m.eps)
    return jax.jit(f)


VOCAB_CHUNK = 16400      # LM-head columns per step of the vocab scan


def vocab_chunks(vocab: int) -> int:
    """The fewest equal chunks of at most ``VOCAB_CHUNK`` columns."""
    n = -(-vocab // VOCAB_CHUNK)
    while vocab % n:
        n += 1
    return n


def head_leaves(m: Dims, w: Dict) -> Dict:
    return {"emb": w["emb"]} if m.tied else {"head": w["head"]}


def head_fn(m: Dims, quant: bool):
    """jitted ``(h (S, D), head leaves, rows (ROW_BLOCK,), ids
    (ROW_BLOCK,)) -> (best logit, logit of ids, argmax)`` for the rows
    ``h[rows]``, scanning the vocabulary in chunks so that no float32 copy
    of the whole head is made."""
    n = vocab_chunks(m.vocab)
    c = m.vocab // n

    def f(h, w, rows, ids):
        h = h[rows]
        if m.tied:
            chunks, spec, axis = w["emb"].reshape(n, c, m.d), "rd,vd->rv", 0
        else:
            chunks, spec, axis = w["head"].reshape(m.d, n, c), "rd,dv->rv", 1
        ids = jnp.clip(ids, 0, m.vocab - 1)

        def body(carry, i):
            best, at, arg = carry
            wc = jax.lax.dynamic_index_in_dim(chunks, i, axis, keepdims=False)
            lg = mm(spec, h, wc.astype(F32), quant, -1, 1 - axis)
            cmax = jnp.max(lg, axis=-1)
            carg = jnp.argmax(lg, axis=-1) + i * c
            own = (ids >= i * c) & (ids < (i + 1) * c)
            val = jnp.take_along_axis(
                lg, jnp.clip(ids - i * c, 0, c - 1)[:, None], axis=1)[:, 0]
            at = jnp.where(own, val, at)
            arg = jnp.where(cmax > best, carg, arg)
            return (jnp.maximum(best, cmax), at, arg), None

        R = h.shape[0]
        init = (jnp.full((R,), -jnp.inf, F32), jnp.zeros((R,), F32),
                jnp.zeros((R,), jnp.int32))
        (best, at, arg), _ = jax.lax.scan(body, init, jnp.arange(n))
        return best, at, arg
    return jax.jit(f)
