"""Unified model API: build_model(cfg) -> Model.

One object per architecture exposing schema/init/loss/prefill/decode plus the
ShapeDtypeStruct ``input_specs`` used by the multi-pod dry-run (no device
allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeCell
from repro.models import encdec, transformer
from repro.models.layers import (
    abstract_params, init_params, logical_axes,
)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    schema: Any
    loss: Callable          # (params, batch, mesh) -> (loss, metrics)
    prefill: Callable       # (params, batch, mesh, max_len[, valid_len])
    #                          -> (logits, cache); valid_len marks the real
    #                          prompt length under bucket-padded tokens
    #                          (uniform-KV families only)
    decode_step: Callable   # (params, cache, tokens, mesh) -> (logits, cache)
    init_cache: Callable    # (batch, max_len) -> cache pytree
    # paged-KV data plane (block-table-indexed pool); None for families
    # without a uniform KV stack (ssm / hybrid / audio)
    init_paged_cache: Optional[Callable] = None
    # (batch, max_len, block_tokens) -> pages {"kp","vp"} (L,P,K,bt,hd)
    paged_decode_step: Optional[Callable] = None
    # (params, pages, tokens, block_tables, seq_lens, mesh) -> (logits, pages)
    paged_prefill_write: Optional[Callable] = None
    # (pages, k_rows, v_rows, block_ids, prompt_len) -> pages
    paged_prefill_chunk: Optional[Callable] = None
    # (params, pages, tokens, block_tables, ctx_lens, valid_lens, mesh)
    #   -> (last-valid-position logits, pages)
    kv_migrate: Optional[Callable] = None
    # (near, far, dem_src, dem_dst, pro_src, pro_dst) -> (near, far)
    #   one fused near<->far tier migration event (gather-first)

    def abstract_params(self):
        return abstract_params(self.schema, jnp.dtype(self.cfg.param_dtype))

    def param_logical_axes(self):
        return logical_axes(self.schema)

    def init(self, key):
        return init_params(self.schema, key, jnp.dtype(self.cfg.param_dtype))

    def cache_logical_axes(self, cache):
        return transformer.cache_logical_axes(self.cfg, cache)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "audio":
        return Model(
            cfg=cfg,
            schema=encdec.encdec_schema(cfg),
            loss=lambda p, b, mesh=None: encdec.encdec_loss(p, cfg, b, mesh),
            prefill=lambda p, b, mesh=None, max_len=None:
                encdec.encdec_prefill(p, cfg, b, mesh, max_len),
            decode_step=lambda p, c, t, mesh=None:
                encdec.encdec_decode_step(p, cfg, c, t, mesh),
            init_cache=lambda batch, max_len:
                encdec.encdec_init_cache(cfg, batch, max_len),
        )
    paged = {}
    if transformer.lm_supports_paged(cfg):
        paged = dict(
            init_paged_cache=lambda batch, max_len, block_tokens=16,
                frames=None:
                transformer.lm_init_paged_cache(cfg, batch, max_len,
                                                block_tokens, frames=frames),
            kv_migrate=transformer.lm_kv_migrate,
            paged_decode_step=lambda p, pages, t, btab, lens, mesh=None:
                transformer.lm_paged_decode_step(p, cfg, pages, t, btab,
                                                 lens, mesh),
            paged_prefill_write=lambda pages, k_rows, v_rows, ids, prompt_len,
                skip_tokens=0:
                transformer.lm_paged_prefill_write(cfg, pages, k_rows, v_rows,
                                                   ids, prompt_len,
                                                   skip_tokens),
            paged_prefill_chunk=lambda p, pages, t, btab, ctx, valid,
                mesh=None:
                transformer.lm_paged_prefill_chunk(p, cfg, pages, t, btab,
                                                   ctx, valid, mesh),
        )
    return Model(
        cfg=cfg,
        schema=transformer.lm_schema(cfg),
        loss=lambda p, b, mesh=None: transformer.lm_loss(p, cfg, b, mesh),
        prefill=lambda p, b, mesh=None, max_len=None, valid_len=None:
            transformer.lm_prefill(p, cfg, b, mesh, max_len, valid_len),
        decode_step=lambda p, c, t, mesh=None:
            transformer.lm_decode_step(p, cfg, c, t, mesh),
        init_cache=lambda batch, max_len:
            transformer.lm_init_cache(cfg, batch, max_len),
        **paged,
    )


# --------------------------------------------------------------------------
# Input specs (dry-run stand-ins; weak-type-correct, shardable)
# --------------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeCell) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train/prefill: the batch dict.  decode: {"tokens": (B,1)} — the cache is
    built separately via init_cache (it is carried state, not an input).
    """
    B, S = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        batch = {"tokens": _sds((B, S), jnp.int32)}
        if shape.kind == "train":
            batch["labels"] = _sds((B, S), jnp.int32)
        if cfg.family == "vlm":
            P = min(cfg.n_patch_tokens, S // 4)
            batch["vis_embeds"] = _sds((B, P, cfg.d_model), jnp.bfloat16)
            batch["pos_ids"] = _sds((B, S, 3), jnp.int32)
        if cfg.family == "audio":
            batch["frames"] = _sds((B, cfg.enc_frames, cfg.d_model),
                                   jnp.bfloat16)
        return batch
    # decode: one new token against a cache of seq_len
    return {"tokens": _sds((B, 1), jnp.int32)}


def batch_logical_axes(cfg: ModelConfig, batch: Dict[str, Any]):
    """Logical axes for each input-batch leaf (dict-structured)."""
    out = {}
    for k, v in batch.items():
        nd = len(v.shape)
        out[k] = ("batch",) + (None,) * (nd - 1)
    return out


def make_concrete_batch(cfg: ModelConfig, batch_specs, seed: int = 0):
    """Materialize a random batch matching input_specs (tests/examples)."""
    rng = np.random.RandomState(seed)
    out = {}
    for k, spec in batch_specs.items():
        if spec.dtype == jnp.int32:
            out[k] = jnp.asarray(
                rng.randint(0, max(2, cfg.vocab - 1), size=spec.shape),
                jnp.int32)
        else:
            out[k] = jnp.asarray(rng.randn(*spec.shape), jnp.float32) \
                .astype(spec.dtype)
    return out
