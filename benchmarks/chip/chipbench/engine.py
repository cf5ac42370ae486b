"""The system under test, built as ``launch/serve.py`` builds it.

Paged KV plane, chunked bucketed prefill at the default chunk of 64,
dropless routing for a mixture of experts, prefix cache, tiering and
disaggregation off, the NIC cost model on.  This is the only module of
the benchmark that imports the program.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.dims import Dims
from repro.configs import get_config
from repro.models.model import build_model
from repro.runtime.server import BatchServer, encode_request


def program_config(c: Dict):
    """The program's ``ModelConfig`` with every size from the file."""
    kw = dict(n_layers=int(c["num_hidden_layers"]),
              d_model=int(c["hidden_size"]),
              n_heads=int(c["num_attention_heads"]),
              n_kv_heads=int(c["num_key_value_heads"]),
              head_dim=int(c["head_dim"]), vocab=int(c["vocab_size"]),
              rope_theta=float(c["rope_theta"]),
              norm_eps=float(c["rms_norm_eps"]),
              tie_embeddings=bool(c["tie_word_embeddings"]),
              param_dtype=c["torch_dtype"], compute_dtype=c["torch_dtype"],
              cache_dtype=c["torch_dtype"])
    if c.get("num_local_experts"):
        kw.update(n_experts=int(c["num_local_experts"]),
                  top_k=int(c["num_experts_per_tok"]),
                  d_ff_expert=int(c["intermediate_size"]), d_ff=0,
                  moe_routing="dropless")
    else:
        kw.update(d_ff=int(c["intermediate_size"]))
    return get_config(c["program_arch"]).replace(**kw)


def arrange_for(cfg):
    """Reference-named leaves -> the program's parameter tree.  Rows of the
    vocabulary that the program pads on are zero, as a checkpoint loaded
    into padded storage holds them."""
    pad = cfg.padded_vocab - cfg.vocab

    def arrange(w):
        blocks = {"ln1": w["attn_norm"], "ln2": w["mlp_norm"],
                  "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                           "wo": w["wo"]}}
        if cfg.family == "moe":
            blocks["moe"] = {"router": w["router"], "wg": w["e_gate"],
                             "wu": w["e_up"], "wd": w["e_down"]}
        else:
            blocks["mlp"] = {"wg": w["w_gate"], "wu": w["w_up"],
                             "wd": w["w_down"]}
        tree = {"emb": jnp.pad(w["emb"], ((0, pad), (0, 0))),
                "final_norm": w["final_norm"], "blocks": blocks}
        if not cfg.tie_embeddings:
            tree["head"] = jnp.pad(w["head"], ((0, 0), (0, pad)))
        return tree
    return arrange


def make_params(cfg, m: Dims, model, seed: int):
    """The program's parameters from ``seed``, in one call on the device;
    their tree must be exactly the one the model declares."""
    fn = weights.maker(m, arrange_for(cfg))
    words = jnp.asarray(weights.seed_words(seed))
    got = jax.eval_shape(fn, words)
    want = model.abstract_params()
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise RuntimeError("the generated parameters do not match the "
                           "model's declared tree")
    return fn(words)


def build(cfg, params, traffic: Dict, model=None) -> BatchServer:
    model = model if model is not None else build_model(cfg)
    srv = BatchServer(model, batch_slots=int(traffic["slots"]),
                      max_len=int(traffic["max_len"]), params=params,
                      paged_kv="auto", prefill_chunk="auto", nic_cost=True)
    if not (srv.paged and srv.prefill_chunk == 64 and not srv.prefix_cache
            and not srv.tiered):
        raise RuntimeError("the engine is not the paged, chunked (64), "
                           "untiered plane without a prefix cache")
    return srv


def decode_buckets(max_blocks: int):
    """Block-table widths a decode step ships: the blocks that cover the
    resident tokens, rounded up to a multiple of 8, capped at the table."""
    return sorted({min(max_blocks, -(-n // 8) * 8)
                   for n in range(1, max_blocks + 1)})


def warm(srv: BatchServer) -> int:
    """Compile every decode bucket and chunk bucket through the engine's
    own jitted callables, block tables on the trash page; returns the
    number of calls made."""
    fns = srv.jit_fns()
    B, mb = srv.slots, srv.pager.max_blocks
    trash = srv.pages["kp"].shape[1] - 1
    zeros = jnp.asarray(np.zeros((B,), np.int32))
    calls = 0
    for nb in decode_buckets(mb):
        _, srv.pages = fns["paged_decode"](
            srv.params, srv.pages, jnp.asarray(np.zeros((B, 1), np.int32)),
            jnp.asarray(np.full((B, nb), trash, np.int32)), zeros)
        calls += 1
    for c in srv.chunk_buckets:
        _, srv.pages = fns["chunk_prefill"](
            srv.params, srv.pages, jnp.asarray(np.zeros((B, c), np.int32)),
            jnp.asarray(np.full((B, mb), trash, np.int32)), zeros, zeros)
        calls += 1
    jax.block_until_ready(srv.pages)
    return calls


def compiled_programs(srv: BatchServer) -> int:
    return sum(srv.trace_counts().values())


def wires(reqs) -> list:
    return [encode_request(r.req_id, r.prompt.tolist(), r.max_new)
            for r in reqs]


def release(srv: Optional[BatchServer]):
    """Drop the engine's device state so the reference has the chip."""
    if srv is None:
        return
    for leaf in jax.tree.leaves((srv.params, srv.pages)):
        leaf.delete()
    srv.params = srv.pages = None
