"""A configuration file's sizes under short names (Hugging Face keys in,
the shapes that the reference, the weights and the counts share out)."""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int                  # hidden_size
    heads: int              # num_attention_heads
    kv_heads: int           # num_key_value_heads
    head_dim: int
    layers: int             # num_hidden_layers, as run
    vocab: int              # vocab_size
    ffn: int                # dense intermediate_size (0 for experts)
    experts: int            # num_local_experts (0 for a dense FFN)
    top_k: int              # num_experts_per_tok
    expert_ffn: int         # width of one expert
    rope_theta: float
    eps: float              # rms_norm_eps
    tied: bool              # tie_word_embeddings

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def moe(self) -> bool:
        return self.experts > 0


def from_config(c: Dict) -> Dims:
    experts = int(c.get("num_local_experts", 0))
    width = int(c["intermediate_size"])
    return Dims(
        d=int(c["hidden_size"]), heads=int(c["num_attention_heads"]),
        kv_heads=int(c["num_key_value_heads"]), head_dim=int(c["head_dim"]),
        layers=int(c["num_hidden_layers"]), vocab=int(c["vocab_size"]),
        ffn=0 if experts else width, experts=experts,
        top_k=int(c.get("num_experts_per_tok", 0)),
        expert_ffn=width if experts else 0,
        rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        tied=bool(c["tie_word_embeddings"]))
