"""Random weights from ``--seed``, made on the device in one jitted call.

The leaves have the reference's names (``references/gqa_decoder.py``) and
are stored in bfloat16, the type the configurations are served in.  Each
leaf is drawn from its own key, ``fold_in(seed key, crc32(name))``, so a
leaf's values depend on its name and the seed alone.  The program's tree
is a rearrangement of the same leaves (``engine.program_params``), made
inside the same call; the reference calls the same function again once
the program's state is freed.

Scales follow the program's own initialiser: ``N(0, 1/fan_in)`` for
matrices, 0.02 for the embedding and the router.  Norm gains are
``1 + delta`` with ``delta ~ N(0, 0.1^2)``, so that a norm that drops its
gain is wrong and not a no-op; the program stores ``delta``.
"""
from __future__ import annotations

import zlib
from typing import Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.dims import Dims

GAIN_SCALE = 0.1
EMB_SCALE = 0.02
ROUTER_SCALE = 0.02


def specs(m: Dims) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Leaf name -> (shape, standard deviation)."""
    L, D = m.layers, m.d
    s = {
        "emb": ((m.vocab, D), EMB_SCALE),
        "final_norm": ((D,), GAIN_SCALE),
        "attn_norm": ((L, D), GAIN_SCALE),
        "mlp_norm": ((L, D), GAIN_SCALE),
        "wq": ((L, D, m.q_dim), D ** -0.5),
        "wk": ((L, D, m.kv_dim), D ** -0.5),
        "wv": ((L, D, m.kv_dim), D ** -0.5),
        "wo": ((L, m.q_dim, D), m.q_dim ** -0.5),
    }
    if not m.tied:
        s["head"] = ((D, m.vocab), D ** -0.5)
    if m.moe:
        E, F = m.experts, m.expert_ffn
        s.update({
            "router": ((L, D, E), ROUTER_SCALE),
            "e_gate": ((L, E, D, F), D ** -0.5),
            "e_up": ((L, E, D, F), D ** -0.5),
            "e_down": ((L, E, F, D), F ** -0.5),
        })
    else:
        F = m.ffn
        s.update({
            "w_gate": ((L, D, F), D ** -0.5),
            "w_up": ((L, D, F), D ** -0.5),
            "w_down": ((L, F, D), F ** -0.5),
        })
    return s


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from any whole number (seeds may pass
    32 signed bits)."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    return ss.generate_state(2, dtype=np.uint32)


def maker(m: Dims, arrange: Optional[Callable[[Dict], Dict]] = None,
          names: Optional[Iterable[str]] = None):
    """A jitted ``f(words) -> tree``: the leaves of ``specs(m)`` (or only
    ``names`` of them) drawn in bfloat16, then ``arrange``d (default: the
    reference's own names)."""
    table = specs(m)
    if names is not None:
        table = {k: table[k] for k in names if k in table}

    def make(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0), words[0]), words[1])
        out = {}
        for name, (shape, scale) in sorted(table.items()):
            k = jax.random.fold_in(key, np.uint32(zlib.crc32(name.encode())
                                                  & 0x7FFFFFFF))
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * scale).astype(jnp.bfloat16)
        return arrange(out) if arrange is not None else out

    return jax.jit(make)


def make(m: Dims, seed: int, arrange: Optional[Callable] = None,
         names: Optional[Iterable[str]] = None) -> Dict:
    return maker(m, arrange, names)(jnp.asarray(seed_words(seed)))
