"""Decide ``correct``: the served tokens against the plain reference.

Once the window has closed and the engine's device state is freed, a
sample of the requests that the window served (drawn from the seed, with
the longest in it) is run through the reference once, over each prompt
with its served tokens.  The number compared is the widest gap by which
a served token's logit lies below the reference's best logit at that
position (greedy decoding: a sound program serves the reference's best
token or one within rounding of it).

Two numbers come of the gaps: the widest (``served_logit_gap``) and the
mean over all served tokens (``served_logit_gap_mean``); a cell's limits
file names the ones it compares.  The control (``control=True``) runs the
reference again with every matrix product in float8 e4m3 and reads, at
the same positions, the same two numbers for the token that it puts
first.
"""
from __future__ import annotations

import importlib.util
import math
import sys
import time
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.dims import Dims
from chipbench.spec import BENCH_DIR
from chipbench.traffic import rng_for

Seq = Tuple[List[int], List[int]]        # (prompt, served tokens)


def reference_module(name: str):
    path = BENCH_DIR / "references" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pick(cands: Dict[int, Tuple[int, int]], k: int, seed: int) -> List[int]:
    """``k`` request ids from ``{req_id: (prompt_len, n_served)}``: the
    longest, then others in an order drawn from the seed."""
    ids = sorted(cands)
    if not ids:
        return []
    longest = max(ids, key=lambda i: (sum(cands[i]), -i))
    rest = [i for i in ids if i != longest]
    order = rng_for(seed, "sample").permutation(len(rest))
    return [longest] + [rest[j] for j in order[:k - 1]]


def _free(tree):
    for leaf in jax.tree.leaves(tree):
        leaf.delete()


def _forward(m: Dims, ref, seed: int, seqs: Sequence[Seq], s_pad: int,
             quant: bool):
    """Final-normed hidden states ``(s_pad, D)`` of each sequence (the
    prompt and all served tokens but the last), layer by layer."""
    toks = []
    for prompt, served in seqs:
        t = np.zeros((s_pad,), np.int32)
        seq = list(prompt) + list(served[:-1])
        t[:len(seq)] = seq
        toks.append(jnp.asarray(t))
    w = weights.make(m, seed, names=["emb"])
    embed = ref.embed_fn(m, quant)
    xs = [embed(t, w["emb"]) for t in toks]
    _free(w)
    w = weights.make(m, seed, names=ref.LAYER_LEAVES)
    layer = ref.layer_fn(m, quant)
    for li in range(m.layers):
        li = jnp.asarray(li, jnp.int32)
        xs = [layer(x, w, li) for x in xs]
    _free(w)
    w = weights.make(m, seed, names=["final_norm"])
    final = ref.final_fn(m)
    hs = [final(x, w["final_norm"]) for x in xs]
    _free(w)
    return hs


def _rows(seqs: Sequence[Seq], block: int):
    """Per sequence, blocks of (positions, served ids): position
    ``len(prompt) - 1 + j`` predicts served token ``j``."""
    out = []
    for prompt, served in seqs:
        pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        ids = np.asarray(served, np.int64)
        blocks = []
        for a in range(0, len(pos), block):
            p = np.zeros((block,), np.int32)
            i = np.zeros((block,), np.int32)
            n = min(block, len(pos) - a)
            p[:n], i[:n] = pos[a:a + n], np.clip(ids[a:a + n], 0, 2**31 - 1)
            blocks.append((p, i, n))
        out.append(blocks)
    return out


def _head(m: Dims, ref, seed: int, hs, rows, quant: bool, ids_of=None):
    """Per row: (best, logit of the given id, argmax) under ``quant``."""
    w = ref.head_leaves(m, weights.make(
        m, seed, names=["emb"] if m.tied else ["head"]))
    head = ref.head_fn(m, quant)
    out = []
    for s, (h, blocks) in enumerate(zip(hs, rows)):
        for b, (p, i, n) in enumerate(blocks):
            ids = i if ids_of is None else ids_of[s][b]
            best, at, arg = head(h, w, jnp.asarray(p), jnp.asarray(ids))
            out.append((np.asarray(best)[:n], np.asarray(at)[:n],
                        np.asarray(arg)[:n]))
    _free(w)
    return out


def readings(m: Dims, ref, seed: int, seqs: Sequence[Seq], s_pad: int,
             control: bool) -> Dict[str, float]:
    """The numbers compared, and the control's reading when asked."""
    served = np.concatenate([np.asarray(s, np.int64) for _, s in seqs])
    bad = int(np.sum((served < 0) | (served >= m.vocab)))
    rows = _rows(seqs, ref.ROW_BLOCK)
    t = time.time()
    hs = _forward(m, ref, seed, seqs, s_pad, quant=False)
    jax.block_until_ready(hs)
    t1 = time.time()
    res = _head(m, ref, seed, hs, rows, quant=False)
    print(f"[chipbench] reference forward {t1 - t:.3f} s, LM head "
          f"{time.time() - t1:.3f} s", file=sys.stderr, flush=True)
    best = np.concatenate([r[0] for r in res])
    at = np.concatenate([r[1] for r in res])
    arg = np.concatenate([r[2] for r in res])
    gap = best - at
    gap[(served < 0) | (served >= m.vocab)] = math.inf
    out = {"served_logit_gap": float(np.max(gap)),
           "served_logit_gap_mean": float(np.mean(gap)),
           "bad_token_ids": bad,
           "served_tokens": int(len(served)),
           "reference_argmax_share": float(np.mean(arg == served))}
    if control:
        hq = _forward(m, ref, seed, seqs, s_pad, quant=True)
        resq = _head(m, ref, seed, hq, rows, quant=True)
        _free(hq)
        # the control's first choice at each row, read under float32
        ids_of, k = [], 0
        for blocks in rows:
            per = []
            for p, i, n in blocks:
                a = np.zeros_like(i)
                a[:n] = resq[k][2]
                per.append(a)
                k += 1
            ids_of.append(per)
        resc = _head(m, ref, seed, hs, rows, quant=False, ids_of=ids_of)
        gq = np.concatenate([r[0] - r[1] for r in resc])
        out["control_logit_gap"] = float(np.max(gq))
        out["control_logit_gap_mean"] = float(np.mean(gq))
    _free(hs)
    return out
