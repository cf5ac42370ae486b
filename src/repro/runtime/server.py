"""Serving runtime: RPC front-end + async continuous batching + decode loop.

The Cohet integration points (paper §V):
  * requests arrive as Protobuf-style wire messages (core.rpc codec) — the
    (de)serialization stage the CXL-NIC offloads; the integrated
    ``runtime.niccost`` model projects CXL-NIC vs PCIe-NIC cost of the
    actual wire traffic the server moved (Fig 18, live);
  * decode slots are claimed through a fetch-and-add ticket sequencer —
    the decentralized RAO CENTRAL pattern (core.rao), so no single
    coordinator thread sits on the critical path;
  * each slot's KV/state footprint is paged in token blocks through the
    coherent memory pool (core.pool), with the HBM-vs-host tier decision
    planned by core.placement (runtime.scheduler.KVBlockPager);
  * attention-family models decode through the **paged KV data plane**
    (``paged_kv="auto"``): the KV cache is a pooled page arena indexed by
    the pager's real block table, decode runs the paged-attention kernel
    path (``kernels.paged_attention`` on TPU, its jit'd ref off-TPU) over
    per-slot ragged lengths, admission writes only the admitted slot's
    pages (no full-cache splice), and slots admit continuously — the
    equal-prompt-length wave restriction of the dense shared-write-index
    cache is gone.  ``paged_kv=False`` keeps the dense (slots, max_len)
    cache path.  Sliding-window configs page under ``"auto"`` too: partial
    pager release (``KVBlockPager.release_behind``) frees behind-the-window
    pages as the window advances, so the steady-state footprint is
    O(window);
  * prompts stream in through a **chunked, bucketed prefill pipeline**
    (``prefill_chunk``): each PREFILLING slot advances by one fixed-size
    chunk per tick (padded up into a small mask-aware bucket table, like
    the decode side's ``_decode_bucket``), chunk KV scatters straight into
    the pool pages, and decode steps interleave between chunks — long
    prompts no longer block the wave, and the prefill XLA trace count is
    O(buckets) instead of O(distinct prompt lengths).  ``prefill_chunk=0``
    keeps the one-shot exact-length prefill (retraces per length).  The
    ``moe`` family joins the pipeline under dropless routing
    (``cfg.moe_routing="dropless"``, the serving default via
    ``launch.serve`` — no expert drops, so dispatch is a pure per-token
    function); capacity-factor routing serves one-shot only.  The dense
    plane (``paged_kv=False``) pads one-shot prefill lengths through the
    same geometric bucket table (O(buckets) graphs per group size);
    explicit ``prefill_chunk=0`` keeps its exact-length path.

Two engines share the scheduler core (``runtime.scheduler``):

  * ``BatchServer`` — synchronous tick loop (``step`` / ``run_until_drained``)
    with per-request state machines QUEUED -> PREFILL -> DECODE -> DONE;
  * ``AsyncBatchServer`` — asyncio engine: ``submit_async`` resolves a
    future per request while ``run_engine`` admits and decodes
    continuously; drive it with ``runtime.loadgen`` arrival traces.

Runs end-to-end on CPU with a reduced model (examples/serve_rpc_batch.py).
"""
from __future__ import annotations

import asyncio
import dataclasses
import functools
import math
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Set

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rpc as wire
from repro.runtime import spans
from repro.runtime.niccost import NicCostModel, NullNicCostModel
from repro.runtime.scheduler import (
    AdmissionQueue, KVBlockPager, Request, RequestState, SlotTable,
    blocks_for,
)

REQ_SCHEMA = {1: "int", 2: "bytes", 3: "int", "_subs": {}}
# fields: 1=request_id, 2=prompt tokens (int32 bytes), 3=max_new_tokens
RESP_SCHEMA = {1: "int", 2: "bytes", "_subs": {}}
# fields: 1=request_id, 2=generated tokens (int32 bytes)

# disagg prefill->decode handoff message (DisaggEngine): the per-request
# unit of inter-worker wire traffic.  Int-heavy by construction (ticket +
# repeated block-table page ids — the shape the varint-accurate
# message_profile exists for) plus 'str' prompt metadata.
HANDOFF_SCHEMA = {1: "int", 2: "int", 3: "int", 4: "int", 5: "int",
                  6: "int", 7: "str", 8: "str", "_subs": {}}
# fields: 1=request_id, 2=decode-slot RAO ticket, 3=prompt tokens,
#         4=max_new, 5=generated tokens so far (repeated), 6=block-table
#         page ids in position order, -1 = window-released (repeated),
#         7=model family, 8=handoff lane tag
# the decode worker's slot-ticket counter lives at its own RAO address:
# the engine's linearization guarantee is per-address (core.rao), so the
# prefill-admission counter (addr 0) and this one serialize independently
DECODE_TICKET_ADDR = 64


def _as_list(v) -> list:
    """Normalize a decoded repeated field (scalar when one element)."""
    return v if isinstance(v, list) else [v]


def encode_request(req_id: int, prompt: List[int], max_new: int) -> bytes:
    return wire.encode({1: req_id,
                        2: np.asarray(prompt, np.int32).tobytes(),
                        3: max_new})


def decode_request(buf: bytes) -> Dict:
    msg = wire.decode(buf, REQ_SCHEMA)
    return {"req_id": msg[1],
            "prompt": np.frombuffer(msg[2], np.int32).tolist(),
            "max_new": msg[3]}


def encode_response(req_id: int, tokens: List[int]) -> bytes:
    return wire.encode({1: req_id,
                        2: np.asarray(tokens, np.int32).tobytes()})


def _set_rows(full, one, slot_arr, axis: int):
    """Scatter the batch rows of `one` into `full[..., slot_arr, ...]`
    along `axis` (jax or numpy)."""
    idx = (slice(None),) * axis + (slot_arr,)
    if hasattr(full, "at"):
        return full.at[idx].set(one)
    full = full.copy()
    full[idx] = one
    return full


def _prefill_buckets(chunk: int, n_buckets: int):
    """Mask-aware pad targets for the ragged last chunk of a prompt:
    geometric halves of ``chunk`` (ascending), at most ``n_buckets`` of
    them, floor 8 tokens.  Every full chunk uses the largest bucket, so
    the chunk-prefill trace count is bounded by ``len(buckets)``."""
    if n_buckets < 1:
        raise ValueError(f"prefill_buckets must be >= 1, got {n_buckets}")
    sizes = [chunk]
    while len(sizes) < n_buckets and sizes[-1] // 2 >= 8:
        sizes.append(sizes[-1] // 2)
    return tuple(sorted(sizes))


def _named(f, name: str):
    """``f`` under ``name``: ``jax.jit`` names the program it compiles
    after the function (``jit_<name>``)."""
    @functools.wraps(f)
    def named(*args, **kwargs):
        return f(*args, **kwargs)
    named.__name__ = named.__qualname__ = name
    return named


def _splice_rows_tree(cache, cache1, slot_arr, *, n_slots: int):
    """Write a B=k prefill cache into batch rows `slot_arr` of the shared
    cache.  Stacked (L, B, ...) leaves splice on axis 1, per-batch
    (B, ...) leaves on axis 0; scalars pass through (the caller owns the
    shared write index).  Jitted by the server: one fused scatter per leaf,
    retraced only per distinct admission-group size k."""
    k = slot_arr.shape[0]

    def splice(full, one):
        nd = getattr(one, "ndim", 0)
        if nd == 0:
            return full
        if nd >= 2 and one.shape[1] == k and full.shape[1] == n_slots:
            return _set_rows(full, one, slot_arr, axis=1)
        if one.shape[0] == k and full.shape[0] == n_slots:
            return _set_rows(full, one, slot_arr, axis=0)
        return full

    return jax.tree.map(splice, cache, cache1)


class BatchServer:
    """Slot-based continuous batching: prefill on admit, batched decode.

    Per-request lifecycle is the scheduler state machine; slot claims go
    through the RAO ticket sequencer; the pager accounts each slot's cache
    blocks in the coherent pool.  ``nic_cost=None`` disables the SimCXL
    NIC projection (e.g. in throughput microbenchmarks).
    """

    def __init__(self, model, *, batch_slots: int = 4, max_len: int = 128,
                 params=None, key=None, mesh=None, block_tokens: int = 16,
                 nic_cost: Optional[object] = True, pool=None,
                 jit: bool = True, prefill_batch: int = 1,
                 paged_kv="auto", prefill_chunk="auto",
                 prefill_buckets: int = 4,
                 prefix_cache: bool = False, prefix_watermark: float = 0.0,
                 kv_overcommit: float = 1.0,
                 kv_near_blocks: Optional[int] = None,
                 kv_demote_after: Optional[int] = None):
        self.model = model
        self.mesh = mesh
        self.max_len = max_len
        self.slots = batch_slots
        self.params = params if params is not None else \
            model.init(key if key is not None else jax.random.PRNGKey(0))
        family = getattr(getattr(model, "cfg", None), "family", None)
        self.family = family or ""
        self.window = int(getattr(getattr(model, "cfg", None),
                                  "sliding_window", 0) or 0)
        # recurrent-state families admit continuously; shared-write-index
        # KV caches admit in equal-prompt-length waves (scheduler.py) —
        # unless the paged data plane (per-slot lengths) is active
        self.continuous = family == "ssm"
        if paged_kv in ("auto", None):
            # sliding-window configs page under auto too: partial pager
            # release (KVBlockPager.release_behind) frees behind-the-window
            # pages as the window advances, so the paged footprint is
            # O(window) like the dense ring's
            paged_kv = (not self.continuous and
                        getattr(model, "paged_decode_step", None) is not None)
        self.paged = bool(paged_kv)
        if self.paged and getattr(model, "paged_decode_step", None) is None:
            raise ValueError(f"paged_kv requested but model "
                             f"{family!r} has no paged decode path")
        # prefill is chunk/pad-invariant iff routing decisions are a pure
        # per-token function: every family except capacity-factor MoE,
        # whose expert drops depend on the token population of each
        # dispatch call (rank-in-expert resets per chunk, pad rows consume
        # capacity).  Dropless MoE routing (cfg.moe_routing="dropless",
        # the serving default via launch.serve) removes the drops, so moe
        # runs the chunked bucketed pipeline like every other family.
        self._moe_routing = getattr(getattr(model, "cfg", None),
                                    "moe_routing", "capacity")
        chunk_invariant = family != "moe" or self._moe_routing == "dropless"
        if self.paged:
            if prefill_chunk in ("auto", None):
                prefill_chunk = min(64, max_len) if chunk_invariant else 0
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 0:
                raise ValueError(f"prefill_chunk must be >= 0 (0 = one-shot "
                                 f"exact-length prefill), got {prefill_chunk}")
            if prefill_chunk and not chunk_invariant:
                raise ValueError(
                    "chunked prefill needs chunk-invariant routing: "
                    "capacity-factor MoE drops depend on co-resident "
                    "tokens; serve with cfg.moe_routing='dropless' or "
                    "use prefill_chunk=0")
            if prefill_chunk and \
                    getattr(model, "paged_prefill_chunk", None) is None:
                raise ValueError(f"chunked prefill requested but model "
                                 f"{family!r} has no paged_prefill_chunk path")
            dense_bucketed = False
        else:
            if prefill_chunk not in ("auto", None, 0):
                raise ValueError("prefill_chunk requires the paged KV plane "
                                 "(paged_kv)")
            # dense-plane bucketed one-shot prefill: under "auto", prompt
            # lengths pad up through the same geometric bucket table as
            # the chunked pipeline (valid_len carries the real length), so
            # prefill compiles O(buckets) graphs per group size instead of
            # one per distinct prompt length.  Right-padding is exact only
            # for causal full-attention KV families with pad-invariant
            # routing; explicit prefill_chunk=0 keeps exact-length prefill
            # (the seed/PR-3 dense plane, bit-for-bit).
            dense_bucketed = (prefill_chunk in ("auto", None)
                              and chunk_invariant and not self.window
                              and family in ("dense", "moe", "vlm"))
            prefill_chunk = 0
        self.prefill_chunk = prefill_chunk
        self.chunk_buckets = _prefill_buckets(prefill_chunk, prefill_buckets) \
            if prefill_chunk else ()
        if dense_bucketed:
            if prefill_buckets < 1:
                raise ValueError(f"prefill_buckets must be >= 1, got "
                                 f"{prefill_buckets}")
            # the dense table runs the full geometric ladder from max_len
            # down to the 8-token floor (not just prefill_buckets rungs):
            # its rungs must reach max_len to cover long prompts, so a
            # count-capped table would make every short prompt pay a
            # max_len/2^(cap-1)-token forward — the ladder keeps padding
            # <= 2x (+ the floor) while the trace bound is its length,
            # O(log2(max_len / 8))
            self.dense_buckets = _prefill_buckets(
                max_len, max(prefill_buckets, max_len.bit_length()))
        else:
            self.dense_buckets = ()
        # -------------------------------------------------- KV tiering
        # kv_overcommit > 1 (or an explicit kv_near_blocks) splits the
        # pooled arena into a near (HBM) tier the kernels read and a far
        # (CXL) tier holding cold pages; logical capacity is unchanged —
        # every page keeps a home — but only near_frames of them are
        # kernel-addressable at once (KVBlockPager does the tiering)
        self.kv_overcommit = float(kv_overcommit)
        if self.kv_overcommit < 1.0:
            raise ValueError(f"kv_overcommit must be >= 1.0 (1.0 = no "
                             f"overcommit), got {kv_overcommit}")
        if kv_near_blocks is not None and self.kv_overcommit != 1.0:
            raise ValueError("kv_near_blocks and kv_overcommit both size "
                             "the near tier; pass one")
        n_pages = batch_slots * blocks_for(max_len, block_tokens)
        near_frames: Optional[int] = None
        if kv_near_blocks is not None:
            near_frames = int(kv_near_blocks)
        elif self.kv_overcommit > 1.0:
            near_frames = max(blocks_for(max_len, block_tokens),
                              int(math.ceil(n_pages / self.kv_overcommit)))
        if near_frames is not None and not self.paged:
            raise ValueError("KV tiering (kv_overcommit/kv_near_blocks) "
                             "requires the paged KV plane (paged_kv)")
        tiered = near_frames is not None and near_frames < n_pages
        if kv_demote_after is not None:
            if int(kv_demote_after) < 1:
                raise ValueError(f"kv_demote_after must be >= 1, got "
                                 f"{kv_demote_after}")
            if not tiered:
                raise ValueError("kv_demote_after requires active KV "
                                 "tiering (kv_overcommit > 1 or "
                                 "kv_near_blocks < pool size)")
        if self.paged:
            if tiered:
                # near arena: what the kernels address (plus trash frame);
                # far arena: the remaining frames, host/CXL-placed
                self.pages = model.init_paged_cache(
                    batch_slots, max_len, block_tokens, frames=near_frames)
                self.far_pages = model.init_paged_cache(
                    batch_slots, max_len, block_tokens,
                    frames=n_pages - near_frames)
            else:
                self.pages = model.init_paged_cache(batch_slots, max_len,
                                                    block_tokens)
                self.far_pages = None
            self.cache = None
            kp = self.pages["kp"]
            # k+v bytes per token, derived from the arena itself
            footprint = (2 * kp.nbytes // (kp.shape[1] * block_tokens), 0)
        else:
            self.pages = None
            self.far_pages = None
            self.cache = model.init_cache(batch_slots, max_len)
            footprint = None
        # prefix caching shares KV pool pages across requests whose
        # prompts extend a chunk-aligned cached prefix; off by default —
        # retained prefixes keep pool pages referenced past request drain
        if prefix_cache and not self.paged:
            raise ValueError("prefix_cache requires the paged KV plane "
                             "(paged_kv)")
        if not 0.0 <= prefix_watermark < 1.0:
            raise ValueError(f"prefix_watermark must be in [0, 1), got "
                             f"{prefix_watermark}")
        self.prefix_cache = bool(prefix_cache)
        self.prefix_watermark = float(prefix_watermark)
        self.table = SlotTable(batch_slots)
        self.queue = AdmissionQueue(continuous=self.continuous or self.paged)
        params_bytes = int(sum(getattr(l, "nbytes", 0) for l in
                               jax.tree_util.tree_leaves(self.params)))
        # whether the cache has a per-token (pageable) KV footprint; model
        # stubs can claim one via `paged_kv_footprint`
        has_kv = family in ("dense", "moe", "vlm", "hybrid", "audio") or \
            getattr(model, "paged_kv_footprint", False)
        self.pager = KVBlockPager(self.cache, n_slots=batch_slots,
                                  max_len=max_len, block_tokens=block_tokens,
                                  paged=has_kv, pool=pool,
                                  params_bytes=params_bytes,
                                  track_table=self.paged,
                                  footprint=footprint,
                                  prefix_cache=self.prefix_cache,
                                  near_frames=near_frames)
        self.tiered = bool(getattr(self.pager, "tiered", False))
        if kv_demote_after is not None:
            self.pager.policy = dataclasses.replace(
                self.pager.policy, demote_after=int(kv_demote_after))
        if self.paged:
            # the model sized the arenas, the pager sized the page table —
            # every near frame index must address a real (non-trash) arena
            # page, and near + far frames must cover the logical pool
            assert self.pages["kp"].shape[1] == self.pager.near_frames + 1, \
                (self.pages["kp"].shape, self.pager.near_frames)
            if self.tiered:
                assert self.far_pages["kp"].shape[1] == \
                    self.pager.far_frames + 1, \
                    (self.far_pages["kp"].shape, self.pager.far_frames)
        if nic_cost is True:
            self.niccost = NicCostModel()
        elif nic_cost in (None, False):
            self.niccost = NullNicCostModel()
        else:
            self.niccost = nic_cost
        # jit registry: every jit-compiled engine callable is created
        # through _jit() under a stable name, so the trace auditor
        # (repro.analysis.jaxpr) and tests can enumerate + label the
        # engine's graph set through jit_fns()/trace_counts() instead of
        # poking private attributes
        self._jit_fns: Dict[str, Any] = {}

        def _jit(name, f, **kw):
            # compiled under its registry name (``jit_paged_decode``, not
            # ``jit__lambda``), which names the program in a device trace
            fn = jax.jit(_named(f, name), **kw) if jit else f
            self._jit_fns[name] = fn
            return fn

        self._decode = _jit(
            "decode", lambda p, c, t: model.decode_step(p, c, t, mesh))
        self._prefill = _jit(
            "prefill", lambda p, b: model.prefill(p, b, mesh, max_len))
        if self.dense_buckets:
            # bucket-padded one-shot prefill: tokens padded to a bucket
            # length, valid_len carries the real prompt length (traced, so
            # no retrace per length — only per (group size, bucket))
            self._prefill_bucketed = _jit(
                "prefill_bucketed",
                lambda p, b, vl: model.prefill(p, b, mesh, max_len, vl))
        self._splice = _jit("splice", _splice_rows_tree,
                            static_argnames=("n_slots",))
        if self.paged:
            # one-shot path (prefill_chunk=0 only): prefill to the exact
            # prompt length (no padding to max_len: page writes replace
            # the padded splice) at the cost of one XLA trace per
            # (group size, prompt length) pair.  The default chunked
            # pipeline (_prefill_step) replaces this with bucket-padded
            # chunk calls whose trace count is bounded by chunk_buckets.
            self._prefill_exact = _jit(
                "prefill_exact", lambda p, b: model.prefill(p, b, mesh,
                                                            None))
            if self.prefill_chunk:
                # full-batch chunk step over the slot dim; the arena is
                # donated so chunk KV scatters in place
                self._chunk_prefill = _jit(
                    "chunk_prefill",
                    lambda p, pg, t, bt_, cx, vl:
                        model.paged_prefill_chunk(p, pg, t, bt_, cx, vl,
                                                  mesh),
                    donate_argnums=(1,))
            # the arena is donated: the new-token scatter and the per-slot
            # page writes update it in place instead of copying it
            self._paged_decode = _jit(
                "paged_decode",
                lambda p, pg, t, bt_, ln:
                    model.paged_decode_step(p, pg, t, bt_, ln, mesh),
                donate_argnums=(1,))
            self._page_write = _jit(
                "page_write",
                lambda pg, k, v, ids, n, skip=0:
                    model.paged_prefill_write(pg, k, v, ids, n, skip),
                static_argnames=("n", "skip"), donate_argnums=(0,))
            if self.tiered:
                # fused demote/promote copy between the arenas; both are
                # donated so a migration never doubles the KV footprint.
                # Gather-first inside (promote rows read before demote
                # rows land), so one event can swap through a full tier.
                self._kv_migrate = _jit(
                    "kv_migrate",
                    lambda near, far, ds, dd, ps, pd:
                        model.kv_migrate(near, far, ds, dd, ps, pd),
                    donate_argnums=(0, 1))
        # engagement bookkeeping (tiered plane): which slots this tick's
        # dispatches may touch, and a least-recently-engaged clock so
        # deferral rotates fairly.  None = everything engaged (untiered).
        self._engaged: Optional[Set[int]] = None
        self._last_engaged: Dict[int, int] = {}
        # quiet-tick fast path: mid-wave steady ticks (no admission,
        # release, or migration since the last full plan, and no slot
        # crossing a block boundary) cannot allocate frames or touch a
        # far page, so the whole engage/plan/pin cycle is skipped
        self._tier_dirty = True
        self._engaged_cache: Optional[Set[int]] = None
        self.prefill_batch = max(1, prefill_batch)
        # counters, and the seconds of every host span (runtime.spans)
        self.stats = {"prefills": 0, "prefill_chunks": 0, "decode_steps": 0,
                      "completed": 0, "failed": 0, "admitted": 0, "ticks": 0,
                      "decode_tokens": 0, "decode_pages_shipped": 0,
                      "decode_pages_live": 0, **spans.zeroed()}
        self.completed_reqs: List[Request] = []
        self._unbilled_tickets = 0
        self._busy_slot_ticks = 0
        self._closed = False

    # ---------------------------------------------------------- properties
    @property
    def active(self) -> Dict[int, Request]:
        return self.table.active

    @property
    def slot_utilization(self) -> float:
        total = self.stats["ticks"] * self.slots
        return self._busy_slot_ticks / total if total else 0.0

    def _span(self, name: str) -> spans.Span:
        """``with self._span("decode.select"):`` times a stage of the tick
        into ``stats`` and onto the profiler's host timeline."""
        return spans.Span(self.stats, name)

    # ------------------------------------------------------- audit hooks
    def jit_fns(self) -> Dict[str, Any]:
        """Name -> jit-compiled engine callable, the engine's full graph
        surface.  The trace auditor labels captured cache entries through
        this (public) registry instead of private attributes."""
        return dict(self._jit_fns)

    def trace_counts(self) -> Dict[str, int]:
        """Name -> live XLA cache-entry count per engine callable (0 when
        the engine was built with ``jit=False``).  The per-config sum is
        the quantity the trace-contract (J5) pins."""
        return {name: int(fn._cache_size())
                if hasattr(fn, "_cache_size") else 0
                for name, fn in self._jit_fns.items()}

    # ------------------------------------------------------------- admit
    def _request_from_msg(self, msg: Dict, wire_len: int) -> Request:
        req = Request(msg[1], np.frombuffer(msg[2], np.int32).tolist(),
                      msg[3])
        req.wire_bytes = wire_len
        return req

    def submit_wire(self, buf: bytes):
        msg = wire.decode(buf, REQ_SCHEMA)     # single decode on ingress
        with self._span("niccost"):
            self.niccost.on_ingress(msg)
        self.submit(self._request_from_msg(msg, len(buf)))

    def submit(self, req: Request):
        if self._closed:
            raise RuntimeError("server closed to new submissions")
        # decentralized slot claim: FAA ticket mod slots (binding to a
        # concrete free slot happens at admission time)
        req.ticket = self.table.claim_ticket()
        req.slot = self._ticket_hint(req.ticket)
        self._unbilled_tickets += 1
        if req.arrival_t == 0.0:
            req.arrival_t = time.perf_counter()
        self.queue.push(req)

    def close(self):
        """No further submissions; drain what is queued."""
        self._closed = True

    def reopen(self):
        """Accept submissions again after a drain — lets a benchmark run
        repeated timed waves against one warmed engine (retained prefix
        pages, compiled graphs, tier state all carry over)."""
        self._closed = False

    # ------------------------------------------------------ worker hooks
    # The monolithic engine owns the whole slot table and moves finished
    # prefills straight into DECODE.  DisaggEngine overrides these four
    # to partition the table into a prefill-worker range and a decode-
    # worker range and to route finished prefills through the wire
    # handoff instead.
    def _ticket_hint(self, ticket: int) -> int:
        """Slot hint derived from the admission FAA ticket."""
        return ticket % self.slots

    def _bind_admit(self, req: Request) -> int:
        """Bind an admitted request to a slot (the prefill worker's range
        under disaggregation)."""
        return self.table.bind(req)

    def _admit_free(self) -> int:
        """Slots the admission loop may still fill this tick."""
        return self.table.free

    def _after_prefill(self, req: Request, now: float):
        """A request's prompt is fully resident and its first token is
        emitted: monolith decodes it in place; disagg parks it for the
        decode-worker handoff."""
        req.to(RequestState.DECODE, now)

    def _do_handoffs(self, now: float):
        """Monolith: no handoff stage."""

    # ----------------------------------------------------------- prefill
    def _fail(self, req: Request, now: float) -> bytes:
        req.to(RequestState.FAILED, now)
        self.stats["failed"] += 1
        self.completed_reqs.append(req)
        buf = encode_response(req.req_id, [])
        self._notify(req, buf)
        return buf

    def _admit_group(self, reqs: List[Request], now: float):
        """Prefill a group of equal-prompt-length requests in one call
        (B=len(reqs)), then install each row: per-slot page writes on the
        paged plane, one fused splice on the dense cache."""
        for req in reqs:
            req.to(RequestState.PREFILL, now)
        slot_arr = np.array([self._bind_admit(req) for req in reqs],
                            np.int32)
        toks = np.asarray([r.prompt for r in reqs], np.int32)
        S = int(toks.shape[1])
        bucket = next((b for b in self.dense_buckets if b >= S), None)
        if bucket is not None:
            padded = np.pad(toks, ((0, 0), (0, bucket - S)))
            logits, cache1 = self._prefill_bucketed(
                self.params, {"tokens": padded}, jnp.asarray(S, jnp.int32))
        else:
            prefill = self._prefill_exact if self.paged else self._prefill
            logits, cache1 = prefill(self.params, {"tokens": toks})
        # repro-lint: disable=R4 -- intentional sync: the sampled token must reach host before the request can advance
        nxt = np.asarray(logits).argmax(axis=-1)
        t1 = time.perf_counter()
        for row, req in enumerate(reqs):
            req.generated.append(int(nxt[row]))
            self._after_prefill(req, t1)

        if self.paged:
            # ring-packed SWA one-shot rows (S > window) leave zero-KV
            # leading positions: those pages must be neither acquired from
            # nor published into the prefix cache
            shareable = not (self.window and S > self.window)
            skip = 0
            if self.prefix_cache and len(reqs) == 1 and shareable:
                # prefix-cached singleton admission: map the shared prefix
                # pages (pure refcounts, no allocation) and scatter ONLY
                # the tail blocks — shared pages are immutable for their
                # co-resident readers, and a re-write of "the same" KV is
                # not bit-safe (XLA low bits vary with the computing
                # call's batch shape)
                skip, ids = self.pager.admit_cached(
                    int(slot_arr[0]), reqs[0].prompt, S)
                if skip:
                    with self._span("niccost"):
                        self.niccost.on_prefix_share(
                            skip // self.pager.block_tokens,
                            self.pager.block_bytes)
            else:
                # one fused write of the admitted slots' blocks; nobody
                # else's cache moves
                ids = [p for slot in slot_arr
                       for p in self.pager.admit(int(slot), S)]
            # fresh allocations may have force-demoted cold pages: land
            # those copies before the write; the new pages are near by
            # construction, so the id -> near-frame translation is total
            self._drain_migrations()
            ids_near = self.pager.to_near(np.asarray(ids, np.int32))
            self.pages = self._page_write(
                self.pages, cache1["k"], cache1["v"],
                jnp.asarray(ids_near, jnp.int32), S, skip)
            if self.prefix_cache and shareable:
                for slot, req in zip(slot_arr, reqs):
                    self.pager.publish_prefix(int(slot), req.prompt)
        else:
            self.cache = self._splice(self.cache, cache1, slot_arr,
                                      n_slots=self.slots)
            if not self.continuous:
                # shared write index: admission waves have equal prompt
                # lengths, so overwriting it never moves it under an
                # in-flight request
                self.cache["cur"] = cache1["cur"]
                if "pos" in self.cache:
                    # shared SWA ring-position array: every in-flight slot
                    # sits at the same cur, and the freshly prefilled ring
                    # is the canonical pos state at that cur.  Without this
                    # install the ring stayed all -1 after admission (the
                    # (T,) leaf passes through the batch-row splice), so
                    # dense-SWA decode masked the entire prompt dead —
                    # caught by tests/test_differential.py
                    self.cache["pos"] = cache1["pos"]
            for slot in slot_arr:
                self.pager.admit(int(slot), self.table.active[int(slot)].pos)
        self.stats["prefills"] += len(reqs)
        self.stats["admitted"] += len(reqs)
        self._tier_dirty = True                # fresh slots + page claims

    def _admit(self, now: float) -> List[bytes]:
        """Admit from the queue while slots are free and the head request
        is admissible under the family's policy.  Consecutive admissible
        requests with the same prompt length prefill as one batched call
        (up to ``prefill_batch``)."""
        failures: List[bytes] = []
        group: List[Request] = []
        # overcommit admission gate: a request only enters a slot when its
        # prompt blocks fit the obtainable near frames (free + demotable);
        # otherwise it stays queued — exactly the cold engine's queueing
        # behavior, but against near+far capacity instead of HBM alone.
        # Chunked admissions allocate one block up front and stream the
        # rest under the engagement plan, so they gate on a single block.
        headroom = self.pager.admit_headroom() if self.tiered else None
        planned = 0

        def flush():
            if group:
                self._admit_group(group, now)
                group.clear()

        while self._admit_free() > len(group):
            if self.tiered:
                head = next(iter(self.queue), None)
                if head is not None:
                    need = 1 if self.prefill_chunk else max(
                        1, blocks_for(min(len(head.prompt), self.max_len),
                                      self.pager.block_tokens))
                    if planned + need > headroom:
                        break
                    planned += need
            empty = not self.active and not group
            if self.continuous or self.paged or empty:
                wi = 0                            # unused by the policy
            elif group:
                # mid-wave: the group fixes the admissible prompt length
                wi = len(group[0].prompt)
            else:
                wi = int(self.cache["cur"])       # device sync only if needed
            req = self.queue.pop_admissible(engine_empty=empty,
                                            write_index=wi)
            if req is None:
                break
            if not req.prompt or req.max_new < 1 or \
                    (self.paged and len(req.prompt) > self.max_len):
                failures.append(self._fail(req, now))
                continue
            if self.prefill_chunk:
                # chunked pipeline: bind a slot now, stream the prompt in
                # one bucket-padded chunk per tick (_prefill_step) — no
                # admission-time prefill call, no equal-length grouping
                self._admit_chunked(req, now)
                continue
            if self.prefix_cache and self.pager.match_prefix(req.prompt):
                # cached-prefix one-shot admissions go as singleton
                # groups: the page-write skip count must be uniform
                # across a group
                flush()
                group.append(req)
                flush()
                continue
            if group and (len(group) >= self.prefill_batch
                          or len(req.prompt) != len(group[0].prompt)):
                flush()
            group.append(req)
        flush()
        return failures

    def _admit_chunked(self, req: Request, now: float):
        """Chunked admission: claim the slot and the fixed-state region;
        prompt pages are allocated chunk by chunk, and the first token
        comes out of the final chunk."""
        req.to(RequestState.PREFILL, now)
        self._bind_admit(req)
        if self.prefix_cache:
            hit, _ = self.pager.admit_cached(req.slot, req.prompt, 0)
            if hit:
                # resume mid-prompt: positions [0, hit) are already
                # resident in shared pages — this is where the prefill
                # compute is actually skipped
                req.prefilled = hit
                with self._span("niccost"):
                    self.niccost.on_prefix_share(
                        hit // self.pager.block_tokens,
                        self.pager.block_bytes)
        else:
            self.pager.admit(req.slot, 0)
        req.to(RequestState.PREFILLING, now)
        self.stats["admitted"] += 1

    # ------------------------------------------------------------ decode
    def _finish(self, req: Request, now: float) -> bytes:
        req.to(RequestState.DONE, now)
        slot = req.slot
        self.table.release(slot)
        self.pager.release(slot)
        self.stats["completed"] += 1
        self.completed_reqs.append(req)
        buf = encode_response(req.req_id, req.generated)
        with self._span("niccost"):
            self.niccost.on_egress({1: req.req_id,
                                    2: np.asarray(req.generated,
                                                  np.int32).tobytes()})
        self._notify(req, buf)
        return buf

    def _exhausted(self, req: Request) -> bool:
        return len(req.generated) >= req.max_new or \
            (not self.continuous and req.pos >= self.max_len)

    def _harvest(self, now: float) -> List[bytes]:
        with self._span("harvest"):
            out = [self._finish(req, now)
                   for _, req in sorted(self.active.items())
                   if req.state is RequestState.DECODE
                   and self._exhausted(req)]
        if out:
            self._tier_dirty = True            # slots released pages
        return out

    # ----------------------------------------------------- chunked prefill
    def _prefill_step(self):
        """Advance every PREFILLING slot by one prompt chunk (ragged last
        chunks pad up into ``chunk_buckets``), batched over the full slot
        dimension so the XLA trace count is bounded by the bucket table —
        never by distinct prompt lengths or by which slots happen to be
        prefilling.  The chunk call ships the full-width block table (a
        fixed column count keeps retraces O(buckets)); decode keeps its
        finer 8-column bucketing."""
        pre = {slot: req for slot, req in self.active.items()
               if req.state is RequestState.PREFILLING}
        if self._engaged is not None:
            # tiered plane: only the engaged slots' pages are near; the
            # deferred ones chunk on a later tick (engage() rotates)
            pre = {s: r for s, r in pre.items() if s in self._engaged}
        if not pre:
            return
        with self._span("chunk") as sp:
            sp.stage("chunk.prep")
            step_v: Dict[int, int] = {}
            hi = 0
            for slot, req in pre.items():
                v = min(self.prefill_chunk, len(req.prompt) - req.prefilled)
                step_v[slot] = v
                hi = max(hi, v)
            C = next(b for b in self.chunk_buckets if b >= hi)
            toks = np.zeros((self.slots, C), np.int32)
            ctx = np.zeros((self.slots,), np.int32)
            valid = np.zeros((self.slots,), np.int32)
            for slot, req in pre.items():
                v = step_v[slot]
                toks[slot, :v] = req.prompt[req.prefilled:req.prefilled + v]
                ctx[slot] = req.prefilled
                valid[slot] = v
                self.pager.advance(slot, req.prefilled + v)
            # chunk growth may have force-demoted; land copies pre-dispatch
            self._drain_migrations()
            btab = self.pager.to_near(self._masked_block_table(pre))
            completes = any(req.prefilled + step_v[slot] >= len(req.prompt)
                            for slot, req in pre.items())
            sp.stage("chunk.dispatch")
            logits, self.pages = self._chunk_prefill(
                self.params, self.pages, jnp.asarray(toks),
                jnp.asarray(btab), jnp.asarray(ctx), jnp.asarray(valid))
            # materialize logits only on ticks where some prompt completes
            # — a device sync on every chunk tick would serialize the async
            # engine's dispatch overlap for nothing (mid-prompt logits are
            # never read)
            nxt = None
            if completes:
                sp.stage("chunk.wait")
                # repro-lint: disable=R4 -- intentional sync: gated on prompt completion; mid-chunk ticks stay async
                jax.block_until_ready(logits)
                sp.stage("chunk.select")
                # repro-lint: disable=R4 -- intentional sync: the completed prompts' first tokens reach host here
                nxt = np.asarray(logits).argmax(axis=-1)
        self.stats["prefill_chunks"] += 1
        now = time.perf_counter()
        for slot, req in pre.items():
            req.prefilled += step_v[slot]
            if self.window:
                # the next query position is >= req.prefilled: everything
                # behind its window is dead for every future step
                self.pager.release_behind(
                    slot, max(0, req.prefilled - self.window + 1))
            if req.prefilled >= len(req.prompt):
                req.generated.append(int(nxt[slot]))
                self._after_prefill(req, now)
                self.stats["prefills"] += 1
                if self.prefix_cache:
                    # chunk writes are position-exact, so the now-complete
                    # full prompt blocks are publishable; window-released
                    # leading blocks (-1 rows) end the chain inside
                    self.pager.publish_prefix(slot, req.prompt)

    def _masked_block_table(self, live, nb: Optional[int] = None):
        """Owned copy of the pager's block table with the rows of every
        slot NOT in ``live`` set to -1: the kernels mask those reads dead
        and route their writes to the trash page, so a dispatch (chunk
        step or decode step) can never touch a slot it doesn't own."""
        btab = np.array(self.pager.block_table(nb))
        skip = np.ones((self.slots,), bool)
        skip[list(live)] = False
        btab[skip] = -1
        return btab

    def _decode_bucket(self, max_resident: int) -> int:
        """Block-table columns to ship this step: blocks covering every
        resident token plus the incoming one, rounded up to a multiple of
        8 (bounded jit retraces; short contexts never pay attention over
        the engine's max_len)."""
        need = max(1, blocks_for(max_resident, self.pager.block_tokens))
        return min(self.pager.max_blocks, -(-need // 8) * 8)

    # ------------------------------------------------------- KV tiering
    @staticmethod
    def _pad_pairs(pairs, trash_src: int, trash_dst: int, m: int):
        """(src, dst) frame pairs -> int32 index arrays padded to width
        ``m`` with trash-to-trash self-copies (the trash frames are
        never read meaningfully, so extra copies are inert)."""
        src = np.full((m,), trash_src, np.int32)
        dst = np.full((m,), trash_dst, np.int32)
        for i, (s, d) in enumerate(pairs):
            src[i] = s
            dst[i] = d
        return src, dst

    def _drain_migrations(self):
        """Execute the pager's pending migration plan against the arenas.
        Events run in plan order (later events may reuse frames earlier
        ones freed) and must all land before the next arena-touching
        dispatch — which they do: XLA executes the donated-arena calls
        in dispatch order on the stream."""
        if not self.tiered:
            return
        for dem, pro in self.pager.take_migrations():
            # both sides padded to ONE power-of-two width: the migrate
            # kernel's shape family is then the diagonal (m, m) —
            # O(log frames) total compiles, all captured by
            # warmup_migrations() — rather than the (dem, pro) cross
            # product, any cell of which could first appear mid-wave
            m = 1 << (max(1, len(dem), len(pro)) - 1).bit_length()
            ds, dd = self._pad_pairs(dem, self.pager.near_frames,
                                     self.pager.far_frames, m)
            ps, pd = self._pad_pairs(pro, self.pager.far_frames,
                                     self.pager.near_frames, m)
            self.pages, self.far_pages = self._kv_migrate(
                self.pages, self.far_pages,
                jnp.asarray(ds), jnp.asarray(dd),
                jnp.asarray(ps), jnp.asarray(pd))
            if dem or pro:
                with self._span("niccost"):
                    self.niccost.on_kv_migrate(len(dem) + len(pro),
                                               self.pager.block_bytes)
                self._tier_dirty = True        # residency moved

    def warmup_migrations(self):
        """Compile every migrate-kernel shape off the serving hot path.
        Pair counts are power-of-two bucketed, so the shape set is
        O(log frames); each warmup call is a trash-to-trash self-copy
        (inert).  The serving-engine analogue of capturing decode graphs
        at startup: without it the first few migration events pay an XLA
        compile mid-wave."""
        if not self.tiered:
            return
        nt, ft = self.pager.near_frames, self.pager.far_frames
        m, bound = 1, max(nt, ft)
        while True:
            self.pages, self.far_pages = self._kv_migrate(
                self.pages, self.far_pages,
                jnp.full((m,), nt, jnp.int32), jnp.full((m,), ft, jnp.int32),
                jnp.full((m,), ft, jnp.int32), jnp.full((m,), nt, jnp.int32))
            if m >= bound:
                break
            m <<= 1
        # repro-lint: disable=R4 -- intentional sync: one-time startup graph capture, off the serving path
        jax.block_until_ready(self.pages)

    def _want_tokens(self, req: Request) -> int:
        """Tokens the slot's next dispatch makes resident (the engagement
        demand unit)."""
        if req.state is RequestState.PREFILLING:
            # +1: a chunk that completes the prompt decodes this same
            # tick at position len(prompt) + 1
            t = min(req.prefilled + self.prefill_chunk,
                    len(req.prompt)) + 1
        else:
            t = req.pos
        return min(t, self.max_len)

    def _quiet_tick(self) -> bool:
        """True when this tick provably needs no engagement plan: nothing
        was admitted, released, or migrated since the last full plan, the
        cached engaged set covers every active slot, and no slot's next
        dispatch crosses a block boundary.  Under those conditions no
        frame can be claimed and no far page read, so skipping the plan
        (including its pins — pins only guard claims) is sound.  SWA
        engines are excluded: release-behind changes block lists
        mid-tick."""
        if self._tier_dirty or self.window or self._engaged_cache is None:
            return False
        bt = self.pager.block_tokens
        for slot, req in self.active.items():
            if slot not in self._engaged_cache:
                return False                   # a deferred slot wants in
            if req.state not in (RequestState.PREFILLING,
                                 RequestState.DECODE):
                return False
            if blocks_for(self._want_tokens(req), bt) \
                    > self.pager.resident_blocks(slot):
                return False
        return True

    def _plan_engaged(self, *, prefetch: bool = False) -> Optional[Set[int]]:
        """Pick the slots this tick's dispatches may touch (near-capacity
        packing over their working sets, least-recently-engaged first so
        deferral rotates) and make their pages near-resident.  With
        ``prefetch=True`` (end of tick) the same plan runs for the *next*
        tick's set, so its promotions overlap idle time and count as
        prefetches, not demand stalls."""
        if not self.tiered:
            return None
        if self._quiet_tick():
            return self._engaged_cache
        wants = []
        order = sorted(self.active.items(),
                       key=lambda kv: (self._last_engaged.get(kv[0], -1),
                                       kv[0]))
        for slot, req in order:
            if req.state not in (RequestState.PREFILLING,
                                 RequestState.DECODE):
                continue
            wants.append((slot, self._want_tokens(req)))
        if not wants:
            # still reset pins / run the proactive demoter on idle ticks
            self.pager.plan_near(set(), prefetch=prefetch)
            self._drain_migrations()
            self._engaged_cache = set()
            self._tier_dirty = False
            return set()
        engaged = self.pager.engage(wants)
        self.pager.plan_near_slots(engaged, prefetch=prefetch)
        self._drain_migrations()
        if not prefetch:
            for s in engaged:
                self._last_engaged[s] = self.stats["ticks"]
        # the plan + drained copies leave the engaged set near-resident
        # and consistent: until something changes (dirty), subsequent
        # ticks may reuse it without replanning
        self._engaged_cache = set(engaged)
        self._tier_dirty = False
        return self._engaged_cache

    def step(self) -> List[bytes]:
        """One scheduler tick: admit from queue, advance chunked prefills
        by one chunk, hand finished prefills to the decode worker (disagg
        only), one batched decode step over the DECODE slots."""
        with self._span("tick"):
            with self._span("admit"):
                now = time.perf_counter()
                self.stats["ticks"] += 1
                if self.tiered:
                    # pins protect pages only within a tick; admission may
                    # demote last tick's working set (the plan below
                    # re-promotes)
                    self.pager.begin_tick(self.stats["ticks"])
                if self.prefix_cache and self.prefix_watermark:
                    # proactive LRU eviction keeps free-page headroom for
                    # incoming admissions
                    self.pager.evict_to_watermark(self.prefix_watermark)
                if self._unbilled_tickets:
                    with self._span("niccost"):
                        self.niccost.on_ticket_batch(self._unbilled_tickets)
                    self._unbilled_tickets = 0
                finished = self._admit(now)
            # tiered plane: pick + promote this tick's engaged working set
            # before any dispatch reads the arena (demand fetches land here)
            self._engaged = self._plan_engaged()
            if self.prefill_chunk:
                self._prefill_step()
            # disagg: move HANDOFF-parked requests into decode-worker slots
            # before harvest, so an already-exhausted handoff (max_new == 1)
            # finishes this same tick
            self._do_handoffs(now)
            # prefill emits the first token: single-token requests are
            # already complete and must not burn a decode step
            finished += self._harvest(now)
            return finished + self._decode_tick(now)

    def _decode_tick(self, now: float) -> List[bytes]:
        """The decode worker's half of a tick: one batched decode dispatch
        over the DECODE slots (plus tier prefetch planning).  Extracted
        from ``step`` so the disagg benchmark can time the decode worker
        separately from prefill interference."""
        self._busy_slot_ticks += len(self.active)
        decoding = {slot: req for slot, req in self.active.items()
                    if req.state is RequestState.DECODE}
        if self._engaged is not None:
            decoding = {s: r for s, r in decoding.items()
                        if s in self._engaged}
        if not decoding:
            if self.tiered:
                # prefetch the next tick's working set into the near tier
                self._plan_engaged(prefetch=True)
            return []

        last = np.zeros((self.slots, 1), np.int32)
        for slot, req in decoding.items():
            last[slot, 0] = req.generated[-1] if req.generated else 0
        # decode_wall_s: prep + dispatch + wait + select, exactly
        with self._span("decode") as sp:
            sp.stage("decode.prep")
            if self.paged:
                # per-slot ragged lengths; grow each slot's block list so
                # the incoming token's page exists before the kernel
                # computes its write location from (block_table, seq_lens)
                lens = np.zeros((self.slots,), np.int32)
                for slot, req in decoding.items():
                    lens[slot] = req.pos - 1      # tokens resident in pages
                    self.pager.advance(slot, req.pos)
                    if self.window:
                        # pages wholly behind this (and every future)
                        # query's window go back to the free list —
                        # steady-state footprint stays O(window) per slot
                        self.pager.release_behind(
                            slot, max(0, req.pos - self.window))
                nb = self._decode_bucket(int(lens.max()) + 1)
                # block-table columns shipped vs pages the kernel reads:
                # those holding a resident token inside the window
                bt = self.pager.block_tokens
                first = (np.maximum(lens - self.window + 1, 0)
                         if self.window else 0)
                live = -(-lens // bt) - first // bt
                self.stats["decode_pages_shipped"] += len(decoding) * nb
                self.stats["decode_pages_live"] += int(live.sum())
                # token-growth allocations may have force-demoted cold
                # pages
                self._drain_migrations()
                # PREFILLING slots hold live table rows but must be
                # neither attended nor written by the decode step
                btab = self.pager.to_near(
                    self._masked_block_table(decoding, nb))
                sp.stage("decode.dispatch")
                logits, self.pages = self._paged_decode(
                    self.params, self.pages, jnp.asarray(last),
                    jnp.asarray(btab), jnp.asarray(lens))
            else:
                sp.stage("decode.dispatch")
                logits, self.cache = self._decode(self.params, self.cache,
                                                  last)
            sp.stage("decode.wait")
            # repro-lint: disable=R4 -- intentional sync: greedy sampling needs the token on host to emit and schedule
            jax.block_until_ready(logits)
            sp.stage("decode.select")
            # repro-lint: disable=R4 -- intentional sync: the logits are ready; this copies them to host for the argmax
            nxt = np.asarray(logits).argmax(axis=-1)
        self.stats["decode_steps"] += 1
        self.stats["decode_tokens"] += len(decoding)

        now = time.perf_counter()
        for slot, req in decoding.items():
            req.generated.append(int(nxt[slot]))
            if not self.paged:
                self.pager.advance(slot, req.pos)
        finished = self._harvest(now)
        if self.tiered:
            # plan + fetch the next tick's engaged set now: these copies
            # overlap the tick boundary and count as prefetches
            self._plan_engaged(prefetch=True)
        return finished

    def run_until_drained(self,
                          max_ticks: Optional[int] = None) -> List[bytes]:
        """Tick until queue and slots are empty.  Unbounded by default —
        every tick makes progress (admission when empty, decode otherwise)
        and max_new/max_len bound each request, so draining terminates.
        Pass ``max_ticks`` to cap the run anyway (returns what drained)."""
        out = []
        ticks = 0
        while max_ticks is None or ticks < max_ticks:
            ticks += 1
            out.extend(self.step())
            if not len(self.queue) and not self.active:
                break
        return out

    # --------------------------------------------------------- reporting
    def _notify(self, req: Request, buf: bytes):
        """Completion hook (AsyncBatchServer resolves futures here)."""

    def kv_stats(self) -> dict:
        out = self.pager.stats()
        out["paged_kv"] = self.paged
        out["tiered"] = self.tiered
        return out

    def nic_report(self) -> dict:
        return self.niccost.report()


class AsyncBatchServer(BatchServer):
    """Asyncio continuous-batching engine on the same scheduler core.

    ``submit_async`` enqueues a request and resolves to its wire response;
    ``run_engine`` is the engine coroutine — it admits + decodes while work
    is pending and parks on an event when idle.  ``close()`` lets the
    engine exit once everything in flight has drained.
    """

    def __init__(self, *args, idle_wait_s: float = 0.01, **kwargs):
        super().__init__(*args, **kwargs)
        self.idle_wait_s = idle_wait_s
        self._futures: Dict[int, asyncio.Future] = {}
        self._wakeup: Optional[asyncio.Event] = None
        self._engine_exc: Optional[BaseException] = None

    def _event(self) -> asyncio.Event:
        if self._wakeup is None:
            self._wakeup = asyncio.Event()
        return self._wakeup

    async def submit_async(self, req) -> bytes:
        """Submit a Request (or wire-encoded bytes); awaits the response."""
        if self._engine_exc is not None:
            raise RuntimeError("engine crashed") from self._engine_exc
        # decode/validate before submitting: if anything raises (closed
        # server, bad wire bytes, duplicate id) no orphaned future is left
        # behind to wedge _drained(), and no future gets overwritten
        if isinstance(req, (bytes, bytearray)):
            buf = bytes(req)
            msg = wire.decode(buf, REQ_SCHEMA)
            rid = msg[1]
            self._check_unique(rid)
            with self._span("niccost"):
                self.niccost.on_ingress(msg)
            self.submit(self._request_from_msg(msg, len(buf)))
        else:
            rid = req.req_id
            self._check_unique(rid)
            self.submit(req)
        fut = asyncio.get_running_loop().create_future()
        self._futures[rid] = fut
        self._event().set()
        return await fut

    def _check_unique(self, rid: int):
        if rid in self._futures:
            raise ValueError(f"request id {rid} already in flight")

    def close(self):
        super().close()
        if self._wakeup is not None:
            self._wakeup.set()

    def reopen(self):
        super().reopen()
        self._wakeup = None     # the next drive loop binds a fresh event

    def _notify(self, req: Request, buf: bytes):
        fut = self._futures.pop(req.req_id, None)
        if fut is not None and not fut.done():
            fut.set_result(buf)

    def _drained(self) -> bool:
        return not len(self.queue) and not self.active and not self._futures

    async def run_engine(self):
        """Engine loop: tick while work is pending, park when idle, exit
        when closed and fully drained.  A crash fails every outstanding
        future so no awaiting submitter hangs."""
        ev = self._event()
        try:
            while not (self._closed and self._drained()):
                if self.active or len(self.queue):
                    self.step()
                    await asyncio.sleep(0)        # cooperative yield
                    continue
                ev.clear()
                if self._closed and self._drained():
                    break
                try:
                    await asyncio.wait_for(ev.wait(),
                                           timeout=self.idle_wait_s)
                except asyncio.TimeoutError:
                    pass
        except BaseException as e:
            self._engine_exc = e
            for fut in self._futures.values():
                if not fut.done():
                    fut.set_exception(
                        RuntimeError(f"engine crashed: {e!r}"))
            self._futures.clear()
            raise
        return self.stats

    async def drain(self, poll_s: float = 0.001):
        """Wait (without closing) until nothing is queued or in flight."""
        while not self._drained():
            await asyncio.sleep(poll_s)


class DisaggEngine(BatchServer):
    """Disaggregated prefill/decode serving over the coherent KV pool —
    the composition of the paper's two killer apps on real traffic.

    The slot table is partitioned into a **prefill worker** range
    ``[0, prefill_slots)`` and a **decode worker** range
    ``[prefill_slots, prefill_slots + batch_slots)``; both workers share
    ONE ``KVBlockPager`` arena (the CXL-coherent pool), so prefix caching
    and near/far tiering span workers unchanged.  The prefill worker
    admits requests and runs the chunked bucketed prefill pipeline in its
    range; when a prompt is fully resident it parks the request in
    HANDOFF and, per request, claims a decode-slot RAO FAA ticket
    (``DECODE_TICKET_ADDR`` — its own counter word, serialized
    independently of the admission counter per core.rao's per-address
    guarantee), encodes a ``HANDOFF_SCHEMA`` wire message (ticket,
    block-table row, prompt metadata) through ``core.rpc``, and bills it
    via ``niccost.on_egress``.  The decode worker decodes the message
    (``on_ingress``), binds a slot in its own range from the ticket hint,
    and re-homes the pages with ``KVBlockPager.handoff`` — a pure
    metadata move over the coherent pool, billed by
    ``niccost.on_kv_handoff`` as CXL.cache coherent mapping vs the
    per-block PCIe DMA re-copy a non-coherent deployment would pay.

    Greedy decode is bit-identical to the monolith: f32 argmax outputs
    are batch-shape invariant (the differential harness's foundation), so
    moving a row between slots changes nothing the kernels compute.
    Backpressure is natural: with every decode slot busy, finished
    prefills wait in HANDOFF occupying their prefill slot, which in turn
    pauses admission — no token is ever dropped.
    """

    def __init__(self, model, *, batch_slots: int = 4,
                 prefill_slots: Optional[int] = None, **kw):
        # batch_slots sizes the decode worker (the monolith meaning: how
        # many requests decode concurrently); the prefill worker gets its
        # own range on top, defaulting to symmetric capacity
        self.decode_slots = int(batch_slots)
        self.prefill_slots = int(batch_slots if prefill_slots is None
                                 else prefill_slots)
        if self.prefill_slots < 1:
            raise ValueError(f"prefill_slots must be >= 1, got "
                             f"{self.prefill_slots}")
        if self.decode_slots < 1:
            raise ValueError(f"batch_slots must be >= 1, got "
                             f"{self.decode_slots}")
        super().__init__(model,
                         batch_slots=self.prefill_slots + self.decode_slots,
                         **kw)
        if not self.paged:
            raise ValueError("disaggregated serving requires the paged KV "
                             "plane (paged_kv) — the handoff moves pool "
                             "pages by block-table row")
        self._handoffs: Deque[Request] = deque()
        self.stats.update({"handoffs": 0, "handoff_blocks": 0,
                           "handoff_wire_bytes": 0})

    # ------------------------------------------------- worker partition
    def _ticket_hint(self, ticket: int) -> int:
        return ticket % self.prefill_slots

    def _bind_admit(self, req: Request) -> int:
        return self.table.bind(req, lo=0, hi=self.prefill_slots)

    def _admit_free(self) -> int:
        return self.table.free_in(0, self.prefill_slots)

    def _after_prefill(self, req: Request, now: float):
        # TTFT anchors here (the prefill worker emitted the token);
        # HANDOFF slots drop out of the engagement plan, so their pages
        # unpin and may demote while parked — promotion happens on the
        # decode side's next plan
        req.to(RequestState.HANDOFF, now)
        self._handoffs.append(req)

    # ----------------------------------------------------- wire handoff
    def _handoff_msg(self, req: Request, row: np.ndarray) -> Dict:
        return {1: req.req_id,
                2: req.decode_ticket,
                3: len(req.prompt),
                4: req.max_new,
                5: [int(t) for t in req.generated],
                6: [int(p) for p in row],
                7: self.family,
                8: "prefill->decode"}

    def _do_handoffs(self, now: float):
        """Drain HANDOFF-parked requests into free decode-worker slots,
        one wire message per request."""
        moved = False
        while self._handoffs and \
                self.table.free_in(self.prefill_slots, self.slots):
            req = self._handoffs.popleft()
            src = req.slot
            full_row = np.asarray(self.pager.block_table()[src])
            live = np.nonzero(full_row >= 0)[0]
            # occupied span: leading -1s are window-released blocks the
            # decode worker must keep masked dead at the same columns
            span = int(live[-1]) + 1 if live.size else 0
            # prefill worker: claim the decode slot ticket + publish
            req.decode_ticket = self.table.claim_ticket(DECODE_TICKET_ADDR)
            self._unbilled_tickets += 1
            msg = self._handoff_msg(req, full_row[:span])
            buf = wire.encode(msg)
            with self._span("niccost"):
                self.niccost.on_egress(msg)
            # decode worker: consume the message, bind in its own range,
            # map the same pool pages (zero KV bytes move)
            got = wire.decode(buf, HANDOFF_SCHEMA)
            with self._span("niccost"):
                self.niccost.on_ingress(got)
            self.table.release(src)
            req.slot = self.prefill_slots + got[2] % self.decode_slots
            dst = self.table.bind(req, lo=self.prefill_slots, hi=self.slots)
            n_live = self.pager.handoff(src, dst)
            with self._span("niccost"):
                self.niccost.on_kv_handoff(n_live, self.pager.block_bytes)
            new_row = np.asarray(self.pager.block_table()[dst])
            if _as_list(got.get(6, [])) != new_row[:span].tolist():
                raise RuntimeError(
                    f"handoff page-id mismatch for req {req.req_id}: wire "
                    f"{got.get(6)} != pager row {new_row[:span].tolist()}")
            req.to(RequestState.DECODE, now)
            self.stats["handoffs"] += 1
            self.stats["handoff_blocks"] += n_live
            self.stats["handoff_wire_bytes"] += len(buf)
            moved = True
        if moved:
            self._tier_dirty = True            # slot rows moved ranges


class AsyncDisaggEngine(AsyncBatchServer, DisaggEngine):
    """Asyncio front-end over the disaggregated engine (same MRO trick as
    AsyncBatchServer: the engine coroutine drives ``step``, which runs
    admission + prefill + handoff + decode per tick)."""
