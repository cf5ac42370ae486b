"""Paged KV data plane: kernel-vs-ref exactness, backend dispatch, model
paged-vs-dense decode, server end-to-end exactness, block-table churn.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, reduced
from repro.core import rpc as wire
from repro.kernels import dispatch as kd
from repro.kernels import ops, ref   # ops import populates the registry
from repro.kernels.paged_attention import paged_attention as raw_paged
from repro.models.model import build_model
from repro.models import transformer as tr
from repro.runtime.scheduler import KVBlockPager, Request
from repro.runtime.server import BatchServer

RNG = np.random.RandomState(1234)


def _rand_pool(B, H, K, hd, bt, nb, dtype, *, lens):
    """Random q/pool/new-token set with a shuffled block table covering
    ``lens`` tokens per slot (position order; unused entries -1)."""
    P = B * nb + 1
    q = jnp.asarray(RNG.randn(B, H, hd), dtype)
    kp = jnp.asarray(RNG.randn(P, K, bt, hd), dtype)
    vp = jnp.asarray(RNG.randn(P, K, bt, hd), dtype)
    kn = jnp.asarray(RNG.randn(B, K, hd), dtype)
    vn = jnp.asarray(RNG.randn(B, K, hd), dtype)
    perm = RNG.permutation(P - 1)
    btab = np.full((B, nb), -1, np.int32)
    j = 0
    for b, L in enumerate(lens):
        for i in range(-(-int(L) // bt) if L else 0):
            btab[b, i] = perm[j]
            j += 1
    return q, kp, vp, kn, vn, jnp.asarray(btab), jnp.asarray(lens, jnp.int32)


# ---------------------------------------------------------- kernel vs ref
@pytest.mark.parametrize("bt", [16, 64])
@pytest.mark.parametrize("H,K,hd", [(4, 2, 16), (4, 4, 32), (6, 2, 64)])
@pytest.mark.parametrize("window", [0, 24])
def test_paged_kernel_matches_ref(bt, H, K, hd, window):
    """Pallas kernel (interpret) vs the jnp oracle across ragged lengths:
    empty slot, exact block boundary, mid-block, full table."""
    B, nb = 4, 3
    lens = [0, bt, min(nb * bt - 1, bt + 5), nb * bt]
    q, kp, vp, kn, vn, btab, lens = _rand_pool(
        B, H, K, hd, bt, nb, jnp.float32, lens=lens)
    out = raw_paged(q, kp, vp, btab, lens, kn, vn, window=window,
                    interpret=True)
    exp = ref.paged_attention(q, kp, vp, btab, lens, kn, vn, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


def test_paged_kernel_bf16(
):
    B, nb, bt, H, K, hd = 3, 2, 16, 4, 2, 32
    lens = [3, bt, 2 * bt - 1]
    q, kp, vp, kn, vn, btab, lens = _rand_pool(
        B, H, K, hd, bt, nb, jnp.bfloat16, lens=lens)
    out = raw_paged(q, kp, vp, btab, lens, kn, vn, interpret=True)
    exp = ref.paged_attention(q, kp, vp, btab, lens, kn, vn)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=2e-2, rtol=2e-2)


# (H, K, hd) of mistral-nemo-12b and granite-moe-3b-a800m; at bt 16 the
# derived compute block is 8 pages (nemo; granite in f32) or 16 (granite
# bf16), so these cases span several blocks and end in a ragged one
NEMO, GRANITE = (32, 8, 128), (24, 8, 64)
F32_TOL, BF16_TOL = 1e-5, 2e-2


@pytest.mark.parametrize("heads,dtype,nb,lens,window", [
    (NEMO, jnp.float32, 11, [0, 128, 176], 0),        # nb % ppb != 0
    (NEMO, jnp.float32, 20, [16, 127, 129, 256], 0),  # page / block edges
    (NEMO, jnp.float32, 20, [0, 128, 257, 320], 100),  # window straddles
    (GRANITE, jnp.float32, 20, [0, 200, 300], 128),   # window = one block
    (GRANITE, jnp.float32, 20, [5, 129, 319], 1),     # only the new token
    (NEMO, jnp.bfloat16, 21, [130, 333], 0),
    (GRANITE, jnp.bfloat16, 21, [0, 257, 333], 0),
    (GRANITE, jnp.bfloat16, 21, [256, 300, 40], 40),
], ids=["nb-ragged", "edges", "window-straddle", "window-block",
        "window-1", "nemo-bf16", "granite-bf16", "granite-bf16-window"])
def test_paged_kernel_compute_blocks(heads, dtype, nb, lens, window):
    """Multi-page compute blocks vs the oracle: a table whose width is not
    a multiple of the block, lengths at page and block edges, empty slots,
    windows that start inside a block, scattered (shuffled) page ids, and
    the served models' head shapes in bf16."""
    from repro.kernels.paged_attention import pages_per_block
    H, K, hd = heads
    bt = 16
    ppb = pages_per_block(bt, K, hd, dtype)
    assert nb > ppb and max(lens) <= nb * bt
    q, kp, vp, kn, vn, btab, lens = _rand_pool(
        len(lens), H, K, hd, bt, nb, dtype, lens=lens)
    out = raw_paged(q, kp, vp, btab, lens, kn, vn, window=window,
                    interpret=True)
    exp = ref.paged_attention(q, kp, vp, btab, lens, kn, vn, window=window)
    tol = F32_TOL if dtype == jnp.float32 else BF16_TOL
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=tol, rtol=tol)


def test_paged_ref_matches_dense_gqa():
    """The ref oracle itself must agree with the dense GQA attention the
    cache path uses: rebuild each slot's dense KV from its pages."""
    from repro.models.layers import gqa_attention
    B, H, K, hd, bt, nb = 3, 4, 2, 16, 16, 3
    T = nb * bt
    lens = np.asarray([5, bt, T - 2], np.int32)
    kd_ = jnp.asarray(RNG.randn(B, T + 1, K, hd), jnp.float32)
    vd = jnp.asarray(RNG.randn(B, T + 1, K, hd), jnp.float32)
    q = jnp.asarray(RNG.randn(B, 1, H, hd), jnp.float32)
    P = B * nb + 1
    kp = np.zeros((P, K, bt, hd), np.float32)
    vp = np.zeros_like(kp)
    btab = np.full((B, nb), -1, np.int32)
    pid = 0
    for b in range(B):
        for i in range(-(-int(lens[b]) // bt)):
            btab[b, i] = pid
            s, e = i * bt, min((i + 1) * bt, int(lens[b]))
            kp[pid, :, :e - s] = np.asarray(kd_[b, s:e]).swapaxes(0, 1)
            vp[pid, :, :e - s] = np.asarray(vd[b, s:e]).swapaxes(0, 1)
            pid += 1
    kn = jnp.stack([kd_[b, int(lens[b])] for b in range(B)])
    vn = jnp.stack([vd[b, int(lens[b])] for b in range(B)])
    out = ref.paged_attention(q[:, 0], jnp.asarray(kp), jnp.asarray(vp),
                              jnp.asarray(btab), jnp.asarray(lens), kn, vn)
    for b in range(B):
        L = int(lens[b])
        exp = gqa_attention(q[b:b + 1], kd_[b:b + 1, :L + 1],
                            vd[b:b + 1, :L + 1],
                            q_pos=jnp.asarray([L]), causal=True)
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(exp[0, 0]),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------- chunked-prefill kernel vs ref
def _rand_chunk(B, C, H, K, hd, bt, nb, dtype, *, lens, dead_first=()):
    """Random chunk q / pool / chunk-kv set with a shuffled block table
    covering ``lens`` context tokens per slot; slots in ``dead_first`` get
    their leading block released (-1), as partial SWA reclamation does."""
    P = B * nb + 1
    q = jnp.asarray(RNG.randn(B, C, H, hd), dtype)
    kp = jnp.asarray(RNG.randn(P, K, bt, hd), dtype)
    vp = jnp.asarray(RNG.randn(P, K, bt, hd), dtype)
    kn = jnp.asarray(RNG.randn(B, C, K, hd), dtype)
    vn = jnp.asarray(RNG.randn(B, C, K, hd), dtype)
    perm = RNG.permutation(P - 1)
    btab = np.full((B, nb), -1, np.int32)
    j = 0
    for b, L in enumerate(lens):
        for i in range(-(-int(L) // bt) if L else 0):
            btab[b, i] = perm[j]
            j += 1
    for b in dead_first:
        btab[b, 0] = -1
    return q, kp, vp, kn, vn, jnp.asarray(btab), jnp.asarray(lens, jnp.int32)


class TestPagedPrefillKernel:
    @pytest.mark.parametrize("bt,C", [(16, 8), (16, 16), (64, 8)])
    @pytest.mark.parametrize("H,K,hd", [(4, 2, 16), (4, 4, 32)])
    @pytest.mark.parametrize("window", [0, 24])
    def test_matches_ref(self, bt, C, H, K, hd, window):
        """Pallas chunk-prefill kernel (interpret) vs the jnp oracle:
        empty context, block boundary, mid-block, full table; with a
        window, also a partially-released leading block."""
        from repro.kernels.paged_prefill_attention import (
            paged_prefill_attention as raw,
        )
        B, nb = 4, 3
        lens = [0, bt, bt + 5, nb * bt]
        dead = (3,) if window else ()    # freed block must stay masked
        q, kp, vp, kn, vn, btab, lens = _rand_chunk(
            B, C, H, K, hd, bt, nb, jnp.float32, lens=lens, dead_first=dead)
        out = raw(q, kp, vp, btab, lens, kn, vn, window=window,
                  interpret=True)
        exp = ref.paged_prefill_attention(q, kp, vp, btab, lens, kn, vn,
                                          window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=1e-5, rtol=1e-5)

    def test_ref_matches_one_shot_attention(self):
        """The chunk oracle must agree with dense causal GQA attention when
        the pages hold the first L tokens and the chunk holds the next C:
        query c attends pages[0:L] + chunk[0:c+1] at absolute positions."""
        from repro.models.layers import gqa_attention
        B, C, H, K, hd, bt, nb = 2, 8, 4, 2, 16, 16, 2
        T = nb * bt
        lens = np.asarray([5, T - 3], np.int32)
        kd_ = jnp.asarray(RNG.randn(B, T + C, K, hd), jnp.float32)
        vd = jnp.asarray(RNG.randn(B, T + C, K, hd), jnp.float32)
        q = jnp.asarray(RNG.randn(B, C, H, hd), jnp.float32)
        P = B * nb + 1
        kp = np.zeros((P, K, bt, hd), np.float32)
        vp = np.zeros_like(kp)
        btab = np.full((B, nb), -1, np.int32)
        pid = 0
        for b in range(B):
            for i in range(-(-int(lens[b]) // bt)):
                btab[b, i] = pid
                s, e = i * bt, min((i + 1) * bt, int(lens[b]))
                kp[pid, :, :e - s] = np.asarray(kd_[b, s:e]).swapaxes(0, 1)
                vp[pid, :, :e - s] = np.asarray(vd[b, s:e]).swapaxes(0, 1)
                pid += 1
        kn = jnp.stack([kd_[b, int(lens[b]):int(lens[b]) + C]
                        for b in range(B)])
        vn = jnp.stack([vd[b, int(lens[b]):int(lens[b]) + C]
                        for b in range(B)])
        out = ref.paged_prefill_attention(
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(btab),
            jnp.asarray(lens), kn, vn)
        for b in range(B):
            L = int(lens[b])
            exp = gqa_attention(q[b:b + 1], kd_[b:b + 1, :L + C],
                                vd[b:b + 1, :L + C],
                                q_pos=jnp.arange(L, L + C), causal=True)
            np.testing.assert_allclose(np.asarray(out[b]),
                                       np.asarray(exp[0]),
                                       atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------- dispatch
class TestKernelDispatch:
    def test_all_kernels_registered(self):
        assert {"flash_attention", "paged_attention",
                "paged_prefill_attention", "ssd_scan",
                "moe_gmm", "rao_scatter_add", "rmsnorm"} <= set(kd.names())

    def test_backends_agree(self):
        B, H, K, hd, bt, nb = 2, 4, 2, 16, 16, 2
        lens = [5, bt + 3]
        q, kp, vp, kn, vn, btab, lens = _rand_pool(
            B, H, K, hd, bt, nb, jnp.float32, lens=lens)
        args = (q, kp, vp, btab, lens, kn, vn)
        out_ref = kd.dispatch("paged_attention", "ref")(*args)
        out_int = kd.dispatch("paged_attention", "interpret")(*args)
        np.testing.assert_allclose(np.asarray(out_int), np.asarray(out_ref),
                                   atol=1e-5, rtol=1e-5)

    def test_default_backend_policy_off_tpu(self):
        assert jax.default_backend() != "tpu"   # this container
        assert kd.default_backend("paged_attention") == "ref"
        assert kd.default_backend("paged_prefill_attention") == "ref"
        assert kd.default_backend("rmsnorm") == "interpret"

    def test_unknown_kernel_and_backend_raise(self):
        with pytest.raises(KeyError, match="unknown kernel"):
            kd.dispatch("nope")
        with pytest.raises(ValueError, match="backend"):
            kd.dispatch("rmsnorm", "cuda")


# ------------------------------------------------- model paged vs dense
def _tiny(cfg_name="mistral-nemo-12b", **over):
    cfg = reduced(get_config(cfg_name)).replace(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=64, vocab=128, **over)
    return cfg, build_model(cfg)


class TestPagedModelVsDense:
    @pytest.mark.parametrize("bt", [16, 64])
    def test_paged_decode_matches_dense_ragged(self, bt):
        """lm_paged_decode_step vs per-slot dense lm_decode_step across
        ragged lengths, f32 end to end: <= 1e-5 agreement.  (At bf16 the
        comparison is batch-shape-sensitive at the ULP level and param
        init is salted per process — f32 keeps the bound deterministic.)"""
        cfg, model = _tiny(**F32)
        params = model.init(jax.random.PRNGKey(0))
        max_len = 2 * bt + 16
        lens = [5, bt, bt + 9]
        B = len(lens)
        pages = model.init_paged_cache(B, max_len, bt)
        nbmax = tr.paged_blocks(max_len, bt)
        btab = np.full((B, nbmax), -1, np.int32)
        free = list(RNG.permutation(B * nbmax))
        prompts = [RNG.randint(1, 127, size=l).tolist() for l in lens]
        dense = []
        for b, p in enumerate(prompts):
            _, cache = model.prefill(
                params, {"tokens": jnp.asarray([p], jnp.int32)}, None, None)
            dense.append(cache)
            nb = -(-len(p) // bt)
            ids = [free.pop() for _ in range(nb)]
            btab[b, :nb] = ids
            pages = model.paged_prefill_write(
                pages, cache["k"][:, :1], cache["v"][:, :1],
                jnp.asarray(ids, jnp.int32), len(p))
        tok = RNG.randint(1, 127, size=(B, 1)).astype(np.int32)
        lg_p, pages2 = model.paged_decode_step(
            params, pages, jnp.asarray(tok), jnp.asarray(btab),
            jnp.asarray(lens, jnp.int32))
        for b in range(B):
            c = dense[b]
            padT = max_len - c["k"].shape[2]
            dcache = {
                "k": jnp.pad(c["k"], ((0, 0), (0, 0), (0, padT),
                                      (0, 0), (0, 0))),
                "v": jnp.pad(c["v"], ((0, 0), (0, 0), (0, padT),
                                      (0, 0), (0, 0))),
                "cur": c["cur"]}
            lg_d, dc2 = model.decode_step(params, dcache,
                                          jnp.asarray(tok[b:b + 1]))
            np.testing.assert_allclose(np.asarray(lg_p[b]),
                                       np.asarray(lg_d[0]),
                                       atol=1e-5, rtol=1e-5)
            assert int(jnp.argmax(lg_p[b])) == int(jnp.argmax(lg_d[0]))
            # the new token's kv landed in the right page and matches
            # what the dense cache wrote at the same position
            blk, off = lens[b] // bt, lens[b] % bt
            got = pages2["kp"][:, btab[b, blk], :, off].astype(jnp.float32)
            want = dc2["k"][:, 0, lens[b]].astype(jnp.float32)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, rtol=1e-5)

    def test_trash_page_absorbs_inactive_slots(self):
        cfg, model = _tiny()
        params = model.init(jax.random.PRNGKey(0))
        bt, max_len, B = 16, 32, 2
        pages = model.init_paged_cache(B, max_len, bt)
        P = pages["kp"].shape[1]
        btab = np.full((B, 2), -1, np.int32)
        btab[0, 0] = 0                      # slot 0 active with 1 token
        lens = jnp.asarray([1, 0], jnp.int32)
        tok = jnp.asarray([[5], [0]], jnp.int32)
        lg, pages2 = model.paged_decode_step(params, pages, tok,
                                             jnp.asarray(btab), lens)
        assert np.all(np.isfinite(np.asarray(lg, np.float32)))
        # inactive slot wrote only to the trash page
        real = np.asarray(pages2["kp"][:, 1:P - 1], np.float32)
        assert float(np.abs(real).sum()) == 0.0


# -------------------------------------------------- server end-to-end
# f32 params + cache: greedy-token equality must not hinge on bf16 argmax
# near-ties flipping under batch-size-dependent XLA fusion
F32 = dict(param_dtype="float32", cache_dtype="float32")


def _sequential_ref(model, params, prompt, max_new, max_len):
    logits, cache = jax.jit(lambda p, b: model.prefill(p, b, None, max_len))(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    out = [int(jnp.argmax(logits[0]))]
    dec = jax.jit(lambda p, c, t: model.decode_step(p, c, t))
    for _ in range(max_new - 1):
        logits, cache = dec(params, cache,
                            jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    return out


def _drain_tokens(srv, reqs):
    for i, (p, m) in enumerate(reqs):
        srv.submit(Request(i, list(p), m))
    out = {}
    for buf in srv.run_until_drained():
        msg = wire.decode(buf, {1: "int", 2: "bytes"})
        out[msg[1]] = np.frombuffer(msg[2], np.int32).tolist()
    return out


class TestPagedServer:
    def test_ragged_continuous_admission_matches_sequential(self):
        """Paged engine (continuous admission, per-slot lengths) produces
        the sequential greedy tokens for ragged prompts — the dense engine
        can only do this in equal-length waves."""
        cfg, model = _tiny(**F32)
        params = model.init(jax.random.PRNGKey(3))
        prompts = [RNG.randint(1, 127, size=l).tolist()
                   for l in (4, 9, 5, 16, 3, 7)]
        max_new = 4
        srv = BatchServer(model, batch_slots=3, max_len=32, params=params,
                          nic_cost=None)
        assert srv.paged                     # auto-on for dense family
        got = _drain_tokens(srv, [(p, max_new) for p in prompts])
        for i, p in enumerate(prompts):
            assert got[i] == _sequential_ref(model, params, p, max_new, 32), i
        # all pages recycled
        pg = srv.kv_stats()["paged"]
        assert pg["pages_in_use"] == 0
        assert srv.kv_stats()["blocks_allocated"] > 0

    def test_sliding_window_paged_matches_sequential(self):
        """SWA config: paged masks the window over absolute positions; the
        dense path uses a ring cache.  Greedy tokens must agree, including
        prompts longer than the window (ring unpermute on one-shot
        admission).  Paged SWA is on under auto since partial pager
        release keeps the footprint O(window); paged_kv=False still opts
        out to the dense ring.  One-shot prefill here — the chunked
        pipeline's SWA equality lives in tests/test_differential.py."""
        cfg, model = _tiny("h2o-danube-3-4b", **F32)
        assert cfg.sliding_window > 0
        params = model.init(jax.random.PRNGKey(5))
        W = cfg.sliding_window
        prompts = [RNG.randint(1, 127, size=l).tolist()
                   for l in (W // 2, W, W + 5, 2 * W + 3)]
        max_new = 4
        max_len = 2 * W + 16
        assert not BatchServer(model, batch_slots=2, max_len=max_len,
                               params=params, nic_cost=None,
                               paged_kv=False).paged
        assert BatchServer(model, batch_slots=2, max_len=max_len,
                           params=params, nic_cost=None).paged
        srv = BatchServer(model, batch_slots=2, max_len=max_len,
                          params=params, nic_cost=None, paged_kv=True,
                          prefill_chunk=0)
        assert srv.paged
        got = _drain_tokens(srv, [(p, max_new) for p in prompts])
        for i, p in enumerate(prompts):
            assert got[i] == _sequential_ref(model, params, p, max_new,
                                             max_len), i

    def test_staggered_midflight_admission(self):
        """A request admitted while others are mid-decode (impossible for
        the dense attention engine unless lengths line up)."""
        cfg, model = _tiny(**F32)
        params = model.init(jax.random.PRNGKey(3))
        prompts = [RNG.randint(1, 127, size=l).tolist() for l in (6, 11, 4)]
        max_new = 5
        srv = BatchServer(model, batch_slots=3, max_len=32, params=params,
                          nic_cost=None)
        srv.submit(Request(0, prompts[0], max_new))
        srv.submit(Request(1, prompts[1], max_new))
        out = srv.step() + srv.step()
        srv.submit(Request(2, prompts[2], max_new))   # mid-decode, new len
        out += srv.run_until_drained()
        got = {}
        for buf in out:
            m = wire.decode(buf, {1: "int", 2: "bytes"})
            got[m[1]] = np.frombuffer(m[2], np.int32).tolist()
        for i, p in enumerate(prompts):
            assert got[i] == _sequential_ref(model, params, p, max_new, 32), i

    def test_overlong_prompt_fails_cleanly(self):
        cfg, model = _tiny(**F32)
        params = model.init(jax.random.PRNGKey(3))
        srv = BatchServer(model, batch_slots=2, max_len=16, params=params,
                          nic_cost=None)
        srv.submit(Request(0, [1] * 20, 4))     # > max_len: reject
        srv.submit(Request(1, [1, 2, 3], 2))
        got = _drain_tokens(srv, [])
        assert got[0] == []
        assert len(got[1]) == 2
        assert srv.stats["failed"] == 1

    def test_async_engine_paged(self):
        """AsyncBatchServer on the paged plane drains a ragged closed loop
        and recycles every page."""
        import asyncio
        from repro.runtime.server import AsyncBatchServer, encode_request

        cfg, model = _tiny(**F32)
        params = model.init(jax.random.PRNGKey(3))
        wires = [encode_request(i, RNG.randint(1, 127, size=l).tolist(), 3)
                 for i, l in enumerate((4, 9, 5, 12))]

        async def go():
            srv = AsyncBatchServer(model, batch_slots=2, max_len=32,
                                   params=params, nic_cost=None)
            assert srv.paged
            eng = asyncio.ensure_future(srv.run_engine())
            outs = await asyncio.gather(*[srv.submit_async(w)
                                          for w in wires])
            srv.close()
            await eng
            return srv, outs
        srv, outs = asyncio.run(go())
        assert len(outs) == 4
        assert srv.stats["completed"] == 4
        assert srv.kv_stats()["paged"]["pages_in_use"] == 0

    @pytest.mark.parametrize("arch", ["mistral-nemo-12b", "h2o-danube-3-4b"])
    def test_decode_page_counters(self, arch):
        """``decode_pages_shipped`` counts active slots x table columns of
        every decode step; ``decode_pages_live`` the pages that hold a
        resident token inside the window (what the kernel reads)."""
        cfg, model = _tiny(arch, **F32)
        params = model.init(jax.random.PRNGKey(3))
        window = cfg.sliding_window
        srv = BatchServer(model, batch_slots=3, max_len=64, params=params,
                          nic_cost=None)
        bt = srv.pager.block_tokens
        calls = []
        step = srv._paged_decode

        def recording(params, pages, last, btab, lens):
            calls.append((np.asarray(btab), np.asarray(lens)))
            return step(params, pages, last, btab, lens)
        srv._paged_decode = recording
        prompts = [RNG.randint(1, 127, size=n).tolist()
                   for n in (3, 17, 33, 9)]
        _drain_tokens(srv, [(p, 12) for p in prompts])
        assert calls
        shipped = live = 0
        for btab, lens in calls:
            active = np.flatnonzero(lens > 0)
            shipped += len(active) * btab.shape[1]
            for L in lens[active]:
                first = max(int(L) - window + 1, 0) if window else 0
                live += sum(1 for g in range(btab.shape[1])
                            if g * bt < L and (g + 1) * bt > first)
        assert srv.stats["decode_pages_shipped"] == shipped
        assert srv.stats["decode_pages_live"] == live
        assert 0 < live < shipped

    def test_moe_family_paged(self):
        cfg, model = _tiny("qwen3-moe-235b-a22b", **F32)
        assert cfg.family == "moe"
        params = model.init(jax.random.PRNGKey(2))
        prompts = [RNG.randint(1, 127, size=l).tolist() for l in (4, 6)]
        srv = BatchServer(model, batch_slots=2, max_len=16, params=params,
                          nic_cost=None)
        assert srv.paged
        got = _drain_tokens(srv, [(p, 3) for p in prompts])
        for i, p in enumerate(prompts):
            assert got[i] == _sequential_ref(model, params, p, 3, 16), i


# -------------------------------------------- block-table churn property
class TestBlockTableChurn:
    def _pager(self, slots=4, max_len=64, bt=16):
        return KVBlockPager(None, n_slots=slots, max_len=max_len,
                            block_tokens=bt, track_table=True,
                            footprint=(64, 0))

    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 3),      # slot
                              st.integers(1, 64),     # prompt tokens
                              st.integers(0, 12)),    # decode tokens
                    min_size=1, max_size=40))
    def test_release_reuse_invariants(self, ops_list):
        """Admission churn: pages are never double-owned, the free list
        plus live table rows always partition the pool, release returns
        exactly what admission+growth took."""
        p = self._pager()
        live = {}                                     # slot -> tokens
        for slot, toks, extra in ops_list:
            if slot in live:
                p.release(slot)
                del live[slot]
            ids = p.admit(slot, toks)
            assert len(ids) == -(-toks // p.block_tokens)
            total = min(toks + extra, p.max_len)
            p.advance(slot, total)
            live[slot] = total
            # invariants after every op
            rows = [np.asarray(p.block_table()[s][:p.resident_blocks(s)])
                    for s in live]
            used = np.concatenate(rows) if rows else np.empty(0, np.int32)
            assert len(set(used.tolist())) == len(used), "double-owned page"
            assert len(used) + p.free_pages == p.n_pages
            assert all(0 <= u < p.n_pages for u in used.tolist())
        for slot in list(live):
            p.release(slot)
        assert p.free_pages == p.n_pages
        assert (p.block_table() == -1).all()
        assert p.stats()["blocks_allocated"] == p.stats()["blocks_freed"]

    def test_lifo_reuse(self):
        p = self._pager(slots=2)
        ids = p.admit(0, 48)                          # 3 blocks
        p.release(0)
        ids2 = p.admit(1, 48)
        assert ids2 == ids                            # hottest-first reuse

    def test_capacity_overflow_raises(self):
        p = self._pager(slots=1, max_len=32, bt=16)
        p.admit(0, 32)
        with pytest.raises(MemoryError, match="exceeds"):
            p.advance(0, 33)
