"""The traffic generator is deterministic per seed, gives every seed the
same sizes in another order, and matches the distributions its files
state."""
import json
from statistics import NormalDist

import numpy as np
import pytest

from chipbench import traffic
from chipbench.spec import BENCH_DIR

UNIFORM = {"dist": "uniform", "lo": 64, "hi": 256}
LOGNORMAL = {"dist": "lognormal", "median": 1024, "sigma": 0.5,
             "lo": 256, "hi": 2048}
POISSON = {"arrivals": "poisson", "rate_rps": 2.0, "warm_s": 5, "slots": 4,
           "max_len": 2304, "strata": 16, "prompt_tokens": LOGNORMAL,
           "output_tokens": {"dist": "uniform", "lo": 32, "hi": 128}}


def _lens(reqs):
    return [len(r.prompt) for r in reqs], [r.max_new for r in reqs]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, -3, 2**70])
def test_same_seed_same_requests(seed):
    a = traffic.generate(POISSON, 1000, seed, 10.0, 5.0)
    b = traffic.generate(POISSON, 1000, seed, 10.0, 5.0)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.req_id, x.due, x.max_new) == (y.req_id, y.due, y.max_new)
        assert np.array_equal(x.prompt, y.prompt)
        assert x.prompt.dtype == np.int32
        assert 1 <= x.prompt.min() and x.prompt.max() < 1000


def test_seeds_share_sizes_in_another_order():
    a = traffic.generate(POISSON, 1000, 1, 10.0, 5.0)
    b = traffic.generate(POISSON, 1000, 2, 10.0, 5.0)
    pa, oa = _lens(a)
    pb, ob = _lens(b)
    assert pa != pb
    for k in range(0, len(a) - 16 + 1, 16):      # every full stratum
        assert sorted(pa[k:k + 16]) == sorted(pb[k:k + 16])
        assert sorted(oa[k:k + 16]) == sorted(ob[k:k + 16])
    ga = np.diff([r.due for r in a])
    gb = np.diff([r.due for r in b])
    assert not np.allclose(ga, gb)


def test_uniform_quantiles():
    q = traffic.quantiles(UNIFORM, 193)
    assert q.min() == 64 and q.max() == 256
    assert sorted(set(q.tolist())) == list(range(64, 257))   # each once
    assert abs(q.mean() - 160) < 0.5


def test_lognormal_quantiles_median_and_clip():
    q = traffic.quantiles(LOGNORMAL, 1001)
    assert int(np.median(q)) == 1024
    assert q.min() >= 256 and q.max() == 2048
    # share clipped at the top: P(Z > ln 2 / 0.5)
    above = 1 - NormalDist().cdf(np.log(2) / 0.5)
    assert abs(np.mean(q == 2048) - above) < 0.01


def test_poisson_gaps_have_the_stated_rate():
    reqs = traffic.generate(POISSON, 1000, 3, 40.0, 10.0)
    due = np.array([r.due for r in reqs])
    assert due[0] == -5.0                       # arrivals start at -warm_s
    gaps = np.diff(due)
    for k in range(0, len(gaps) - 16, 16):
        block = np.diff(due[k:k + 17])
        assert abs(block.mean() - 0.5) < 1e-9   # 1 / rate, exactly
    # the shape is exponential: the median gap is ln 2 / rate
    assert abs(np.median(gaps) - np.log(2) / 2.0) < 0.05
    assert len(reqs) == traffic.n_requests(POISSON, 40.0, 10.0)


def test_backlog_requests_are_all_due_at_once():
    t = {"arrivals": "backlog", "requests": 48, "strata": 16, "max_len": 2304,
         "prompt_tokens": UNIFORM,
         "output_tokens": {"dist": "uniform", "lo": 512, "hi": 2048}}
    reqs = traffic.generate(t, 1000, 5, 10.0)
    assert len(reqs) == 48 and all(r.due == 0.0 for r in reqs)


def test_a_request_longer_than_max_len_is_refused():
    t = dict(POISSON, max_len=1000)
    with pytest.raises(ValueError):
        traffic.generate(t, 1000, 0, 10.0)


@pytest.mark.parametrize(
    "path", sorted((BENCH_DIR / "traffic").glob("*.json")),
    ids=lambda p: p.stem)
def test_committed_traffic_files_generate(path):
    t = json.loads(path.read_text())
    reqs = traffic.generate(t, 49155, 11, 51.0)
    assert reqs and all(len(r.prompt) + r.max_new <= t["max_len"]
                        for r in reqs)
    assert int(t["sample_requests"]) >= 1


BACKLOG = {"arrivals": "backlog", "slots": 32, "max_len": 2304,
           "pending": 32, "requests": 64, "strata": 16,
           "prompt_tokens": UNIFORM,
           "output_tokens": {"dist": "uniform", "lo": 512, "hi": 2048}}


def test_staggered_backlog_starts_each_slot_at_another_stage():
    flat = traffic.generate(BACKLOG, 1000, 5, 10.0)
    stag = traffic.generate(dict(BACKLOG, staggered=True), 1000, 5, 10.0)
    k = BACKLOG["slots"]
    for a, b in zip(flat, stag):
        # the same request, a share of its answer moved into its prompt
        assert len(a.prompt) + a.max_new == len(b.prompt) + b.max_new
        assert b.max_new >= 1
    assert all(len(a.prompt) == len(b.prompt) for a, b in
               zip(flat[k:], stag[k:]))
    served = np.array([len(b.prompt) - len(a.prompt)
                       for a, b in zip(flat[:k], stag[:k])])
    share = served / np.array([a.max_new for a in flat[:k]])
    # shares spread over (0, 1): one in each eighth, none at the ends
    assert set(np.floor(share * 8).astype(int)) == set(range(8))
    # contexts at the start span most of max_len
    ctx = [len(b.prompt) for b in stag[:k]]
    assert min(ctx) < 400 and max(ctx) > 1800


def test_staggered_backlog_same_pairs_for_every_seed():
    k, pairs = BACKLOG["slots"], []
    for seed in (1, 2, 2**31 + 9):
        flat = traffic.generate(BACKLOG, 1000, seed, 10.0)[:k]
        stag = traffic.generate(dict(BACKLOG, staggered=True), 1000, seed,
                                10.0)[:k]
        # (answer, share of it served before the window), in another order
        pairs.append([(a.max_new, len(b.prompt) - len(a.prompt))
                      for a, b in zip(flat, stag)])
    assert pairs[0] != pairs[1]
    assert sorted(pairs[0]) == sorted(pairs[1]) == sorted(pairs[2])
