"""itl_p95_ms: 95th percentile (nearest rank) of every gap between
consecutive tokens of one request whose later token came in the window."""

from chipbench import stats


def read(w):
    v = stats.gaps(w.recs.values(), w.w0, w.w1)
    return 1e3 * stats.pct(v, 95) if v else None
