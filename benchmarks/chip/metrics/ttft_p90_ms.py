"""ttft_p90_ms: 90th percentile (nearest rank) of time to first token
over every request due in the window, from its due time; a failed or
unanswered request counts as infinitely late."""

from chipbench import stats


def read(w):
    due_end = w.w0 + w.due_s
    v = stats.ttfts(w.recs.values(), w.w0, due_end)
    return 1e3 * stats.pct(v, 90) if v else None
