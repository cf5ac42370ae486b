"""gen_lag_p90_ms.online: 90th percentile of submit time minus due time
over the requests due in the window: how late the load loop ran."""

from chipbench import stats


def read(w):
    recs = stats.due_in(w.recs.values(), w.w0, w.w0 + w.due_s)
    lags = [r.submit_t - r.due for r in recs if r.submit_t == r.submit_t]
    return 1e3 * stats.pct(lags, 90) if lags else None
