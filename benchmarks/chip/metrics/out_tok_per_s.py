"""out_tok_per_s: every output token emitted inside the window, finished
requests or not, over the window's seconds (host clock)."""

from chipbench import stats


def read(w):
    return stats.tokens_in(w.recs.values(), w.w0, w.w1) / w.seconds
