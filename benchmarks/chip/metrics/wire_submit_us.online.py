"""wire_submit_us.online: mean host time of one ``submit_wire`` call
(wire decode, NIC cost hook, ticket, queue push) in the window."""

from chipbench import stats


def read(w):
    v = stats.in_window(w.spans, "submit", w.w0, w.w1)
    return 1e6 * sum(v) / len(v) if v else None
