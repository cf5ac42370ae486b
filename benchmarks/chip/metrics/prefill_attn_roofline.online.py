"""prefill_attn_roofline.online: the chunked-prefill kernel's roofline
time for the prompt positions prefilled in the window (valid rows against
their causal keys; K/V of the prompt read once) over the device time of
its events in the trace, in percent."""

from chipbench import counts


def read(w):
    t = w.trace
    if t is None or not t.kernel_s.get("paged_prefill_attention"):
        return None
    flops = nbytes = 0
    for a, b, _ in w.prefill_spans():
        f, n = counts.prefill_attn(w.dims, a, b)
        flops, nbytes = flops + f, nbytes + n
    if not flops:
        return None
    least, _ = counts.roofline_s(flops, nbytes, w.peak)
    return 100.0 * least / t.kernel_s["paged_prefill_attention"]
