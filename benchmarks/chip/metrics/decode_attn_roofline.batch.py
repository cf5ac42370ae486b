"""decode_attn_roofline.batch: the paged decode kernel's roofline time
for the window's decode tokens (each live slot's resident K/V read once,
q, out and the new k/v; memory-bound at 4 FLOPs per byte of K/V) over the
device time of its events in the trace, in percent."""

from chipbench import counts


def read(w):
    t = w.trace
    if t is None or not t.kernel_s.get("paged_attention"):
        return None
    flops = nbytes = 0
    for p, i in w.decode_tokens():
        f, b = counts.decode_attn(w.dims, p + i - 1)
        flops, nbytes = flops + f, nbytes + b
    if not flops:
        return None
    least, _ = counts.roofline_s(flops, nbytes, w.peak)
    return 100.0 * least / t.kernel_s["paged_attention"]
