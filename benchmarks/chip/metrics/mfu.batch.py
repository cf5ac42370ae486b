"""mfu.batch: useful model FLOPs of the window (decoded tokens with the
top-k experts they route to, prefilled prompt positions) over the
window's seconds times the chip's peak, in percent."""


def read(w):
    f = w.model_flops()
    return 100.0 * f / (w.seconds * float(w.peak["bf16_flops_per_s"])) \
        if f else None
