"""prefill_mfu.online: useful model FLOPs of the prompt positions
prefilled in the window over the device time of the chunk-prefill
programs (those that run the prefill kernel) times the peak, in percent."""

from chipbench import counts


def read(w):
    t = w.trace
    if t is None or not t.prefill_program_s:
        return None
    f = sum(counts.prefill_flops(w.dims, a, b, p)
            for a, b, p in w.prefill_spans())
    return 100.0 * f / (t.prefill_program_s
                        * float(w.peak["bf16_flops_per_s"])) if f else None
