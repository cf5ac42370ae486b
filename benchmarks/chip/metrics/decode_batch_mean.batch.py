"""decode_batch_mean.batch: slots decoded per decode step,
``stats["decode_tokens"] / stats["decode_steps"]`` over the window."""


def read(w):
    steps = w.delta("decode_steps")
    return w.delta("decode_tokens") / steps if steps else None
