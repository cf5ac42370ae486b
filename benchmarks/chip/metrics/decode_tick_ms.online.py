"""decode_tick_ms.online: host time of one paged decode step and its
argmax, ``stats["decode_wall_s"] / stats["decode_steps"]`` over the
window (the timer ends when the logits reach the host)."""


def read(w):
    steps = w.delta("decode_steps")
    return 1e3 * w.delta("decode_wall_s") / steps if steps else None
