"""token_select_ms.batch: host time of the greedy token selection after
a decode step (the logits copied to the host, then their argmax),
``stats["decode_select_wall_s"] / stats["decode_steps"]`` over the
window, in ms.  Nothing where the program has no ``decode.select`` span."""


def read(w):
    steps = w.delta("decode_steps")
    if not steps or "decode_select_wall_s" not in w.stats1:
        return None
    return 1e3 * w.delta("decode_select_wall_s") / steps
