"""host_ms_per_tick.batch: host time of one scheduler tick outside its
waits for the device, ``(tick - decode.wait - chunk.wait) / ticks`` from
the program's span seconds in ``stats`` over the window, in ms: in a
serial loop, about the device's idle time a tick.  Nothing where the
program has no such spans."""

KEYS = ("tick_wall_s", "decode_wait_wall_s", "chunk_wait_wall_s")


def read(w):
    ticks = w.delta("ticks")
    if not ticks or any(k not in w.stats1 for k in KEYS):
        return None
    tick, dwait, cwait = (w.delta(k) for k in KEYS)
    return 1e3 * (tick - dwait - cwait) / ticks
