"""admit_ms_per_tick.online: the engine's admission time per tick,
``stats["admit_wall_s"] / stats["ticks"]`` over the window."""


def read(w):
    ticks = w.delta("ticks")
    return 1e3 * w.delta("admit_wall_s") / ticks if ticks else None
