"""niccost_us_per_tick.online: host time of the NIC cost model (every
``niccost.on_*`` call: ingress, egress, tickets, prefix shares),
``stats["niccost_wall_s"] / stats["ticks"]`` over the window, in us.
Nothing where the program has no ``niccost`` span."""


def read(w):
    ticks = w.delta("ticks")
    if not ticks or "niccost_wall_s" not in w.stats1:
        return None
    return 1e6 * w.delta("niccost_wall_s") / ticks
