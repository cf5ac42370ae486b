"""device_idle.online: share of the traced window with no operation on
the device, 1 - busy union / window, in percent."""


def read(w):
    return None if w.trace is None else 100.0 * w.trace.idle_share
