"""Arithmetic that several metric readers share."""


def idle_share(w):
    """100 x (1 - busy / window) of the traced window, busy being the
    union of device op intervals, averaged over the chips."""
    t = w.trace
    if t is None or t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
