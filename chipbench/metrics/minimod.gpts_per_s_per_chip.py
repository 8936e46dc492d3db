"""Grid-point updates per second per chip: every step of every call in
the window, over the window (from its start to the last call's end) and
the chips."""


def read(w):
    c = w.counters
    if not c.get("steps"):
        return None
    return c["steps"] * c["cells"] / w.window_s / c["chips"] / 1e9
