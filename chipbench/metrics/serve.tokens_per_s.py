"""Output tokens generated in the window over the window's seconds."""


def read(w):
    n = w.counters.get("tokens_out")
    return n / w.window_s if n else None
