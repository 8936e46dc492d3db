"""Output tokens per device call of the engine (chunk prefills and decode
steps), from its counters over the window."""


def read(w):
    calls = w.counters.get("device_calls")
    return w.counters["tokens_out"] / calls if calls else None
