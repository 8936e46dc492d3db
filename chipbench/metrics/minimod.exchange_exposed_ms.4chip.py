"""Exposed halo exchange per step: the device time of the traced window's
collective ops (the ``collective-permute``s that ``ompx_put`` lowers to)
during which no compute op runs on the same chip, per traced step, in ms.
Averaged over the chips, as the trace reduction averages it.  Nothing to
read where the trace holds no collective op."""


def read(w):
    t, c = w.trace, w.counters
    if t is None or not c.get("traced_steps") or t.collective_s <= 0:
        return None
    return 1000.0 * t.exposed_collective_s / c["traced_steps"]
