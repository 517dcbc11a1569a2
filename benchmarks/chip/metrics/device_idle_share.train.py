"""Share of the traced training window in which no operation ran on the
device: 1 - (union of device op intervals) / window, averaged over the
chips used."""


def read(r):
    t = r.trace
    return 100.0 * (1.0 - t.busy_s / t.window_s) if t.window_s > 0 else None
