"""Share of the traced window, in percent, in which no operation ran on
the device: 100 * (1 - busy / window), busy being the union of the
device operations' intervals (averaged over the cell's chips)."""


def read(r):
    if not r.trace.device or r.trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_ns() / r.trace.window_ns)
