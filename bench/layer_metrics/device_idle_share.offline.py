"""Share of the traced window in which no op ran on the device, in %:
1 - (union of the device's op intervals) / (window), averaged over chips,
over the part of the window that kept every device op
(``harness.reduce_trace``)."""


def read(r):
    if r.reduced is None or r.reduced.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.reduced.busy_s / r.reduced.window_s)
