"""Device time per round of every op that is not a Pallas kernel, in ms: on
the default path the whole of ``ocean_round`` (ranking, the bisect P4 solve,
energy, queue update), over the rounds of the part of the traced window
that kept every device op (``harness.reduce_trace``)."""


def read(r):
    if r.reduced is None or not r.units:
        return None
    return 1e3 * (r.reduced.busy_s - r.reduced.kernel_s) / r.units
