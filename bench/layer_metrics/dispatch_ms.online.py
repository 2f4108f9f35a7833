"""Time per round from the start of the ``solve`` span (host clock) to the
start of the round program's run on the device (its ``XLA Modules`` event),
in ms: the mean over the rounds of the cell's traced probe
(``bench/scopes.py``).  The device's events are put on the host's clock by
one offset per trace, so the reading holds that offset's error; it reads
None where a round's run does not start inside its span."""
import scopes


def read(r):
    p = scopes.probe("online", r.conf)
    if p is None or p.main is None:
        return None
    gaps, dropped = scopes.launch_gaps(p.trace, p.window, "solve", p.main)
    return 1e3 * sum(gaps) / len(gaps) if gaps and not dropped else None
