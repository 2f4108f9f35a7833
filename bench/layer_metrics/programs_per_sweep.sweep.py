"""Device programs one ``GridEngine.run`` launches: the runs of every
compiled program (``XLA Modules`` events) that start inside a ``sweep``
span, per sweep, in the cell's traced probe (``bench/scopes.py``)."""
import scopes


def read(r):
    p = scopes.probe("sweep", r.conf)
    if p is None:
        return None
    return scopes.runs_per_span(p.trace, p.window, "sweep")
