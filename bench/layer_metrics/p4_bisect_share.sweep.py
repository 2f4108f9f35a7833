"""Share of the device's busy time spent under the scope
``ocean/p4_solve/bisect`` (OCEAN's bisection P4 solve) in the sweep's
program, in %: own time of those ops over the union of every op's interval,
in the cell's traced probe (``bench/scopes.py``)."""
import scopes


def read(r):
    p = scopes.probe("sweep", r.conf)
    return None if p is None else scopes.busy_share(p, "ocean/p4_solve/bisect")
