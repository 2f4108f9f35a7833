"""Share of the device's busy time spent under the scope ``grid/sample_env``
(the channel, budget and radio draws of every cell) in the sweep's program,
in %: own time of those ops over the union of every op's interval, in the
cell's traced probe (``bench/scopes.py``)."""
import scopes


def read(r):
    p = scopes.probe("sweep", r.conf)
    return None if p is None else scopes.busy_share(p, "grid/sample_env")
