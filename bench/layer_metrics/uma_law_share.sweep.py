"""Share of the device's busy time spent under the scope ``env/uma_38901``
(the TR 38.901 UMa sector's law inside ``grid/sample_env``) in the sweep's
program, in %: own time of those ops over the union of every op's interval,
in the cell's traced probe (``bench/scopes.py``).  None where the program
has no such scope."""
import scopes

SCOPE = "env/uma_38901"


def read(r):
    p = scopes.probe("sweep", r.conf)
    if p is None or not any(scopes.under(s, SCOPE)
                            for s in p.tables.get(p.main, {}).values()):
        return None
    return scopes.busy_share(p, SCOPE)
