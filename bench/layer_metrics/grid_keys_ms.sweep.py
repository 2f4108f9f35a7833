"""Host time per sweep that ``GridEngine.run`` spends building its seeds and
PRNG keys, in ms: the mean duration of the program's ``grid/keys`` span in
the cell's traced probe (``bench/scopes.py``)."""
import scopes


def read(r):
    p = scopes.probe("sweep", r.conf)
    if p is None:
        return None
    keys = scopes.spans(p.trace, p.window, "grid/keys")
    return 1e3 * sum(s.end - s.start for s in keys) / len(keys) / 1e9 if keys else None
