"""Device time per round under the scope ``ocean/p4_solve`` (the P4 solve
over the candidate prefixes) in the round program, in ms: own time of the
round program's ops whose ``op_name`` lies under that scope, over the round
program's runs, in the cell's traced probe (``bench/scopes.py``); None where
that trace is not whole."""
import scopes


def read(r):
    p = scopes.probe("online", r.conf)
    if p is None or p.main not in p.tables:
        return None
    runs = scopes.module_runs(p.trace, p.window, p.main)
    own = scopes.scope_seconds(p.trace, p.window, p.main, p.tables[p.main])
    if not runs or own is None:
        return None
    return 1e3 * scopes.under_total(own, "ocean/p4_solve") / runs
