"""Per cent of device 0's busy time in ops under no ``fed.`` scope: the
driver's scan bookkeeping, copies XLA inserts, and any phase the program
leaves unnamed.  Read from the readers' own traced window
(``bench/scopes.py``); the largest unscoped ops are on standard error."""
from bench import scopes


def read(run):
    s = scopes.summary(run)
    if s is None or s.busy_s <= 0:
        return None
    return 100.0 * s.scope_s.get(scopes.UNSCOPED, 0.0) / s.busy_s
