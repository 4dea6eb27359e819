"""Device milliseconds a round on device 0 under the program's
``fed.server`` scope: the aggregation of the messages, the direction,
and the update of w, h and the bit ledgers.  Read from the traced window
of the scope readers (``bench/scopes.py``); nothing where the program
has no scopes."""
from bench import scopes


def read(run):
    return scopes.scope_ms(run, "fed.server")
