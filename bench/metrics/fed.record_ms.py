"""Device milliseconds a round on device 0 under the program's
``fed.record`` scope: the scan's record of the objective every round.
Read from the traced window of the scope readers (``bench/scopes.py``);
nothing where the program has no scopes."""
from bench import scopes


def read(run):
    return scopes.scope_ms(run, "fed.record")
