"""Device milliseconds a round on device 0 under the program's
``fed.oracle`` scope: the workers' local gradient, their m Hessian-
vector products and M = SᵀY.  Read from the traced window of the scope
readers (``bench/scopes.py``); nothing where the program has no scopes."""
from bench import scopes


def read(run):
    return scopes.scope_ms(run, "fed.oracle")
