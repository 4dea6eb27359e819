"""Device milliseconds a round on device 0 under the program's
``fed.compress`` scope: the gradient- and Hessian-message compressors
(``fed.compress.grad`` and ``fed.compress.hess``), every branch the
family switch runs.  Read from the traced window of the scope readers
(``bench/scopes.py``); nothing where the program has no scopes."""
from bench import scopes


def read(run):
    return scopes.scope_ms(run, "fed.compress")
