"""Device milliseconds a round on device 0 under the program's ``ssm.ssd``
scope: the chunked SSD scan of every Mamba-2 layer, in the forward pass,
its remat recompute and its backward pass (``transpose(jvp(ssm.ssd))``).
Read from a traced window of its own (``bench/inner_scopes.py``); nothing
where the program has no such scope."""
from bench import inner_scopes


def read(run):
    return inner_scopes.scope_ms(run, "ssm.ssd")
