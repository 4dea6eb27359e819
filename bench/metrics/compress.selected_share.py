"""Per cent of the compressors' device time (``fed.compress``) spent in
the (message, family) branches that some grid point of the cell's plan
selects, taken from its traffic file: the useful share of the work the
family switch attempts.  Under a batched family id every branch runs at
every point; the other branches' results are thrown away."""
from bench import scopes


def read(run):
    s = scopes.summary(run)
    selected = scopes.selected_branches(run.cell.traffic)
    if s is None or selected is None:
        return None
    return scopes.selected_share(s, selected)
