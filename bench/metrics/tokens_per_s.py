"""Global-batch tokens of the steps completed in the window, over the
window (host clock)."""


def read(run):
    if run.cell.counts().get("tokens_per_step") is None:
        return None
    return sum(run.work) / run.window_s
