"""Plan rounds completed in the window, over the window (host clock).
Each call of the compiled plan advances every grid point by
``rounds_per_call`` rounds and ends in ``block_until_ready``."""


def read(run):
    if run.cell.counts().get("rounds_per_call") is None:
        return None
    return sum(run.work) / run.window_s
