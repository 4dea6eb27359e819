"""Share of a plan round's least time on the chip that the round took.

The least time is the larger of the round's counted FLOPs over the peak
FLOP/s and its counted HBM bytes over the peak bandwidth (counts from the
plan's shapes, ``round_counts`` in the configuration's file); the round
time is the traced window's host-clock seconds per round."""


def read(run):
    if run.summary is None:
        return None
    c = run.cell.counts()
    rounds = sum(run.work)
    if not rounds or "flops" not in c:
        return None
    least = max(c["flops"] / run.peaks["bf16_flops_per_s"],
                c["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (run.window_s / rounds)
