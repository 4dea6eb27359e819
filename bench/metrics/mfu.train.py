"""Model FLOP utilisation of the whole training step: the model FLOPs of
one round (``flops_per_round`` in the configuration's file: per token, 6
per matrix weight plus the SSD chunk contractions, remat's recompute not
counted, times the round's tokens) times the rounds per second of the
traced window, over the chips times their peak bf16 FLOP/s."""


def read(run):
    if run.summary is None or run.window_s <= 0:
        return None
    c = run.cell.counts()
    if "flops_per_round" not in c:
        return None
    rate = sum(run.work) / run.window_s
    return (100.0 * c["flops_per_round"] * rate
            / (c["chips"] * run.peaks["bf16_flops_per_s"]))
