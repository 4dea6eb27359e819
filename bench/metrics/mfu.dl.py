"""Model FLOP utilisation of the training step: the model FLOPs a token
needs (``flops_per_token`` in the configuration's file: 6 per matrix
weight plus the SSD contractions, remat's recompute not counted) times
the tokens per second of the traced window, over the chips times their
peak bf16 FLOP/s."""


def read(run):
    if run.summary is None:
        return None
    c = run.cell.counts()
    if "flops_per_token" not in c:
        return None
    rate = sum(run.work) / run.window_s
    return (100.0 * c["flops_per_token"] * rate
            / (c["chips"] * run.peaks["bf16_flops_per_s"]))
