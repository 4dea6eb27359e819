"""Roofline share of the fused compressor kernels (dither and top-k).

Bytes the kernels must move per round, from their shapes (12 B per
element for dither: x, its uniforms, the output; 8 B for top-k: x and the
output), times the rounds traced, over the summed device time of the
kernels' events, against the peak HBM bandwidth.  Nothing is reported
when the trace holds no kernel event."""


def read(run):
    s = run.summary
    if s is None or not s.kernel_events.get("compressor"):
        return None
    secs = s.kernel_s["compressor"]
    if secs <= 0:
        return None
    moved = run.cell.counts()["kernel_bytes"] * sum(run.work)
    return 100.0 * moved / secs / run.peaks["hbm_bytes_per_s"]
