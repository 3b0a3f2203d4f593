"""Share (%) of the chip's peak FLOP/s in the decode programs: the
operations the emitted tokens need (every layer's matmuls, the logits,
attention over each row's real context), over the decode programs'
device time in the trace."""


def read(run):
    t = run.trace
    if not t or t["decode_s"] <= 0:
        return None
    return 100.0 * t["decode_flops"] / (t["decode_s"] * run.peaks["flops_per_s"])
