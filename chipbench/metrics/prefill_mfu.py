"""Share (%) of the chip's peak FLOP/s in the prefill programs: the
operations the computed (unpadded, not reused) prompt tokens need, over
the prefill programs' device time in the trace."""


def read(run):
    t = run.trace
    if not t or t["prefill_s"] <= 0 or t["prefill_flops"] is None:
        return None
    return 100.0 * t["prefill_flops"] / (t["prefill_s"] * run.peaks["flops_per_s"])
