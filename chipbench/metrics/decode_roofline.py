"""Share (%) of its roofline the decode step reaches: per step the least
time the chip could take (the larger of needed FLOPs over peak FLOP/s
and needed bytes over peak bandwidth; needed bytes are the weights, the
K/V of each row's real context and the K/V written), summed over the
traced steps, over their device time."""


def read(run):
    t = run.trace
    if not t or t["decode_s"] <= 0:
        return None
    return 100.0 * t["decode_least_s"] / t["decode_s"]
