"""Mean device-idle ms between consecutive decode programs of one
running batch (two decode steps that share a request), from the trace:
the time the device waits on the host between decode steps."""


def read(run):
    t = run.trace
    if not t or not t["decode_gaps"]:
        return None
    return 1e3 * sum(t["decode_gaps"]) / len(t["decode_gaps"])
