"""Host ms of the control plane per decode iteration: the engines'
start/finish (EcoFreq select, admission, bookkeeping) and the routers'
decisions, from ``loopprof``, less the backend calls inside them (the
iterations, the P->D insert and the slot release, timed by the adapter)
and less the profiler's own start and stop.  Read in the traced run,
where ``loopprof`` is installed."""


def read(run):
    p, w = run.prof, run.wall
    steps = w.calls["decode"]
    if p is None or not steps:
        return None
    backend = sum(w.host_s[k] for k in ("prefill", "decode", "insert",
                                        "release"))
    ctrl = (p.start_total_s + p.finish_total_s + p.route_s - backend
            - w.paused_s)
    return 1e3 * max(0.0, ctrl) / steps
