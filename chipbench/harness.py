"""One run of one cell: set up, open the window, measure, check.

Everything a cell is made of is found by name: its entry in
``BENCHMARK.json``, its configuration ``configs/<config>.json``, its
traffic mix ``traffic/<traffic>.json`` and a reader
``metrics/<metric>.py`` for each metric that ``BENCHMARK.json`` gives
the cell.  A new cell, configuration, mix or metric is new files and
new entries; nothing here names one.

The system under test is the program's served path, built as its
bring-up run builds it: ``PDCluster`` (policy ``voltana``, one prefill
and one decode instance on the chip) -> engines -> ``RealBackend``
(paged, from ``make_real_backend_factory``) -> the jitted paged prefill
and decode steps.  The wall-clock adapter (``wallclock.py``) puts the
cluster's clock on the host clock.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

import model
import reference
import stats
import traffic
import tracereduce
from wallclock import WallClock

HERE = Path(__file__).resolve().parent
WARM_RID = 1 << 30  # warm-up request ids start here


class RunError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, ...)."""


def log(tag: str, **kv) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's parts by name
# ---------------------------------------------------------------------------


def load_bench(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise RunError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, kind: str) -> list:
    """The ``kind`` (end_to_end / per_layer) metrics the cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str, root: Path = HERE):
    path = root / "metrics" / f"{name}.py"
    if not path.is_file():
        raise RunError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_of(kind: str, root: Path = HERE) -> dict:
    table = json.loads((root / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise RunError(f"device {kind!r} is not in peaks.json; add its "
                       "published peaks before measuring on it")
    return table[kind]


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cfg: dict
    mix: dict
    seconds: float
    counted: list  # traffic Items counted in attempted / the tails
    wall: WallClock
    setup_s: float
    prof: object = None  # loopprof.LoopProfile in the traced run
    trace: Optional[dict] = None
    peaks: Optional[dict] = None


def build_cluster(c: dict, mcfg, weights, mix: dict, wall: WallClock,
                  seed: int):
    """PDCluster 1P+1D over paged RealBackends, on the wall clock.  The
    prefill instance's pool holds the largest prompt (it keeps no pages
    without a radix cache); the decode instance's pool is the file's."""
    from repro.core.power import TPU_V5E
    from repro.serving import ClusterConfig, PDCluster
    from repro.serving.cluster import build_predictor
    from repro.serving.realengine import make_real_backend_factory

    s = c["serving"]
    ps, slots = int(s["page_size"]), int(s["slots"])
    pages = {"prefill": int(s["prefill_pool_pages"]),
             "decode": int(s["decode_pool_pages"])}
    real = {kind: make_real_backend_factory(
        mcfg, weights, slots=slots, max_len=int(s["max_len"]), paged=True,
        page_size=ps, pool_pages=n) for kind, n in pages.items()}

    def factory(kind, idx, hw, seed, tp=None):
        return real[kind](kind, idx, hw, seed, tp=tp)

    predictor = build_predictor(
        mcfg, TPU_V5E, TPU_V5E.freq_levels_2,
        kv_cap=pages["decode"] * ps, max_running=slots, seed=0)
    ccfg = ClusterConfig(
        model=mcfg, chip=TPU_V5E, n_prefill=1, n_decode=1,
        policy="voltana", paged=True, kv_page_size=ps,
        decode_max_running=slots, kv_capacity_tokens=pages["decode"] * ps,
        prefix_cache=bool(mix.get("prefix_cache", False)),
        predictor=predictor, online_adapt=False, seed=seed,
        # one chip holds both instances: the handoff costs what insert
        # really takes on the host clock, so no modelled transfer delay
        transfer_const_s=0.0, transfer_bw=math.inf,
        backend_factory=wall.wrap_factory(factory),
    )
    cluster = PDCluster(ccfg)
    wall.attach(cluster)
    return cluster


def requests_of(items, vocab: int):
    from repro.serving.request import Request

    return [Request(rid=it.rid, arrival_s=it.due_s,
                    prompt_len=len(it.prompt), decode_len=it.output - 1,
                    prompt_tokens=[int(t) for t in it.prompt])
            for it in items]


def warm_up(cluster, mix: dict, c: dict, seed: int) -> int:
    """Serve one short request per page count the mix can hand off:
    every prefill bucket, every handoff gather and insert shape, the
    decode step and the release, off the clock.  Two at a time: the
    prefill instance does not wait for decode slots, so requests sent
    together leave their handed-off pages waiting on the device."""
    lens = traffic.warmup_lengths(mix, int(c["serving"]["page_size"]))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    V = int(c["vocab_size"])
    items = [traffic.Item(WARM_RID + i, 0.0,
                          rng.integers(0, V, n, dtype=np.int32), 2)
             for i, n in enumerate(lens)]
    reqs = requests_of(items, V)
    for i in range(0, len(reqs), 2):
        cluster.run(reqs[i: i + 2])
    bad = [r.rid for r in reqs if not r.finished]
    if bad:
        raise RunError(f"warm-up requests unfinished: {bad[:5]}")
    return len(reqs)


class CompileCount:
    """XLA backend compiles, counted while ``on``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if self.on and event == self.EVENT:
            self.n += 1


class TraceWindow:
    """Starts the profiler at the first backend call ``start_s`` into
    the window and stops it ``seconds`` later, draining the device at
    both ends so that the trace holds whole step programs only."""

    def __init__(self, wall: WallClock, start_s: float, seconds: float):
        self.wall, self.start_s, self.seconds = wall, start_s, seconds
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.state = "before"
        self.host_s = 0.0

    def _sync(self) -> None:
        import jax

        jax.block_until_ready([getattr(b, "kvcache", None)
                               for _, b in self.wall.backends])

    def __call__(self, t: float) -> None:
        if self.state == "before" and t >= self.start_s:
            self.wall.pause(self._start)
        elif self.state == "on" and t >= self.start_s + self.seconds:
            self.stop()

    def _start(self) -> None:
        import jax

        self._sync()
        # no Python tracer: it records every Python call of the serving
        # loop, which slows the loop while the trace runs
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.wall.dispatches = []
        self.wall.log_dispatches = self.wall.annotate = True
        self.state, self.t_on = "on", time.perf_counter()

    def _stop(self) -> None:
        import jax

        self._sync()
        self.wall.log_dispatches = self.wall.annotate = False
        jax.profiler.stop_trace()
        self.host_s = time.perf_counter() - self.t_on
        self.state = "done"

    def stop(self) -> None:
        if self.state == "on":
            self.wall.pause(self._stop)

    def read(self, c: dict, peaks: dict) -> Optional[dict]:
        if self.state != "done":
            return None
        ev = tracereduce.extract(self.dir)
        log("trace-planes", **{p: "/".join(ls) for p, ls in ev["planes"]})
        names: dict = {}
        for m in ev["modules"]:
            names[m[0]] = names.get(m[0], 0) + 1
        log("trace-modules", **dict(sorted(names.items(),
                                           key=lambda x: -x[1])[:12]))
        out = tracereduce.reduce(ev, self.wall.dispatches, c, peaks)
        out["window_s"] = out["span_s"]
        out["device_plane"] = ev["device"]
        return out

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def free_device_state(cluster, reqs) -> None:
    """Drop the pools and in-flight handoffs (the weights stay, for the
    reference), so that the check runs on a chip the program left."""
    import jax

    for e in cluster.prefill + cluster.decode:
        for x in jax.tree.leaves(e.backend.kvcache):
            x.delete()
    for r in reqs:
        if r.kv_handoff is not None:  # (page stack, length) when paged
            for x in jax.tree.leaves(r.kv_handoff):
                if isinstance(x, jax.Array):
                    x.delete()
            r.kv_handoff = None
    gc.collect()


# ---------------------------------------------------------------------------
# The output check
# ---------------------------------------------------------------------------


def sample_finished(reqs, check_tokens: int, check_requests: int,
                    seed: int):
    """The longest finished request and then others drawn from the seed,
    until ``check_tokens`` served tokens and ``check_requests`` requests
    are in the sample."""
    done = [r for r in reqs if r.finished]
    if not done:
        return []
    done.sort(key=lambda r: (-(r.prompt_len + len(r.output_tokens)), r.rid))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 4]))
    pick = [done[0]] + [done[1:][i] for i in rng.permutation(len(done) - 1)]
    out, n = [], 0
    for r in pick:
        out.append(r)
        n += len(r.output_tokens)
        if n >= check_tokens and len(out) >= check_requests:
            break
    return out


def control_tokens(mcfg, weights, sample):
    """The control: the program's own int8 weight path (per-channel int8
    weights, bf16 activations; one precision below the served bf16) run
    over the same prompts and served tokens; the token it puts first at
    each position."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    def quant(name, x):  # leaf by leaf: one float32 copy at a time
        return jax.jit(lambda y: M.quantize_params({name: y})[name])(x)

    qblocks = {"layer_0": {
        grp: {k: quant(k, v) if v.ndim >= 3 else v
              for k, v in weights["blocks"]["layer_0"][grp].items()}
        for grp in ("attn", "mlp")}}
    qparams = dict(weights, blocks=qblocks)
    out = []
    for r in sample:
        seq = np.asarray(list(r.prompt_tokens) + r.output_tokens[:-1],
                         np.int32)
        T = reference.bucket(len(seq))
        toks = np.zeros((1, T), np.int32)
        toks[0, : len(seq)] = seq
        P, n = r.prompt_len, len(r.output_tokens)
        fn = _control_fn(mcfg, T)
        ids = np.asarray(fn(qparams, jnp.asarray(toks)))[0]
        out.append(ids[P - 1: P - 1 + n])
    return out


_CONTROL = {}


def _control_fn(mcfg, T: int):
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    key = (mcfg, T)
    if key not in _CONTROL:
        def fn(params, tokens):
            h, _ = M.forward(params, mcfg, tokens=tokens)
            ids = []
            for i in range(0, T, reference.CHUNK):
                lg = M.lm_logits(params, mcfg, h[:, i: i + reference.CHUNK])
                ids.append(jnp.argmax(lg, axis=-1))
            return jnp.concatenate(ids, axis=1)

        _CONTROL[key] = jax.jit(fn)
    return _CONTROL[key]


def gap_numbers(gaps) -> dict:
    """The widest gap, the mean gap and the share of tokens that are not
    the reference's first choice."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not g.size:
        return {"sample_tokens": 0}
    return {"sample_tokens": int(g.size), "gap_max": float(g.max()),
            "gap_mean": float(g.mean()),
            "miss_share": float(np.mean(g > 0.0))}


def check(c: dict, weights, reqs, mix: dict, seed: int, *, mcfg=None,
          control: bool = False) -> dict:
    """The numbers compared with their limits (``check`` in the config),
    from the reference's gaps of a seeded sample of finished requests;
    with ``control``, also the control's numbers under ``"control"``,
    read at the same positions."""
    V = int(c["vocab_size"])
    bad = [r.rid for r in reqs if r.finished
           and (len(r.output_tokens) != r.decode_len + 1
                or not all(0 <= t < V for t in r.output_tokens))]
    sample = sample_finished(reqs, int(mix["check_tokens"]),
                             int(mix["check_requests"]), seed)
    alts = control_tokens(mcfg, weights, sample) if control else \
        [None] * len(sample)
    gs, ga = [], []
    for r, alt in zip(sample, alts):
        a, b = reference.served_gaps(c, weights, r.prompt_tokens,
                                     r.output_tokens, alt)
        gs.append(a)
        ga.append(b)
    served = [t for r in sample for t in r.output_tokens]
    res = {"sample_requests": len(sample),
           "distinct_tokens": len(set(served)),
           "malformed": len(bad), **gap_numbers(gs)}
    if control:
        res["control"] = {"malformed": 0, **gap_numbers(ga)}
    return res


def verdict(c: dict, res: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number within its
    limit, a sample to compare, and no malformed stream."""
    limits = c["check"]
    shown = {k: {"value": res.get(k, math.inf), "limit": float(v)}
             for k, v in limits.items()}
    shown["malformed"] = {"value": res["malformed"], "limit": 0}
    ok = (res["sample_tokens"] > 0
          and all(v["value"] <= v["limit"] for v in shown.values()))
    return ok, shown


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_proc: float, root: Path = HERE.parent,
             require_tpu: bool = True, config: Optional[dict] = None,
             mix: Optional[dict] = None, peaks: Optional[dict] = None,
             control: bool = False,
             patch_backends: Optional[Callable] = None,
             warm: bool = True) -> dict:
    """Run the cell; returns the result object.  ``config``, ``mix``,
    ``peaks`` and ``patch_backends`` stand in for the files, the peak
    table and the program's steps in CPU tests; ``control`` adds the
    control's numbers to the check; ``warm=False`` skips the warm-up
    where an earlier run in this process compiled every shape."""
    import jax

    gc.collect()  # a previous run's device arrays in this process
    bench = load_bench(root)
    cell = cell_of(bench, cell_name)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise RunError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < int(cell["chips"]):
        raise RunError(f"the cell needs {cell['chips']} chips, JAX found "
                       f"{len(devs)}")
    if peaks is None:
        peaks = peaks_of(devs[0].device_kind)
    log("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs), jax=jax.__version__)

    c = config or model.load(cell["config"], HERE)
    mix = mix or traffic.load(cell["traffic"], HERE)
    e2e = metrics_of(bench, cell_name, "end_to_end")
    layer = metrics_of(bench, cell_name, "per_layer")
    readers = {m["name"]: reader(m["name"])
               for m in (layer if trace else e2e)}

    from repro.serving import loopprof

    compiles = CompileCount()
    mcfg = model.program_config(c)
    t = time.perf_counter()
    weights = model.make_weights(c, seed)
    log("weights", model=c["name"], bytes=model.tree_bytes(weights),
        wall_s=f"{time.perf_counter() - t:.2f}")
    wall = WallClock()
    cluster = build_cluster(c, mcfg, weights, mix, wall, seed)
    if patch_backends is not None:
        patch_backends([b for _, b in wall.backends])
    t = time.perf_counter()
    n_warm = warm_up(cluster, mix, c, seed) if warm else 0
    log("warmup", requests=n_warm, wall_s=f"{time.perf_counter() - t:.2f}")

    items = traffic.generate(mix, seconds, seed, int(c["vocab_size"]))
    reqs = requests_of(items, int(c["vocab_size"]))
    prof = loopprof.install(cluster) if trace else None
    tw = None
    if trace:
        tw = TraceWindow(wall, float(mix["trace_start_s"]),
                         float(mix["trace_seconds"]))
        wall.after_call = tw
    for kind, b in wall.backends:
        b.pool.stats.peak_in_use = b.pool.in_use
    horizon = seconds + float(mix["drain_s"])
    gc.collect()
    compiles.on = True
    wall.start(reqs)
    setup_s = wall.t0 - t_proc
    try:
        m = cluster.run(reqs, max_time_s=horizon)
        end_s = wall.now()
        if tw is not None:
            tw.stop()
    finally:
        wall.stop()
        compiles.on = False
    window_compiles = compiles.n

    counted = items
    failed = sum(len(wall.stamps[it.rid]) < it.output for it in items)
    log("window", seconds=seconds, ran_s=f"{end_s:.3f}",
        due=len(items), attempted=len(counted), failed=failed,
        finished=sum(r.finished for r in reqs), compiles=window_compiles)
    run = Run(c, mix, seconds, counted, wall, setup_s, prof=prof,
              peaks=peaks)
    log("samples", ttft=len(stats.ttfts(run)),
        token_gaps=len(stats.token_gaps(run)),
        arrivals=len(wall.lag),
        tokens=sum(len(s) for s in wall.stamps.values()))
    if wall.lag:
        log("generator", lag_p50_ms=f"{1e3 * float(np.median(wall.lag)):.3f}",
            lag_max_ms=f"{1e3 * max(wall.lag):.3f}")
    s = m.summary()
    n_tok = sum(len(x) for x in wall.stamps.values())
    if n_tok:
        log("energy", modelled_j_per_token=f"{s['energy_j'] / n_tok:.4f}")
    log("host", **{k: f"{v:.3f}" for k, v in sorted(wall.host_s.items())},
        decode_calls=wall.calls["decode"])

    mem = devs[0].memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    log("memory", peak_bytes_in_use=peak, bytes_limit=mem.get("bytes_limit"))

    result = {"correct": False, "attempted": len(counted), "failed": failed}
    if trace:
        run.trace = tw.read(c, peaks)
        tw.close()
        if run.trace is not None:
            t_ = run.trace
            device["busy_s"] = t_["busy_s"]
            device["window_s"] = t_["window_s"]
            log("trace", plane=t_["device_plane"], busy_s=t_["busy_s"],
                window_s=t_["window_s"], step_modules=t_["n_step_modules"],
                dispatches=len(wall.dispatches), prefill=t_["n_prefill"],
                decode=t_["n_decode"], host_s=f"{tw.host_s:.3f}",
                pauses="/".join(f"{b - a:.3f}" for a, b in wall.pauses))
            result["breakdown"] = {"device_ops": t_["device_ops"],
                                   "idle_gaps": t_["idle_gaps"]}
    metrics = {}
    for mdef in (layer if trace else e2e):
        v = readers[mdef["name"]].read(run)
        if v is not None:
            metrics[mdef["name"]] = {"value": float(v), "unit": mdef["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if prof is not None:
        log("loopprof", **{k: v for k, v in prof.breakdown().items()
                           if isinstance(v, (int, float))})

    free_device_state(cluster, reqs)
    del cluster, m
    gc.collect()
    t = time.perf_counter()
    res = check(c, weights, reqs, mix, seed, mcfg=mcfg, control=control)
    ctl = res.pop("control", None)
    log("check", **res, wall_s=f"{time.perf_counter() - t:.2f}")
    ok, shown = verdict(c, res)
    result["correct"] = ok
    if ctl is not None:
        log("control", **ctl)
        ctl_ok, ctl_shown = verdict(c, ctl)
        # every number of both readings, compared or not, for control.py
        result["control"] = {"correct": ctl_ok, "numbers": ctl,
                             "sound_numbers": res}
    result["check"] = shown  # last: the numbers compared, with limits
    for x in jax.tree.leaves(weights):
        x.delete()
    return result


def print_result(result: dict) -> None:
    for k, v in result["check"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
