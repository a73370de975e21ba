"""One run of one cell: set-up, the measured window, the check against
the reference, and the result line.

The served path is the program's own: ``ServeEngine`` with Q8_0 weights
and ``OffloadEngine()`` at platform defaults, driven through
``ContinuousBatchingScheduler`` (``submit``/``admit``/``decode_step``), the
entry ``launch/serve.py --continuous`` serves. The harness times every
request itself, from when it was due (open loop) or submitted (closed
loop), with the host clock around calls that end in a host sync.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from chip_bench import reference, spec, stats, trace_reduce, traffic, weights
from chip_bench.peaks import peaks

SOT = 1
DRAIN_S = 60.0          # how long past the window a due request may take


@dataclass
class Served:
    """The harness's record of one request."""
    index: int
    max_new: int
    mel: np.ndarray
    submit: float
    due: Optional[float] = None         # open loop: when it was due
    token_t: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    prefill_start: Optional[float] = None
    prefill_s: Optional[float] = None


@dataclass
class Run:
    """What the per-layer readers see of a run."""
    cell: dict
    cfg: object
    n_slots: int
    frames: int
    t0: float
    t1: float
    requests: Dict[int, Served]
    steps: List[Tuple[float, float, int]]     # (begin, end, active slots)
    admits: List[Tuple[float, float, int]]    # (begin, end, admitted)
    peaks: dict
    stretch: Optional[Tuple[float, float]] = None
    trace: Optional[dict] = None

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def in_stretch(self, t: float) -> bool:
        return self.stretch is not None and \
            self.stretch[0] <= t <= self.stretch[1]


def now() -> float:
    return time.perf_counter()


def build(cell: dict, seed: int):
    """The model configuration and the engine serving it, weights from
    ``seed``."""
    from repro.core.offload import OffloadEngine
    from repro.serve.engine import ServeEngine
    conf = cell["config"]
    cfg = spec.model_config(conf)
    params = weights.make_params(cfg, seed)
    weights.check_layout(cfg, params)
    # eos off: the weights are random, so the drawn length is the length
    engine = ServeEngine(cfg, params, max_len=conf["deployment"]["max_len"],
                         quant=conf["quant"], offload=OffloadEngine(),
                         eos_id=None)
    return cfg, engine


class Client:
    """Drives the scheduler and records what happened, in time order."""

    def __init__(self, sched, traced: bool):
        self.sched = sched
        self.records: Dict[int, Served] = {}
        self.steps: List[Tuple[float, float, int]] = []
        self.admits: List[Tuple[float, float, int]] = []
        self.ann = (jax.profiler.TraceAnnotation if traced
                    else lambda name: contextlib.nullcontext())

    def submit(self, req: traffic.Request, due: Optional[float]) -> None:
        t = now()
        rid = self.sched.submit(req.mel, max_new=req.max_new, sot_id=SOT)
        self.records[rid] = Served(req.index, req.max_new, req.mel, t, due)

    def cycle(self) -> None:
        """One admission pass, then one decode step over the slot pool."""
        with self.ann("bench.admit"):
            a = now()
            n = len(self.sched.admit())
            self.admits.append((a, now(), n))
        if not self.sched.n_active:
            return
        active = self.sched.n_active
        with self.ann("bench.decode_step"):
            a = now()
            events = self.sched.decode_step()
            b = now()
        self.steps.append((a, b, active))
        with self.ann("bench.bookkeeping"):
            for ev in events:
                self.records[ev.rid].token_t.append(b)

    def finish(self) -> None:
        """Copy the program's per-request results into the records."""
        for rid, res in self.sched.finished.items():
            r = self.records.get(rid)
            if r is not None:
                r.tokens = list(res.tokens)
                r.prefill_s = res.prefill_s
                r.prefill_start = r.submit + res.queue_wait_s


class CompileCount:
    """Counts the programs compiled, or fetched from the persistent
    cache, while it is open: a measured window should count none."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        return self

    def _seen(self, event, duration, **kw):
        if event in self.EVENTS:
            self.n += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._seen)


class GcPauses:
    """Times the interpreter's garbage collections while it is open."""

    def __enter__(self):
        self.pauses: List[float] = []
        self._t = None
        gc.callbacks.append(self._seen)
        return self

    def _seen(self, phase, info):
        if phase == "start":
            self._t = now()
        elif self._t is not None:
            self.pauses.append(now() - self._t)
            self._t = None

    def __exit__(self, *exc):
        gc.callbacks.remove(self._seen)


def closed_loop(d: Client, tr: traffic.Traffic, seconds: float,
                first: int = 0) -> Tuple[float, float, int]:
    """Keep the backlog ``tr.depth`` deep for ``seconds``, submitting
    backlog requests from index ``first``; returns the window and the
    next index."""
    nxt = first
    t0 = now()
    end = t0 + seconds
    while now() < end:
        with d.ann("bench.traffic"):
            while d.sched.n_queued < tr.depth:
                d.submit(tr.request(nxt), None)
                nxt += 1
        d.cycle()
    return t0, end, nxt


def open_loop(d: Client, tr: traffic.Traffic, seconds: float,
              drain: bool = True) -> Tuple[float, float, list]:
    """Submit each request when it is due, for ``seconds``; with
    ``drain``, go on until every request due is served (or ``DRAIN_S``
    has passed). Returns the window and how late each submit was."""
    reqs = tr.requests
    t0 = now() + 0.01
    late, i = [], 0
    deadline = t0 + seconds + (DRAIN_S if drain else 0.0)
    while now() < deadline:
        with d.ann("bench.traffic"):
            t = now()
            while i < len(reqs) and t0 + reqs[i].due <= t:
                d.submit(reqs[i], t0 + reqs[i].due)
                late.append(t - (t0 + reqs[i].due))
                i += 1
        if d.sched.n_queued or d.sched.n_active:
            d.cycle()
        elif i < len(reqs):
            with d.ann("bench.idle"):
                due = t0 + reqs[i].due
                if due - now() > 0.002:
                    time.sleep(due - now() - 0.001)
                while now() < due:
                    pass
        else:
            break
    return t0, t0 + seconds, late


def traced_stretch(d: Client, cont) -> Tuple[Tuple[float, float], dict]:
    """Profile ``cont()``, the cell's load carried on past the window,
    inside the ``bench.stretch`` span; returns the stretch's bounds and
    the trace's reduction. Starting and stopping the profiler stalls the
    host for seconds, so the measured window runs before it, untraced."""
    log_dir = tempfile.mkdtemp(prefix="chip_bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.STRETCH):
            a = now()
            cont()
            jax.block_until_ready(d.sched.pool.state)
            b = now()
    finally:
        jax.profiler.stop_trace()
    reduced = trace_reduce.reduce(trace_reduce.load(_xplane(log_dir)))
    shutil.rmtree(log_dir, ignore_errors=True)
    return (a, b), reduced


def end_to_end(run: Run, seconds: float) -> Dict[str, float]:
    """Every end-to-end metric the harness knows, from its own clock."""
    out = {}
    reqs = run.requests.values()
    toks = [t for r in reqs for t in r.token_t if run.in_window(t)]
    out["tokens_per_s"] = stats.rate(len(toks), seconds)
    gaps = [b - a for r in reqs for a, b in zip(r.token_t, r.token_t[1:])
            if run.in_window(b)]
    if gaps:
        out["token_gap_p95_ms"] = 1e3 * stats.percentile(gaps, 95)
    due = [r for r in reqs if r.due is not None and run.in_window(r.due)
           and len(r.token_t) == r.max_new]
    if due:
        out["ttft_p95_ms"] = 1e3 * stats.percentile(
            [r.token_t[0] - r.due for r in due], 95)
        out["latency_p95_ms"] = 1e3 * stats.percentile(
            [r.token_t[-1] - r.due for r in due], 95)
    return out


def host_line(run: Run, pauses: List[float]) -> str:
    """Where the window's longest host waits went: the longest admission
    pass (its batch-1 prefills run one after another), the longest decode
    step, and the garbage collections."""
    adm = max(((b - a, n) for a, b, n in run.admits if run.in_window(b)),
              default=(0.0, 0))
    step = max((b - a for a, b, _ in run.steps if run.in_window(b)),
               default=0.0)
    return (f"host: longest admit {1e3 * adm[0]:.1f} ms for {adm[1]} "
            f"requests, longest decode step {1e3 * step:.1f} ms; "
            f"{len(pauses)} garbage collections, longest "
            f"{1e3 * max(pauses, default=0.0):.1f} ms")


def sample(run: Run, seed: int, want_tokens: int, most: int) -> List[Served]:
    """Finished requests to check: the longest, then others drawn from
    the seed until ``want_tokens`` served tokens or ``most`` requests."""
    done = sorted((r for r in run.requests.values()
                   if r.tokens and len(r.tokens) == r.max_new
                   and run.in_window(r.token_t[-1] if r.due is None
                                     else r.due)),
                  key=lambda r: r.index)
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.tokens))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed % 2**64, 3]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for j in order:
        if n >= want_tokens or len(out) >= most:
            break
        out.append(rest[j])
        n += len(rest[j].tokens)
    return out


def reference_gaps(cfg, seed: int, picked: List[Served], frames: int,
                   pad_to: int, control_bits: Optional[int] = None):
    """Per sampled request, the gaps of its served tokens below the float32
    reference's best; with ``control_bits``, also the gaps of the tokens
    the reference with weights rounded to that many bits puts first."""
    p = weights.make_params(cfg, seed)
    pc = reference.quantized(p, control_bits) if control_bits else None
    served, control = [], []
    for r in picked:
        mel = np.zeros((frames, cfg.n_mels), np.float32)
        mel[:r.mel.shape[0]] = r.mel
        toks = np.asarray(r.tokens, np.int32)
        inp = np.zeros((pad_to,), np.int32)
        inp[0] = SOT
        inp[1:len(toks)] = toks[:-1]
        n = len(toks)
        ref = np.asarray(reference.logits(p, mel, inp, cfg.num_heads))[:n]
        served.append(reference.served_gaps(ref, toks, cfg.vocab_size))
        if pc is not None:
            ctl = np.asarray(reference.logits(pc, mel, inp,
                                              cfg.num_heads))[:n]
            first = ctl[:, :cfg.vocab_size].argmax(-1)
            control.append(reference.served_gaps(ref, first, cfg.vocab_size))
    return served, control


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, engine_hook: Optional[Callable] = None,
             control_bits: Optional[int] = None,
             report_programs: bool = False) -> dict:
    """Run ``cell`` once. Returns the result line's object and, under
    ``lines``, the lines for standard error; ``engine_hook(engine)`` lets
    a test break the served path underneath."""
    dev = jax.devices()[0]
    pk = peaks(dev.device_kind) if dev.platform == "tpu" else {}
    conf = cell["config"]
    n_slots = conf["deployment"]["n_slots"]
    cfg, engine = build(cell, seed)
    if engine_hook is not None:
        engine_hook(engine)
    frames = cfg.encoder_ctx
    sched = engine.scheduler(n_slots=n_slots, n_frames=frames)
    tr = traffic.Traffic(cell["traffic"], seed, seconds, n_slots, cfg.n_mels)
    lines = []

    # warm-up: one request through admission, the slot splice and the step
    warm = Client(sched, traced=False)
    warm.submit(tr.warm_request(), None)
    while sched.n_queued or sched.n_active:
        warm.cycle()
    sched.finished.clear()
    if tr.kind == "backlog":
        tr.prepare(traffic.expected_backlog_requests(
            cell["traffic"]["expected_tokens_per_s"], seconds,
            cell["traffic"]["out_tokens"]) + tr.depth)
    else:
        tr.prepare()
    jax.block_until_ready(sched.pool.state)
    setup_s = now() - t_start
    if report_programs:
        lines += program_memory(engine, sched, frames)
    if trace and tr.kind == "poisson":
        more = traffic.Traffic(cell["traffic"], seed + 2**40,
                               cell["trace"]["seconds"], n_slots, cfg.n_mels)
        more.prepare()

    d = Client(sched, traced=trace)
    with CompileCount() as compiles, GcPauses() as collections:
        if tr.kind == "backlog":
            t0, t1, nxt = closed_loop(d, tr, seconds)
            late = []
            attempted = len(d.records)
        else:
            t0, t1, late = open_loop(d, tr, seconds)
            attempted = len(tr.requests)
    bounds, reduced = None, None
    if trace:
        span = cell["trace"]["seconds"]
        bounds, reduced = traced_stretch(
            d, (lambda: closed_loop(d, tr, span, nxt)) if tr.kind == "backlog"
            else (lambda: open_loop(d, more, span, drain=False)))
    d.finish()
    mem = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for x in jax.devices())
    run = Run(cell, cfg, n_slots, frames, t0, t1, d.records, d.steps,
              d.admits, pk, bounds, reduced)
    lines.append(f"programs compiled inside the window: {compiles.n}")
    lines.append(host_line(run, collections.pauses))
    if late:
        lines.append(f"generator: {len(late)} requests, late p50 "
                     f"{1e3 * stats.percentile(late, 50):.3f} ms, max "
                     f"{1e3 * max(late):.3f} ms")
    else:
        lines.append(f"generator: closed loop, {tr.late} requests' audio "
                     f"made inside the window")
    unserved = sum(1 for r in d.records.values()
                   if r.due is not None and run.in_window(r.due)
                   and len(r.token_t) < r.max_new)

    metrics: Dict[str, dict] = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    breakdown = None
    if trace:
        if run.trace is not None:
            t = run.trace
            lines.append(f"trace: busy {t['busy_s']} s of {t['window_s']} s;"
                         f" programs {t['modules_s']}; kernels "
                         f"{t['kernel_s']}; idle by host span "
                         f"{t['idle_by_span']}")
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            breakdown = {"device_ops": run.trace["device_ops"],
                         "idle_gaps": run.trace["idle_gaps"]}
        for m in cell["per_layer"]:
            v = spec.reader(m["name"], cell["bench_dir"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(run, seconds)
        e2e["setup_s"] = setup_s
        for m in cell["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # the check, once the program's state is gone
    limits = cell["limits"]
    picked = sample(run, seed, limits["sample_tokens"],
                    limits["sample_requests"])
    del engine, sched, d, warm
    gc.collect()
    served, control = reference_gaps(cfg, seed, picked, frames,
                                      limits["pad_tokens"], control_bits)
    short = sum(1 for r in picked if len(r.tokens) != r.max_new)
    checks, correct = judge(served, unserved, short, limits)
    n_tok = sum(len(r.tokens) for r in picked)
    lines.append(f"checked {len(picked)} requests, {n_tok} served tokens, "
                 f"against the float32 reference")
    lines += [f"check {k}: {v['value']} (limit {v['limit']})"
              for k, v in checks.items()]
    result = {"correct": correct, "attempted": attempted,
              "failed": unserved, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    out = {"result": result, "lines": lines, "run": run}
    if control_bits:
        # the control's first choices in place of the served tokens,
        # judged by the same rule
        c_checks, c_correct = judge(control, unserved, short, limits)
        out["control"] = {"checks": c_checks, "correct": c_correct}
        out["control_gaps"] = control
        out["served_gaps"] = served
    return out


def judge(gaps: List[np.ndarray], unserved: int, short: int,
          limits: dict) -> Tuple[dict, bool]:
    """The compared numbers beside their limits, and ``correct``: every
    sampled token's gap below the reference's best within the limit, no
    request due in the window left unserved, none cut short."""
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    checks = {
        "logit_gap": {"value": widest, "limit": limits["max_logit_gap"]},
        "unserved": {"value": unserved, "limit": 0},
        "short": {"value": short, "limit": 0},
    }
    correct = (bool(gaps) and widest <= limits["max_logit_gap"]
               and unserved == 0 and short == 0)
    return checks, correct


def program_memory(engine, sched, frames: int) -> List[str]:
    """The compiled prefill's and decode step's ``memory_analysis()``."""
    import jax.numpy as jnp
    n = sched.n_slots
    mel = jnp.zeros((1, frames, engine.cfg.n_mels), jnp.float32)
    progs = {"prefill": (engine._prefill_jit, (engine._serve_params, mel)),
             "step": (engine._step_jit, (
                 engine._serve_params, jnp.zeros((n, 1), jnp.int32),
                 jnp.zeros((n,), bool), sched.pool.state))}
    out = []
    for name, (fn, args) in progs.items():
        m = fn.lower(*args).compile().memory_analysis()
        out.append(f"program {name}: arguments {m.argument_size_in_bytes} "
                   f"B, outputs {m.output_size_in_bytes} B, temporaries "
                   f"{m.temp_size_in_bytes} B, aliased "
                   f"{m.alias_size_in_bytes} B")
    return out


def _xplane(log_dir: str) -> str:
    for dirpath, _, files in os.walk(log_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise FileNotFoundError(f"no .xplane.pb under {log_dir}")


def require_chips(n: int) -> Optional[str]:
    """Why this process cannot run an ``n``-chip cell, or None."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: JAX runs on {devs[0].platform}"
    if len(devs) < n:
        return f"the cell needs {n} chips, JAX finds {len(devs)}"
    return None


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), for
    every program however quick to compile, so that a cell's second run
    compiles nothing. Entry points call it; tests keep their own."""
    from repro.launch import compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return compile_cache.enable()


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    import json
    t_start = now() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_spec(), args.workload)
    why = require_chips(cell["chips"])
    if why is not None:
        print(f"chip_bench: {why}", file=sys.stderr)
        return 2
    enable_compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start)
    for line in out["lines"]:
        print(line, file=sys.stderr)
    print(json.dumps(out["result"]))
    return 0
