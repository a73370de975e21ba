#!/usr/bin/env python3
"""Record the small trace ``test_bench_trace.py`` reduces, on the chip:

    python3 chip_bench/testdata/record_trace.py <out_dir>

whisper-tiny through the continuous-batching scheduler at 2 slots: one
admission (a batch-1 prefill), two decode steps, and a 3 ms host sleep
labelled ``bench.idle`` between them, all inside ``bench.stretch``.
"""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chip_bench import harness, spec, trace_reduce  # noqa: E402


def main() -> int:
    out = sys.argv[1]
    harness.enable_compile_cache()
    cell = spec.resolve(spec.load_spec(), "whisper-tiny-q8.longform")
    fam, cfg, engine = harness.build(cell, 5)
    sched = engine.scheduler(n_slots=2, **fam.scheduler_kwargs(cfg))
    mel = np.random.default_rng(5).standard_normal(
        (cfg.encoder_ctx, cfg.n_mels)).astype(np.float32)
    for _ in range(2):                      # warm: compile everything
        sched.submit(mel, max_new=3)
        sched.run()
    ann = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    sched.submit(mel, max_new=8)
    jax.profiler.start_trace(out, profiler_options=opts)
    with ann(trace_reduce.STRETCH):
        with ann("bench.admit"):
            sched.admit()
        with ann("bench.decode_step"):
            sched.decode_step()
        with ann("bench.idle"):
            time.sleep(0.003)
        with ann("bench.decode_step"):
            sched.decode_step()
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
