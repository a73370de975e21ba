"""Mean batch-1 prefill (encoder + cross-K/V), the program's
GenerationResult.prefill_s."""
from chip_bench import stats


def read(run):
    p = [r.prefill_s for r in run.requests.values()
         if r.due is not None and run.in_window(r.due)
         and r.prefill_s is not None]
    return 1e3 * stats.mean(p) if p else None
