"""Median wait from a request's due time to the start of its prefill (the
program's queue_wait_s counts from its submit)."""
from chip_bench import stats


def read(run):
    w = [r.prefill_start - r.due for r in run.requests.values()
         if r.due is not None and run.in_window(r.due)
         and r.prefill_start is not None]
    return 1e3 * stats.percentile(w, 50) if w else None
