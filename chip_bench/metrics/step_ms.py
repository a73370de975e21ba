"""Mean host-clock time of one decode_step over the slot pool (step and
host sync), over the window's steps."""
from chip_bench import stats


def read(run):
    ds = [b - a for a, b, _ in run.steps if run.in_window(b)]
    return 1e3 * stats.mean(ds) if ds else None
