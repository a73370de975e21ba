"""Host-clock time spent in admit() between decode steps, per decode step."""


def read(run):
    a = sum(b - a for a, b, _ in run.admits if run.in_window(b))
    n = sum(1 for _, b, _ in run.steps if run.in_window(b))
    return 1e3 * a / n if n else None
