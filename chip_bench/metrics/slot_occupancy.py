"""Mean share of the slot pool holding a live request, over the window's
decode steps."""


def read(run):
    s = [n / run.n_slots for _, b, n in run.steps if run.in_window(b)]
    return 100.0 * sum(s) / len(s) if s else None
