"""Pager: frame-pool hits over faults requested in the window (%), from
the pager's counters."""


def read(run):
    if "pager" not in run.before:
        return None
    hits = run.delta("pager", "hits")
    total = hits + run.delta("pager", "misses")
    return 100.0 * hits / total if total else None
