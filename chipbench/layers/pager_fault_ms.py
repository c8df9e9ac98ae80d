"""Pager: host time of the frame-pool faults per query (ms), from the
engine's `pager_fault` span, over the span-traced requests sent after
the window."""


def read(run):
    if not run.n_traced or "pager_fault" not in run.spans:
        return None
    return run.spans["pager_fault"] / run.n_traced
