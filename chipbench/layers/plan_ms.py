"""Engine + planner: host time of spec resolution per query (ms), from
the engine's `plan` span, over the span-traced requests sent after the
window."""


def read(run):
    if not run.n_traced or "plan" not in run.spans:
        return None
    return run.spans["plan"] / run.n_traced
