"""Engine + planner: host time per query (ms) before the jitted call,
less spec resolution -- the query's host->device copy, the query counter,
bucket padding and the query mask -- from the engine's `stage_in` span,
over the span-traced requests sent after the window."""


def read(run):
    if not run.n_traced or "stage_in" not in run.spans:
        return None
    return run.spans["stage_in"] / run.n_traced
