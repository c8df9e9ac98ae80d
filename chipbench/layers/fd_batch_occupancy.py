"""Front door: requests per fused call over the window (rows/call), from
the front door's counters. Solo dispatches count as calls of one."""


def read(run):
    calls = run.delta("fd", "batches") + run.delta("fd", "solo")
    if calls <= 0:
        return None
    return run.delta("fd", "completed") / calls
