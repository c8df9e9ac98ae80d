"""Device: share of the traced window in which no op ran on the device
(%), from the union of the device-op intervals in the profiler trace."""
from chipbench import trace_reduce


def read(run):
    return trace_reduce.idle_percent(run.trace)
