"""Device: share of the traced window in which no op ran on the device
(%), for the front-door mixes; read as `device_idle_share.solo` is."""
from chipbench import trace_reduce


def read(run):
    return trace_reduce.idle_percent(run.trace)
