"""Scan kernels: device time of the int8 scan kernel per query (ms), from
the profiler trace's kernel events."""
from chipbench import trace_reduce
from chipbench.kernels import sq_scan_topk


def read(run):
    s = trace_reduce.kernel_seconds(run.trace["ops"], sq_scan_topk.matches)
    if s <= 0 or not run.n_requests:
        return None
    return 1e3 * s / run.n_requests
