"""Scan kernels: the int8 scan kernel's share of its roofline (%), with
the bound that applies. Operations and bytes count the valid rows of
each query's probe set (chipbench/kernels/sq_scan_topk.py), so padding
shows as lost share; the time is the kernel's device time in the trace.
One kernel call serves one Q=1 request."""
from chipbench import peaks, trace_reduce
from chipbench.kernels import sq_scan_topk


def read(run):
    s = trace_reduce.kernel_seconds(run.trace["ops"], sq_scan_topk.matches)
    if s <= 0 or run.traffic["q_rows"] != 1:
        return None
    k_out = int(run.traffic["k"]) * int(run.config["engine"]["rerank_factor"])
    d = int(run.config["dim"])
    ops = nbytes = 0.0
    for rows in run.probe_rows:
        o, b = sq_scan_topk.cost(d, int(rows), [int(rows)], k_out,
                                 with_norms=not run.layout["paged"])
        ops += o
        nbytes += b
    share, bound = peaks.roofline(ops, nbytes, s, run.device_kind,
                                  sq_scan_topk.OP_PEAK)
    return {"value": share, "bound": bound}
