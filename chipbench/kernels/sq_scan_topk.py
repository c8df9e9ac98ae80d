"""Operations and bytes of one call of the int8 scan kernel
(`repro.kernels.sq_scan.sq_scan_topk`), counted from the valid rows of
the probed partitions and the real queries only.

Padding rows (each partition is scanned at p_max) and the padded query
tile do no useful work, so they are left out: on the chip they show as
share lost to the roofline. Per query and valid row the kernel's
two-term folded query multiplies d int8 pairs twice (2 x 2d integer
ops); the running top-k merge is compare work that is not counted.
Bytes are what has to leave HBM at least once per call: each probed
partition's valid codes (d B), ids (4 B) and, on the resident path,
their precomputed norms (4 B), plus the folded queries and the outputs.
"""
from __future__ import annotations

from typing import Sequence

OP_PEAK = "int8_ops"


def matches(op: str) -> bool:
    """Whether a device op of the trace is this kernel. The Pallas call
    carries no name of its own: on a TPU it is a `tpu_custom_call` named
    after the jitted function around it, and it alone takes the int8
    two-term query block (`s8[2,...]`)."""
    return 'custom_call_target="tpu_custom_call"' in op and " s8[2," in op


def cost(d: int, union_rows: int, query_rows: Sequence[int], k_out: int,
         with_norms: bool) -> tuple:
    """(ops, bytes) of one call.

    union_rows  valid rows in the union of the call's probed partitions
    query_rows  for each real query, valid rows in its own probe set
    k_out       candidates the call returns per query
    with_norms  the resident tier streams precomputed row norms"""
    q = len(query_rows)
    ops = 2 * 2 * d * sum(query_rows)
    row_bytes = d + 4 + (4 if with_norms else 0)
    nbytes = union_rows * row_bytes + q * (2 * d + 12) + q * k_out * 8
    return float(ops), float(nbytes)
