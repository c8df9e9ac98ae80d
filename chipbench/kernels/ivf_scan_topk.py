"""Operations and bytes of one call of the float32 scan kernel
(`repro.kernels.ivf_scan.ivf_scan_topk`), counted from the valid rows of
the probed partitions and the real queries only.

Per query and valid row the kernel takes one d-long dot product
(2d floating-point operations); padding rows and the padded query tile
are not counted. Bytes are each probed partition's valid float32 rows
(4d B) and ids (4 B), plus the queries and the outputs. The operations
are held against the chip's bf16 peak, the fastest its matrix unit
runs, so the share is an upper bound on how well the kernel does.
"""
from __future__ import annotations

from typing import Sequence

OP_PEAK = "bf16_flops"


def matches(op: str) -> bool:
    """Whether a device op of the trace is this kernel: a
    `tpu_custom_call` (the Pallas call carries no name of its own) that
    takes no int8 query block."""
    return 'custom_call_target="tpu_custom_call"' in op and " s8[2," not in op


def cost(d: int, union_rows: int, query_rows: Sequence[int], k_out: int,
         with_norms: bool = False) -> tuple:
    """(ops, bytes) of one call; the arguments are as for
    `sq_scan_topk.cost`. The float32 kernel computes row norms in
    register, so `with_norms` adds nothing."""
    del with_norms
    q = len(query_rows)
    ops = 2 * d * sum(query_rows)
    nbytes = union_rows * (4 * d + 4) + q * 4 * d + q * k_out * 8
    return float(ops), float(nbytes)
