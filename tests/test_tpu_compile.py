"""Ahead-of-time compiles of the scan kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (block
tiling, VMEM over the scoped limit, ops Mosaic cannot lower). Interpret
mode cannot show any of that. The shapes are the widths the deployments
use: SIFT (d=128) and GIST (d=960), and the paged frame scan at the
largest frame a build leaves.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one running this file
loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hybrid import And, Pred, compile_filter
from repro.core.types import FRAME_ROWS_MAX
from repro.kernels import ivf_scan, sq_scan

# p_max of the SIFT-1M build (1,000,000 x 128, target partition size 100,
# int8 padding to 32) as chip_smoke.py printed it on a v5e on a seed where
# the k-means' largest partition was under FRAME_ROWS_MAX; a build now
# splits every partition above that bar
P_MAX = 576
K_PARTS = 10_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, shapes, sharding, kernel):
    """Compile for the described chip; the Pallas call must lower to a
    `tpu_custom_call` named after the kernel, the name a device trace
    shows for it."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]*"
                     r'custom_call_target="tpu_custom_call"', text), kernel
    return compiled


@pytest.mark.parametrize("d,q_n,k_parts,mqo,filtered", [
    (128, 1, K_PARTS, False, False),
    (128, 32, K_PARTS, True, False),
    (128, 32, K_PARTS, True, True),
    (960, 32, 1000, True, False),
], ids=["d128-q1", "d128-q32-mqo", "d128-q32-filter", "d960-q32-mqo"])
def test_ivf_scan_compiles_for_v5e(one_chip, d, q_n, k_parts, mqo,
                                   filtered):
    n_probe, k_out = 256, 100
    pred = compile_filter(And((Pred(0, "==", 3.0), Pred(1, ">=", 2020.0)))) \
        if filtered else None
    shapes = [((q_n, d), jnp.float32), ((k_parts, P_MAX, d), jnp.float32),
              ((k_parts, P_MAX), jnp.bool_), ((k_parts, P_MAX), jnp.int32),
              ((n_probe,), jnp.int32), ((q_n, n_probe), jnp.bool_),
              ((k_parts, P_MAX, 2), jnp.float32)]

    def fn(q, v, valid, ids, pids, qsel, attrs):
        return ivf_scan.ivf_scan_topk(
            q, v, valid, ids, pids, k_out, qsel=qsel if mqo else None,
            attrs=attrs, attr_filter=pred, interpret=False)
    _compile(fn, shapes, one_chip, "ivf_scan_topk")


@pytest.mark.parametrize("d,q_n,k_parts,with_norms", [
    (128, 32, K_PARTS, True),
    (128, 1, K_PARTS, False),
    (960, 32, 1000, True),
], ids=["d128-q32-norms", "d128-q1-decode", "d960-q32-norms"])
def test_sq_scan_compiles_for_v5e(one_chip, d, q_n, k_parts, with_norms):
    n_probe, k_out = 256, 400
    shapes = [((q_n, d), jnp.float32), ((k_parts, P_MAX, d), jnp.int8),
              ((d,), jnp.float32), ((d,), jnp.float32),
              ((k_parts, P_MAX), jnp.bool_), ((k_parts, P_MAX), jnp.int32),
              ((n_probe,), jnp.int32), ((q_n, n_probe), jnp.bool_),
              ((k_parts, P_MAX), jnp.float32)]

    def fn(q, codes, lo, scale, valid, ids, pids, qsel, norms):
        return sq_scan.sq_scan_topk(
            q, codes, lo, scale, valid, ids, pids, k_out, qsel=qsel,
            norms=norms if with_norms else None, interpret=False)
    _compile(fn, shapes, one_chip, "sq_scan_topk")


@pytest.mark.parametrize("d", [128, 256, 960])
def test_frame_pool_sq_scan_compiles_for_v5e(one_chip, d):
    """The paged int8 frame scan as `executor._scan_frames_sq` runs it: L2,
    no resident norms (each frame decodes in-kernel), one query and its 8
    probed frames, at the largest frame a build leaves (FRAME_ROWS_MAX)
    and the widest candidate list the bar is sized for (k_out 1,024: top
    256 at rerank factor 4). The frame pool is the 10 MiB deployment's at
    d = 128 (p_max x 141 B a frame)."""
    q_n, n_probe, k_out = 1, 8, 1024
    frames = 10 * 2 ** 20 // (FRAME_ROWS_MAX * (d + 13))
    shapes = [((q_n, d), jnp.float32),
              ((frames, FRAME_ROWS_MAX, d), jnp.int8),
              ((d,), jnp.float32), ((d,), jnp.float32),
              ((frames, FRAME_ROWS_MAX), jnp.bool_),
              ((frames, FRAME_ROWS_MAX), jnp.int32),
              ((n_probe,), jnp.int32), ((q_n, n_probe), jnp.bool_)]

    def fn(q, codes, lo, scale, valid, ids, frames, qsel):
        return sq_scan.sq_scan_topk(
            q, codes, lo, scale, valid, ids, frames, k_out, metric="l2",
            qsel=qsel, norms=None, interpret=False)
    _compile(fn, shapes, one_chip, "sq_scan_topk")


def test_paged_plan_compiles_for_v5e(one_chip):
    """The paged planner (`executor._paged_plan`) as `paged_search` calls
    it on the SIFT-1M paged deployment: one L2 query, 10,000 centroids of
    d = 128, 8 probes and a running top-k of 400 (top 100 at rerank
    factor 4). One program from the staged query to the probe list."""
    from repro.core import executor
    shapes = [((1, 128), jnp.float32), ((1,), jnp.bool_),
              ((K_PARTS, 128), jnp.float32), ((K_PARTS,), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = executor._paged_plan.lower(
        *args, n_probe=8, metric="l2", k_want=400, p_max=P_MAX).compile()
    q, qmask, upart, qsel, run_s, run_i = compiled.out_info
    assert (q.shape, qmask.shape) == ((1, 128), (1,))
    assert (upart.shape, upart.dtype) == ((8,), jnp.int32)
    assert (qsel.shape, qsel.dtype) == ((1, 8), jnp.bool_)
    assert run_s.shape == run_i.shape == (1, 400)
