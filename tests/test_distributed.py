"""Distributed search on an 8-device mesh (8 fake CPU devices), under
each metric, against the single-device search.

These run in a subprocess so the 8-device XLA flag never leaks into the
main pytest process (smoke tests must see 1 device).
"""
import json
import subprocess
import sys

import pytest

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import ivf, search
from repro.core.types import IVFConfig
from repro.distributed.sharded_index import distributed_search, index_shardings

if jax.device_count() < 8:   # XLA flag ignored (e.g. real accelerator host)
    print("RESULT SKIP single-device host")
    raise SystemExit(0)
out = {}
rng = np.random.default_rng(0)
centers = rng.normal(size=(16, 32)) * 5
X = (centers[rng.integers(0, 16, 2048)] + rng.normal(size=(2048, 32))).astype(np.float32)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
Q = jnp.asarray(X[:8] + 0.05 * rng.normal(size=(8, 32)).astype(np.float32))
for metric in ("l2", "ip", "cosine"):
    cfg = IVFConfig(dim=32, target_partition_size=64, kmeans_iters=40,
                    delta_capacity=128, metric=metric)
    idx = ivf.build_index(X, cfg=cfg)
    ref = search.ann_search(idx, Q, 10, n_probe=6)
    for merge in ("tournament", "allgather"):
        res = distributed_search(idx, Q, 10, 6, mesh, merge=merge)
        out[f"match_{metric}_{merge}"] = float(
            (np.asarray(res.ids) == np.asarray(ref.ids)).mean())

# index shardings place partitions over model
sh = index_shardings(idx, mesh)
out["vec_spec"] = str(sh.vectors.spec)

print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def dist_result():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=520, env={**__import__("os").environ,
                          "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout
    payload = line[-1][len("RESULT "):]
    if payload.startswith("SKIP"):
        pytest.skip(payload)
    return json.loads(payload)


@pytest.mark.parametrize("metric", ["l2", "ip", "cosine"])
def test_distributed_matches_single_device(dist_result, metric):
    assert dist_result[f"match_{metric}_tournament"] == 1.0
    assert dist_result[f"match_{metric}_allgather"] == 1.0


def test_partitions_sharded_over_model(dist_result):
    assert "model" in dist_result["vec_spec"]

