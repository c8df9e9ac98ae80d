"""Chip benchmark of the MicroNN engine: one cell per run, driven by data.

`BENCHMARK.json` at the repository root names the cells. Each cell's
configuration, traffic mix, correctness limits and per-layer readers are
files under this directory, found by name:

    configs/<config>.json      deployment: data shape, engine settings
    traffic/<traffic>.json     parameters of the one load generator
    limits/<workload>.json     the limits `correct` is judged by
    layers/<metric>.py         reader of one per-layer metric
    kernels/<kernel>.py        operation and byte count of one kernel

Run one cell once with `python3 chipbench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`.
"""
