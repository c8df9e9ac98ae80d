"""Benchmark driver -- one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig9] [--json-dir out/]

Prints ``name,us_per_call,derived`` CSV rows. With `--json-dir` (or the
BENCH_JSON_DIR env var) every section also persists a BENCH_<name>.json
trajectory artifact: sections with their own rich emitter write it
directly, the rest get a generic dump of their CSV rows.
"""
import argparse
import sys
import traceback

from . import common


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-dir", default=None,
                    help="write BENCH_<section>.json artifacts here "
                         "(defaults to $BENCH_JSON_DIR if set)")
    args = ap.parse_args()
    if args.json_dir:
        common.set_json_dir(args.json_dir)

    from . import (bench_build, bench_e2e, bench_executor, bench_fleet,
                   bench_hybrid, bench_minibatch, bench_mqo, bench_obs,
                   bench_paged, bench_quantized, bench_serve,
                   bench_updates)
    sections = {
        "fig4_5_e2e": bench_e2e.main,
        "fig6_build": bench_build.main,
        "fig7_hybrid": bench_hybrid.main,
        "fig8_minibatch": bench_minibatch.main,
        "fig9_mqo": bench_mqo.main,
        "fig10_updates": bench_updates.main,
        "executor": bench_executor.main,
        "quantized": bench_quantized.main,
        "paged": bench_paged.main,
        "serve": bench_serve.main,
        "obs": bench_obs.main,
        "fleet": bench_fleet.main,
    }
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in sections.items():
        if args.only and args.only not in name:
            continue
        before = len(common.ROWS)
        try:
            fn()
        except Exception:
            failed += 1
            print(f"{name},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
            continue
        if common.json_dir() and name not in common.WRITTEN:
            # generic artifact for sections without a dedicated emitter
            rows = [r.split(",", 2) for r in common.ROWS[before:]]
            common.write_json(name, {
                "rows": [{"name": r[0], "us_per_call": float(r[1]),
                          "derived": r[2] if len(r) > 2 else ""}
                         for r in rows]})
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
