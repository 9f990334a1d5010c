"""Benchmark aggregator: one runner per paper table + the roofline report.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only table2,table6]

Trains the three benchmark subjects on first use (cached under
``benchmarks/.bench_cache``), then reproduces each paper table and prints the
claim checks. The roofline section formats whatever dry-run JSON exists
under ``benchmarks/results/`` (produced separately by
``python -m repro.launch.dryrun`` — that entry point needs the 512-device
XLA flag and must own the process).
"""
from __future__ import annotations

import argparse
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache
from . import (
    roofline,
    serving_throughput,
    table1_qa_split,
    table2_weight_quant,
    table3_act_quant,
    table4_oracle_ocs,
    table5_overhead,
    table6_lstm,
    table7_knapsack,
)
from .common import save_bench_json

TABLES = {
    "table1": table1_qa_split.run,
    "table2": table2_weight_quant.run,
    "table3": table3_act_quant.run,
    "table4": table4_oracle_ocs.run,
    "table5": table5_overhead.run,
    "table6": table6_lstm.run,
    "table7": table7_knapsack.run,  # §3.4 knapsack variant (paper's negative result)
    "serving": lambda quick: serving_throughput.main(["--quick"] if quick else []),
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="", help="comma-separated table names")
    args = ap.parse_args(argv)
    enable_compile_cache()
    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(TABLES)

    failures = []
    timings = {}
    for name in names:
        print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}")
        t0 = time.time()
        try:
            TABLES[name](quick=args.quick)
            timings[name] = time.time() - t0
            print(f"[{name}] done in {timings[name]:.0f}s")
        except Exception:
            failures.append(name)
            timings[name] = -1.0
            traceback.print_exc()

    print(f"\n{'=' * 72}\n== roofline (from dry-run artifacts)\n{'=' * 72}")
    try:
        roofline.main([])
    except Exception:
        traceback.print_exc()

    # Stable cross-PR artifact: which runners passed and how long they took
    # (seconds; -1 = failed). Trend tooling in later PRs consumes this.
    save_bench_json(
        "tables",
        metrics={f"{n}_seconds": t for n, t in timings.items()},
        # "only" lets trend tooling distinguish "not run this time" (partial
        # invocation overwrote the file) from a removed/failed table.
        meta={"quick": bool(args.quick), "failed": failures, "only": names},
    )

    if failures:
        raise SystemExit(f"failed tables: {failures}")
    print("\nall benchmark tables completed")


if __name__ == "__main__":
    main()
