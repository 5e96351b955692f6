"""Benchmark harness — one module per paper table (Tables 4.1/4.2/4.3/A.1)
plus the communication-cost and roofline tables. CSV to stdout.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table_4_1,...]

Default is the quick profile (CPU container); --full runs the paper-scale
sweeps. REPRO_BENCH_STEPS / REPRO_BENCH_HIDDEN scale the training runs.
"""
from __future__ import annotations

import argparse
import time

from benchmarks import (alpha_schedule, comm_compress, comm_cost, faults,
                        fleet, fused_step, obs, roofline_bench, serve_live,
                        shard, straggler, table_4_1, table_4_2, table_4_3,
                        table_a_1)
from repro.common.cache import enable_compile_cache

TABLES = {
    "table_4_1": table_4_1.main,
    "table_4_2": table_4_2.main,
    "table_4_3": table_4_3.main,
    "table_a_1": table_a_1.main,
    "alpha_schedule": alpha_schedule.main,
    "comm_cost": comm_cost.main,
    "comm_compress": comm_compress.main,
    "roofline": roofline_bench.main,
    "fused_step": fused_step.main,
    "fused_step_resident": fused_step.resident_main,
    "straggler": straggler.main,
    "serve_live": serve_live.main,
    "faults": faults.main,
    "fleet": fleet.main,
    "shard": shard.main,
    "obs": obs.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else set(TABLES)
    t0 = time.time()
    for name, fn in TABLES.items():
        if name not in only:
            continue
        print(f"\n==== {name} ====", flush=True)
        fn(quick=not args.full)
    print(f"\n# total benchmark wall time: {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
