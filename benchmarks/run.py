"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/_util.emit).
  Fig 9   -> logsize
  Fig 10  -> hang            Fig 11 -> issue_dist
  Table 4 -> regression      Fig 12 -> case2_matmul
  Table 5 -> vminority       §Roofline -> roofline (reads dryrun_out/)
  §Scale  -> ingest (columnar pipeline throughput; BENCH_ingest.json)
  §Fleet  -> fleet (multi-job incremental diagnosis + JSONL replay;
             BENCH_fleet.json)
  §Store  -> storage (JSONL vs FCS bytes/event + replay Mev/s;
             BENCH_storage.json)
  §Robust -> scenarios (fault matrix, scored detector P/R;
             BENCH_scenarios.json)
  §Query  -> archive (predicate-pushdown reads + rollup cache;
             BENCH_archive.json)
  §Live   -> live (socket/tail ingest Mev/s + event->anomaly latency,
             byte-equivalence gated; BENCH_live.json)
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (archive, case2_matmul, fleet, hang, ingest,
                            issue_dist, live, logsize, regression, roofline,
                            scenarios, storage, vminority)
    sections = [
        ("fig9_logsize", logsize.main),
        ("fig10_hang", hang.main),
        ("fig11_issue_dist", issue_dist.main),
        ("table4_regression", regression.main),
        ("fig12_case2", case2_matmul.main),
        ("table5_vminority", vminority.main),
        ("roofline", roofline.main),
        ("scale_ingest", ingest.main),
        ("scale_fleet", fleet.main),
        ("scale_storage", storage.main),
        ("robust_scenarios", scenarios.main),
        ("query_archive", archive.main),
        ("live_serve", live.main),
    ]
    print("name,us_per_call,derived")
    failures = []
    for name, fn in sections:
        print(f"# --- {name} ---")
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED sections: {failures}")
        sys.exit(1)
    print("# all benchmark sections completed")


if __name__ == "__main__":
    main()
