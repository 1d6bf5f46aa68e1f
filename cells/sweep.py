"""Find a served cell's knee: one window at each offered rate, in one
process, each checked like a run of the cell.

    python3 cells/sweep.py --workload kv.ycsb_a --rates 200,400,600 --seconds 8

Prints one JSON line per rate: offered and completed rates, the median
latencies and whether the window was correct; each window's standard
error line has its p50, p90 and p99. The cells' rates were set once from
such a sweep on the chip (``PERF.md``); the benchmark's runs never
search for a rate.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from cells import harness
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell = harness.load_cell(args.workload, rehearse=args.rehearse)
        cell.traffic["rate_ops_s"] = rate
        cell.traffic.get("rehearse", {}).pop("rate_ops_s", None)
        t = time.monotonic()
        line, out = harness.run_cell(cell, args.seed + i, args.seconds,
                                     False, t)
        print(json.dumps({"rate_offered": rate,
                          "ops_s": out.metrics.get("ops_s"),
                          "ack_p50_ms": out.metrics.get("ack_p50_ms"),
                          "read_p50_ms": out.metrics.get("read_p50_ms"),
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "correct": line["correct"],
                          "setup_s": out.metrics.get("setup_s")}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
