"""Run one benchmark cell once and print its result line.

    python3 cells/run.py --workload kv.ycsb_a --seed 7 --seconds 20 --trace 0

The cell (configuration, traffic mix, chips) is looked up by name in
``BENCHMARK.json``. The run makes its data from ``--seed`` on the
device, warms every program the window uses (counted in ``setup_s``),
measures for ``--seconds``, checks what the window produced against
the plain reference in ``cells/reference/``, and prints one JSON object
as the last line of standard output: end-to-end metrics with
``--trace 0``, per-layer metrics from a profiler trace with
``--trace 1``. Each number the check compares is printed beside its
limit as the last lines of standard error and under ``checks``.

A run that finds no TPU, or fewer chips than the cell asks for, exits
2 and prints no result. ``--rehearse`` runs the same control flow at
the configuration's tiny ``rehearse`` sizes on whatever JAX finds
(``JAX_PLATFORMS=cpu``, Pallas in interpret mode); its line says
``"rehearsal": true`` and is not a measurement.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from cells import harness
    cell = harness.load_cell(args.workload, rehearse=args.rehearse)
    try:
        line, outcome = harness.run_cell(cell, args.seed, args.seconds,
                                         bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"cells/run.py: {e}", file=sys.stderr)
        return 2
    harness.print_checks(outcome.checks)
    if args.rehearse:
        # A rehearsal's times are the host's, not a device's: it names
        # the metrics it would report and prints none of their values.
        line = {"rehearsal": True, "correct": line["correct"],
                "attempted": line["attempted"], "failed": line["failed"],
                "reports": sorted(line["metrics"]),
                "checks": line["checks"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
