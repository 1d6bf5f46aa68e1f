"""The controls: each cell run with what its window produced cut to the
next lower precision before the check, which has to come out wrong.

    python3 cells/control.py --workload fanin.r1024 --seeds 1,2,3 --seconds 5

For this store the lower precision is the value lane cut to int32 (the
program's own ``value_width=32`` mode): every value the window answered
or left in the store is replaced by its low 32 bits, sign-extended, as
the int32 reference put in the program's place would hold it. Prints
one JSON line per seed with the numbers the check compared. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def to_int32(v):
    """The low 32 bits, sign-extended: an int64 lane held as int32."""
    return np.asarray(v, np.int64).astype(np.int32).astype(np.int64)


def observe_int32(*produced) -> None:
    """Cut every value the window produced to int32, in place: the
    served cells hand ``(client results, store values)``, the fan-in
    its merged host lanes."""
    if len(produced) == 2:
        res, seen = produced
        res["got"][:] = to_int32(res["got"])
        for s, v in seen.items():
            if v is not None:
                seen[s] = int(to_int32(v))
    else:
        (lanes,) = produced
        lanes["val"] = to_int32(lanes["val"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from cells import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.load_cell(args.workload, rehearse=args.rehearse)
        t = time.monotonic()
        line, _ = harness.run_cell(cell, seed, args.seconds, False, t,
                                   hooks={"observe": observe_int32})
        print(json.dumps({"control": "int32_values", "seed": seed,
                          "correct": line["correct"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
