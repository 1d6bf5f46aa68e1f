"""Every benchmark cell rehearses on the CPU: its whole control flow at
the configuration's tiny ``rehearse`` sizes must check ``correct``, and
its int32 control must then read ``correct`` false.

The cells are read from ``BENCHMARK.json``, so a new cell is covered
without an edit here.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]
assert WORKLOADS, "BENCHMARK.json names no workloads"


def _end_to_end(workload):
    """The end-to-end metrics the benchmark reads from ``workload``:
    those that name it, and those that name no workload."""
    return sorted(m["name"] for m in BENCHMARK["end_to_end"]
                  if workload in m.get("workloads", [workload]))


def _run(script, *args):
    proc = subprocess.run(
        [sys.executable, os.path.join("cells", script), *args],
        cwd=ROOT, capture_output=True, text=True, timeout=150)
    assert proc.returncode == 0, (
        f"{script} {args} exited {proc.returncode}\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_rehearsal_is_correct(workload):
    line = _run("run.py", "--workload", workload, "--seed", "1",
                "--seconds", "3", "--trace", "0", "--rehearse")
    assert line["rehearsal"] is True
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0
    assert line["reports"] == _end_to_end(workload)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_int32_control_is_wrong(workload):
    line = _run("control.py", "--workload", workload, "--seeds", "1",
                "--seconds", "3", "--rehearse")
    assert line["control"] == "int32_values"
    assert line["correct"] is False, line["checks"]
