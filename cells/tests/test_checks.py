"""The check that decides ``correct`` has to fail on a broken window.

Each test drives a whole cell at its rehearsal size on the CPU
(``JAX_PLATFORMS=cpu``; the chip look is skipped by the rehearsal),
with one fault planted in the program underneath the timed path or
with the control's lower precision laid over what the window produced,
and sees ``correct`` come out false. One sound run of each cell must
come out true.

    JAX_PLATFORMS=cpu python3 -m pytest cells/tests -q
"""

from __future__ import annotations

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from cells import control, harness  # noqa: E402

SECONDS = 2.0


def _run(workload: str, seed: int, hooks=None):
    cell = harness.load_cell(workload, rehearse=True)
    line, _ = harness.run_cell(cell, seed, SECONDS, False,
                               time.monotonic(), hooks=hooks)
    return line


# --- faults in the served store ---------------------------------------

def _commit_unchanged(crdt):
    """A write commit that returns the store as it was."""
    crdt._commit_scatter = lambda slots, lt, vals, tombs: crdt._store


def _commit_half(crdt):
    """A write commit that drops the second half of each batch."""
    real = crdt._commit_scatter

    def half(slots, lt, vals, tombs):
        k = max(1, (len(slots) + 1) // 2)
        return real(slots[:k], lt[:k], vals[:k], tombs[:k])
    crdt._commit_scatter = half


def _commit_altered(crdt):
    """A write commit that stores each value off by one."""
    real = crdt._commit_scatter
    crdt._commit_scatter = lambda slots, lt, vals, tombs: real(
        slots, lt, vals + 1, tombs)


def _read_altered(crdt):
    """A point read whose answer is off by one where it is produced."""
    real = crdt.get
    crdt.get = lambda slot: (lambda v: None if v is None else v + 1)(
        real(slot))


# --- faults in the fan-in ----------------------------------------------

def _merge_unchanged(crdt):
    crdt.merge_many = lambda changesets: None


def _merge_half(crdt):
    real = crdt.merge_many

    def half(changesets):
        (cs, ids), = changesets
        r = cs.lt.shape[0] // 2
        real([(type(cs)(*(lane[:r] for lane in cs)), ids)])
    crdt.merge_many = half


def _merge_altered(crdt):
    real = crdt.merge_many

    def altered(changesets):
        real(changesets)
        crdt._store = crdt._store._replace(val=crdt._store.val + 1)
    crdt.merge_many = altered


@pytest.mark.parametrize("workload", ["kv.ycsb_a", "kv.ycsb_load",
                                      "fanin.r1024"])
def test_sound_run_is_correct(workload):
    line = _run(workload, 3_000_000_019)
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("workload,fault", [
    ("kv.ycsb_a", _commit_unchanged),
    ("kv.ycsb_a", _commit_half),
    ("kv.ycsb_a", _read_altered),
    ("kv.ycsb_load", _commit_unchanged),
    ("kv.ycsb_load", _commit_half),
    ("kv.ycsb_load", _commit_altered),
    ("fanin.r1024", _merge_unchanged),
    ("fanin.r1024", _merge_half),
    ("fanin.r1024", _merge_altered),
], ids=lambda x: getattr(x, "__name__", x))
def test_fault_is_caught(workload, fault):
    line = _run(workload, 2_147_483_659, hooks={"plant": fault})
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("workload", ["kv.ycsb_a", "kv.ycsb_load",
                                      "fanin.r1024"])
def test_control_is_caught(workload):
    line = _run(workload, 4_294_967_311,
                hooks={"observe": control.observe_int32})
    assert not line["correct"], line["checks"]
