"""The pod cell's check has to fail on a broken round, and its
reference and per-layer readers have to compute what they say.

Each fault test drives ``pod4.join`` at its rehearsal size on four
virtual CPU devices (``JAX_PLATFORMS=cpu``), with one fault planted in
the rounds or the control's int32 values laid over what the window
produced, and sees ``correct`` come out false. One sound run must come
out true.

    JAX_PLATFORMS=cpu python3 -m pytest cells/tests -q
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time

# The rehearsal's four members need four devices: set before any test
# of this process first touches JAX.
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FLAG}=4").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from cells import control, harness  # noqa: E402
from cells.reference import pod_fold  # noqa: E402

SECONDS = 2.0


def _run(seed: int, hooks=None):
    cell = harness.load_cell("pod4.join", rehearse=True)
    line, _ = harness.run_cell(cell, seed, SECONDS, False,
                               time.monotonic(), hooks=hooks)
    return line


# --- faults in the rounds ----------------------------------------------

def _batch_withheld(group):
    """Member 1's batch is withheld from each round's join and written
    only after it, so the last one never reaches the others."""
    m = group.members[1]
    real_put, real_join = m.put_batch, group.join
    held = []
    m.put_batch = lambda slots, values: held.append((slots, values))

    def join(**kw):
        report = real_join(**kw)
        while held:
            real_put(*held.pop(0))
        return report
    group.join = join


def _join_keeps_larger_value(group):
    """A join that keeps, at every slot, the record with the larger
    value in place of the later stamp."""
    import jax
    from crdt_tpu.hlc import Hlc
    from crdt_tpu.ops.dense import DenseStore

    def join(**kw):
        stores = [jax.device_get(m._store) for m in group.members]
        pick = np.argmax(np.stack([s.val for s in stores]), axis=0)
        cols = np.arange(pick.size)
        lanes = DenseStore(*(np.stack(lane)[pick, cols]
                             for lane in zip(*stores)))
        canonical = max(m.canonical_time.logical_time
                        for m in group.members)
        canonical = max(canonical, int(lanes.lt.max()))
        for m in group.members:
            dev = next(iter(m._store.lt.devices()))
            m._store = jax.device_put(lanes, dev)
            m._canonical_time = Hlc.from_logical_time(canonical,
                                                      m.node_id)
    group.join = join


def _member_left_out(group):
    """Member 3 is left out of each round's join, the final round's
    among them: it keeps its own store and clock."""
    m = group.members[3]
    real_join = group.join

    def join(**kw):
        store, clock = m._store, m.canonical_time
        report = real_join(**kw)
        m._store, m._canonical_time = store, clock
        return report
    group.join = join


def test_sound_run_is_correct():
    line = _run(3_000_000_019)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("fault", [_batch_withheld,
                                   _join_keeps_larger_value,
                                   _member_left_out],
                         ids=lambda f: f.__name__)
def test_fault_is_caught(fault):
    line = _run(2_147_483_659, hooks={"plant": fault})
    assert not line["correct"], line["checks"]


def test_control_is_caught():
    # The written values cut to int32 (cells/control.py) read wrong.
    line = _run(4_294_967_311, hooks={"observe": control.observe_int32})
    assert not line["correct"], line["checks"]
    assert line["checks"]["final_wrong"]["value"] > 0


# --- the plain fold on hand-made logs ----------------------------------

def _winners(log, base_lt=5, base_rank=0, seed=7):
    w = pod_fold.fold(log, base_lt, base_rank, seed)
    return {int(s): (int(t), int(r), int(v))
            for s, t, r, v in zip(w["slots"], w["lt"], w["rank"], w["val"])}


def test_fold_later_stamp_wins_whatever_the_rank():
    log = [(3, 10, np.array([1, 2]), np.array([31, 32])),
           (0, 11, np.array([2]), np.array([2])),
           (1, 12, np.array([4]), np.array([14]))]
    assert _winners(log) == {1: (10, 3, 31), 2: (11, 0, 2),
                             4: (12, 1, 14)}


def test_fold_equal_stamps_go_to_the_higher_rank():
    log = [(2, 20, np.array([6, 7]), np.array([26, 27])),
           (1, 20, np.array([6]), np.array([16])),
           (3, 20, np.array([7]), np.array([37])),
           (0, 20, np.array([8]), np.array([8]))]
    assert _winners(log) == {6: (20, 2, 26), 7: (20, 3, 37),
                             8: (20, 0, 8)}


def test_fold_keeps_the_loaded_record_over_older_writes():
    loaded = int(pod_fold.loaded_value(np.array([9]), 7)[0])
    log = [(1, 4, np.array([9]), np.array([-1]))]
    assert _winners(log, base_lt=5, base_rank=0) == {9: (5, 0, loaded)}
    assert _winners([]) == {}


def test_mismatches_counts_each_wrong_lane():
    want = {"slots": np.array([1, 2, 3, 4, 5]),
            "lt": np.array([10, 10, 10, 10, 10]),
            "rank": np.array([1, 1, 1, 1, 1]),
            "val": np.array([7, 7, 7, 7, 7])}
    got = {"occupied": np.array([True, False, True, True, True]),
           "tomb": np.array([False, False, True, False, False]),
           "lt": np.array([10, 10, 10, 9, 10]),
           "rank": np.array([1, 1, 1, 1, 2]),
           "val": np.array([7, 7, 7, 7, 7])}
    assert pod_fold.mismatches(got, want) == 4
    assert pod_fold.mismatches({**got, "occupied": np.ones(5, bool),
                                "tomb": np.zeros(5, bool),
                                "lt": want["lt"], "rank": want["rank"]},
                               want) == 0


# --- the per-layer readers on a hand-made reduced trace ---------------

def _reader(name):
    path = os.path.join(ROOT, "cells", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _device(busy, in_round, rounds, ops):
    return {"busy_ns": busy, "in_spans": {"pod.round": in_round},
            "span_counts": {"pod.round": rounds}, "ops": ops}


def test_readers_on_a_hand_made_trace():
    trace = {"devices": [
        _device(100_000_000, 80_000_000, 4,
                {"all-reduce.3": [30_000_000, 9], "fusion.1": [70_000_000, 4]}),
        _device(50_000_000, 40_000_000, 4,
                {"_all-gather.1___u32": [10_000_000, 2],
                 "collective-permute-done": [5_000_000, 1],
                 "copy.2": [35_000_000, 3]})]}
    run = {"trace": trace, "joins": 8, "fetch_bytes": 8 * 3_276_848}
    assert _reader("device_ms_per_join.merges")(run) == pytest.approx(15.0)
    assert _reader("collective_pct.merges")(run) == pytest.approx(30.0)
    assert _reader("join_d2h_bytes.merges")(run) == 3_276_848
    # Nothing to read: no trace, no rounds traced, no join counters.
    assert _reader("device_ms_per_join.merges")({"trace": None}) is None
    assert _reader("collective_pct.merges")({}) is None
    idle = {"trace": {"devices": [_device(0, 0, 0, {})]}}
    assert _reader("device_ms_per_join.merges")(idle) is None
    assert _reader("join_d2h_bytes.merges")({"joins": 3,
                                             "fetch_bytes": 0}) is None
