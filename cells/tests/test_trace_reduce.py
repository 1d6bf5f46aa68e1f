"""Self-test of the trace reduction, on the CPU.

The interval arithmetic is checked on hand-made intervals, and the
whole reduction on a small trace recorded on a v5e (``cells/testdata``)
against numbers worked out here from the raw events by a second, plain
route.

    JAX_PLATFORMS=cpu python3 -m pytest cells/tests/test_trace_reduce.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from cells import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "fanin_v5e.xplane.pb")


def test_union_merges_overlaps_and_drops_empty():
    iv = [(5, 9), (0, 3), (2, 4), (9, 10), (12, 12), (11, 13)]
    assert tr.interval_union(iv) == [(0, 4), (5, 10), (11, 13)]


def test_clip_sum_counts_busy_inside_spans_once():
    union = [(0, 4), (5, 10), (11, 13)]
    # Overlapping spans count their union; the parts outside any span
    # do not count.
    assert tr.clip_sum(union, [(2, 6), (3, 7), (12, 20)]) == 2 + 2 + 1
    assert tr.clip_sum(union, []) == 0
    assert tr.clip_sum(union, [(-5, 50)]) == 4 + 5 + 2


def test_gaps_and_labels():
    union = [(0, 4), (5, 10), (11, 13)]
    gaps = tr.gaps_of(union, -2, 20)
    assert gaps == [(-2, 0), (4, 5), (10, 11), (13, 20)]
    host = [("outer", -10, 30), ("inner", 12, 18)]
    # The longest gap first, named by the shortest span holding its
    # midpoint (16.5 -> 16, inside "inner").
    assert tr.label_gaps(gaps, host, top=2) == [("inner", 7), ("outer", 2)]


def _plain_busy(profile, lo, hi):
    """Busy ns of each device plane's op line in [lo, hi), by sweeping
    a sorted list of boundaries (a second route to the union)."""
    out = []
    for plane in profile.planes:
        if not tr._is_device(plane):
            continue
        marks = []
        for line in tr._op_lines(plane):
            for ev in line.events:
                s = max(int(ev.start_ns), lo)
                e = min(int(ev.start_ns + ev.duration_ns), hi)
                if e > s:
                    marks += [(s, 1), (e, -1)]
        busy, depth, at = 0, 0, None
        for t, d in sorted(marks):
            if depth > 0:
                busy += t - at
            depth += d
            at = t
        out.append(busy)
    return out


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace")
def test_recorded_trace():
    profile = tr.load(RECORDED)
    red = tr.reduce_profile(profile, "cells.window", ["fanin.pass"])
    assert red is not None and red["devices"]
    host = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    win = [(s, e) for n, s, e in host if n == "cells.window"]
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    assert red["window_ns"] == hi - lo
    assert [d["busy_ns"] for d in red["devices"]] == \
        _plain_busy(profile, lo, hi)
    dev = red["devices"][0]
    passes = [(s, e) for n, s, e in host if n == "fanin.pass"
              and e > lo and s < hi]
    assert dev["span_counts"]["fanin.pass"] == len(passes) > 0
    # Busy time inside the passes is busy time, and no more than the
    # passes last.
    inside = dev["in_spans"]["fanin.pass"]
    assert 0 < inside <= dev["busy_ns"]
    assert inside <= sum(e - s for s, e in tr.interval_union(passes))
    # Op time is at least the busy time (ops may overlap, never less).
    assert sum(ns for ns, _ in dev["ops"].values()) >= dev["busy_ns"]
    bd = tr.breakdown(red)
    assert 0 < len(bd["device_ops"]) <= 10
    assert len(bd["idle_gaps"]) <= 10
