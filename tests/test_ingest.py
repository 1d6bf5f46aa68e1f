"""Write-path fast lane (crdt_tpu.models.ingest): batched HLC
stamping, the read-your-writes overlay, barrier draining, commit-time
watch events, sharded commits, and gossip rounds that drain mid-flight
staging — the acceptance suite for `DenseCrdt.ingest()`
(docs/INGEST.md)."""

import random

import numpy as np
import pytest

import jax

from crdt_tpu import DenseCrdt, GossipNode, RetryPolicy
from crdt_tpu.hlc import (MAX_COUNTER, MAX_DRIFT, ClockDriftException,
                          Hlc, OverflowException)
from crdt_tpu.models.dense_crdt import ShardedDenseCrdt
from crdt_tpu.models.keyed_dense import KeyedDenseCrdt
from crdt_tpu.parallel import make_fanin_mesh
from crdt_tpu.testing import FakeClock, FaultProxy, ScriptedSchedule

pytestmark = pytest.mark.ingest

BASE = 1_700_000_000_000
N = 64


def frozen():
    """A wall clock that never ticks: under it, staged and unbatched
    writes must produce BIT-IDENTICAL stamps (the combiner's one
    wall-read-per-flush is unobservable when the clock stands still)."""
    return lambda: BASE


# ---------------------------------------------------------- Hlc.send_batch


class TestSendBatch:

    def test_equals_sequential_sends_under_frozen_clock(self):
        canonical = Hlc(BASE, 3, "n")
        seq = canonical
        seq_lts = []
        for _ in range(5):
            seq = Hlc.send(seq, millis=BASE + 7)
            seq_lts.append(seq.logical_time)
        batched, lts = Hlc.send_batch(canonical, 5, millis=BASE + 7)
        assert lts == seq_lts
        assert batched == seq

    def test_stamps_strictly_monotonic_and_dominate_canonical(self):
        canonical = Hlc(BASE, 0, "n")
        new, lts = Hlc.send_batch(canonical, 100, millis=BASE)
        assert all(a < b for a, b in zip(lts, lts[1:]))
        assert lts[0] > canonical.logical_time
        assert new.logical_time == lts[-1]

    def test_overflow_raises_before_stamping(self):
        canonical = Hlc(BASE, MAX_COUNTER - 1, "n")
        with pytest.raises(OverflowException):
            Hlc.send_batch(canonical, 3, millis=BASE)

    def test_drift_raises(self):
        canonical = Hlc(BASE + MAX_DRIFT + 1, 0, "n")
        with pytest.raises(ClockDriftException):
            Hlc.send_batch(canonical, 1, millis=BASE)

    def test_rejects_empty_batch(self):
        with pytest.raises(ValueError):
            Hlc.send_batch(Hlc(BASE, 0, "n"), 0, millis=BASE)


# ------------------------------------------------- staged == unbatched


def _write_script(c: DenseCrdt) -> None:
    c.put_batch([1, 5, 9], [10, 50, 90])
    c.put_batch([2, 5], [20, 55], tombs=[False, True])   # mixed putAll
    c.delete_batch([9])
    c.put_batch([], [])                                  # empty: one tick
    c.put_batch([3], [33])


def test_frozen_clock_bit_identity_with_unbatched():
    unbatched = DenseCrdt("n", N, wall_clock=frozen())
    staged = DenseCrdt("n", N, wall_clock=frozen())
    _write_script(unbatched)
    with staged.ingest():
        _write_script(staged)
    assert staged.canonical_time == unbatched.canonical_time
    assert staged.stats.puts == unbatched.stats.puts
    a, b = staged.record_map(), unbatched.record_map()
    assert a.keys() == b.keys()
    for slot in a:
        assert a[slot].hlc == b[slot].hlc, slot
        assert a[slot].value == b[slot].value, slot


def test_lww_outcome_matches_unbatched_under_ticking_clock():
    # A ticking clock makes the raw stamps differ (one wall read per
    # flush — the documented opt-in trade); the VALUES and tombstone
    # outcomes must still match the unbatched run exactly.
    unbatched = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    staged = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    _write_script(unbatched)
    with staged.ingest():
        _write_script(staged)
    a, b = staged.record_map(), unbatched.record_map()
    assert a.keys() == b.keys()
    assert {s: r.value for s, r in a.items()} == \
        {s: r.value for s, r in b.items()}


def test_hlc_monotonic_across_staged_groups():
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    with c.ingest():
        c.put_batch([0], [1])
        c.put_batch([1], [2])
        c.put_batch([2], [3])
        c.put_batch([3, 4], [9, 9])     # one group, one shared stamp
    rm = c.record_map()
    assert rm[0].hlc < rm[1].hlc < rm[2].hlc < rm[3].hlc
    assert rm[3].hlc == rm[4].hlc       # putAll batch-shares-one-stamp
    # all five stamps come from ONE wall read (consecutive counters)
    assert len({r.hlc.millis for r in rm.values()}) == 1
    assert c.canonical_time.logical_time == rm[4].hlc.logical_time


def test_duplicate_staged_slots_collapse_last_wins():
    c = DenseCrdt("n", N, wall_clock=frozen())
    with c.ingest() as wc:
        c.put_batch([7, 7, 7], [1, 2, 3])
        c.put_batch([7], [4])
        c.delete_batch([8])
        c.put_batch([8], [80])          # resurrects the tombstone
    assert wc.rows_committed == 2       # post-dedup: slots {7, 8}
    assert c.get(7) == 4
    assert c.get(8) == 80


# ------------------------------------------------ read-your-writes overlay


def test_overlay_answers_point_reads_without_flushing():
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    c.put_batch([0], [5])
    with c.ingest() as wc:
        c.put_batch([1], [11])
        c.delete_batch([0])
        assert c.get(1) == 11           # staged put visible
        assert c.get(0) is None         # staged delete shadows commit
        assert c.contains_slot(1)
        assert c.is_deleted(0) is True
        assert c.is_deleted(1) is False
        assert wc.flushes == 0          # none of the above flushed
        assert wc.pending_rows == 2
    assert c.get(1) == 11               # same answers after commit
    assert c.get(0) is None


def test_only_store_reads_count_a_store_wait():
    """A read the store answers waits out the device work queued on the
    store and counts that wait in `store_wait_seconds`; an overlay hit
    touches neither, and neither flushes."""
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    c.put_batch([0], [5])
    with c.ingest() as wc:
        c.put_batch([1], [11])
        before = c.store_wait_seconds
        assert c.get(1) == 11
        assert c.store_wait_seconds == before
        assert c.get(0) == 5
        assert c.store_wait_seconds > before
        assert wc.flushes == 0


def test_count_modified_since_includes_staged_rows():
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    c.put_batch([0], [1])
    watermark = c.canonical_time
    with c.ingest() as wc:
        # the bound is INCLUSIVE (at-or-after, map_crdt.dart:44-45):
        # slot 0 sits exactly at the watermark and counts
        assert c.count_modified_since(watermark) == 1
        c.put_batch([5, 6], [7, 8])
        assert c.count_modified_since(watermark) == 3
        assert c.count_modified_since(None) == 3
        assert wc.flushes == 0
    assert c.count_modified_since(watermark) == 3


# ----------------------------------------------------------- barriers


@pytest.mark.parametrize("surface", [
    lambda c: c.record_map(),
    lambda c: c.to_json(),
    lambda c: c.pack_since(None),
    lambda c: c.export_delta(),
    lambda c: c.get_slot_record(1),
    lambda c: c.store,
    lambda c: c.merge_records({}),
])
def test_bulk_surfaces_drain_first(surface):
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    with c.ingest() as wc:
        c.put_batch([1], [11])
        surface(c)
        assert wc.flushes == 1 and wc.pending_rows == 0
        c.put_batch([2], [22])          # window stays usable after
    assert c.get(1) == 11 and c.get(2) == 22


def test_merge_barrier_keeps_lww_order():
    # A remote record merged MID-WINDOW must lose to a staged write
    # that was issued later in wall order — the drain commits the
    # staged rows (with their pre-merge stamps) before the merge runs.
    clk = FakeClock(start=BASE)
    c = DenseCrdt("n", N, wall_clock=clk)
    remote = DenseCrdt("r", N, wall_clock=FakeClock(start=BASE))
    remote.put_batch([1], [999])
    with c.ingest():
        c.put_batch([1], [1])
        clk.advance(60_000)             # local write is much newer
        c.put_batch([1], [2])
        c.merge_records(remote.record_map())
    assert c.get(1) == 2


def test_checkpoint_save_includes_staged_rows(tmp_path):
    path = str(tmp_path / "snap.crdt")
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    with c.ingest():
        c.put_batch([4, 5], [44, 55])
        c.save(path)                    # barrier: snapshot is complete
    loaded = DenseCrdt.load("n", path)
    assert loaded.get(4) == 44 and loaded.get(5) == 55
    assert loaded.canonical_time == c.canonical_time


def test_gossip_round_drains_mid_flight_staging():
    # The round's watermark read sits AFTER the drain: staged rows get
    # stamps at-or-before the watermark, so the next delta round must
    # not re-send them — and the peer sees every staged write even
    # when the first transport attempt is dropped by the fault proxy.
    clk = FakeClock(start=BASE)
    a = GossipNode(DenseCrdt("a", N, wall_clock=clk),
                   rng=random.Random(7), sleep=lambda _s: None,
                   retry=RetryPolicy(max_attempts=3, base_delay=0.001))
    b = GossipNode(DenseCrdt("b", N, wall_clock=clk),
                   rng=random.Random(8), sleep=lambda _s: None)
    with a, b:
        sched = ScriptedSchedule([{"kind": "drop"}, None])
        with FaultProxy(b.host, b.port, sched) as proxy:
            a.add_peer("b", proxy.host, proxy.port)
            with a.crdt.ingest() as wc:
                a.crdt.put_batch([1, 2], [10, 20])
                assert a.sync_peer("b") == "ok"
                assert wc.pending_rows == 0      # round drained it
                a.crdt.put_batch([3], [30])      # staging still works
            assert a.sync_peer("b") == "ok"
            assert proxy.counters.get("drop") == 1
    assert a.peers["b"].stats.retries == 1
    for slot, val in ((1, 10), (2, 20), (3, 30)):
        assert b.crdt.get(slot) == val


# ----------------------------------------------------- window lifecycle


def test_auto_flush_threshold():
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    with c.ingest(auto_flush_rows=4) as wc:
        c.put_batch([0, 1, 2], [1, 1, 1])
        assert wc.flushes == 0
        c.put_batch([3, 4], [1, 1])     # backlog hits 5 >= 4
        assert wc.flushes == 1 and wc.pending_rows == 0
    assert wc.flushes == 1              # exit flush had nothing to do


def test_windows_do_not_nest_and_refuse_pipelined():
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    with c.ingest():
        with pytest.raises(RuntimeError, match="nest"):
            with c.ingest():
                pass
    with c.pipelined():
        with pytest.raises(RuntimeError, match="pipelined"):
            with c.ingest():
                pass


def test_pipelined_entry_drains_open_window():
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    with c.ingest() as wc:
        c.put_batch([1], [11])
        with c.pipelined():
            pass
        assert wc.flushes == 1 and wc.pending_rows == 0
    assert c.get(1) == 11


def test_body_exception_propagates_and_backlog_still_commits():
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    with pytest.raises(ValueError, match="boom"):
        with c.ingest():
            c.put_batch([1], [11])
            raise ValueError("boom")
    assert c.get(1) == 11               # exit flush ran regardless


def test_invalid_rows_fail_at_the_call_site():
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    with c.ingest() as wc:
        with pytest.raises(IndexError):
            c.put_batch([N + 1], [1])   # out of range: eager, like
        assert wc.pending_rows == 0     # unbatched — nothing staged


# ------------------------------------------------------- watch at commit


def test_watch_fires_at_commit_with_winning_values():
    c = DenseCrdt("n", N, wall_clock=FakeClock(start=BASE))
    rec = c.watch().record()
    keyed = c.watch(1).record()
    with c.ingest():
        c.put_batch([1, 2], [10, 20])
        c.put_batch([1], [30])          # same window: last wins
        c.delete_batch([2])
        assert rec.events == []         # nothing until commit
    assert sorted(rec.events) == [(1, 30), (2, None)]
    assert keyed.events == [(1, 30)]


# ------------------------------------------------------------ obs wiring


def test_flush_metrics_and_trigger_labels():
    from crdt_tpu.obs.registry import default_registry
    reg = default_registry()
    flushes = reg.counter("crdt_tpu_ingest_flush_total", "")
    rows = reg.counter("crdt_tpu_ingest_flush_rows_total", "")
    f0 = flushes.value(trigger="explicit", node="m")
    b0 = flushes.value(trigger="barrier", node="m")
    r0 = rows.value(node="m")
    c = DenseCrdt("m", N, wall_clock=FakeClock(start=BASE))
    with c.ingest() as wc:
        c.put_batch([1], [1])
        wc.flush()
        c.put_batch([2, 3], [2, 3])
        c.record_map()                  # barrier-trigger flush
    assert flushes.value(trigger="explicit", node="m") == f0 + 1
    assert flushes.value(trigger="barrier", node="m") == b0 + 1
    assert rows.value(node="m") == r0 + 3


# -------------------------------------------------------- keyed adapter


def test_keyed_adapter_stages_and_reads_through_overlay():
    kc = KeyedDenseCrdt(DenseCrdt("k", 8, wall_clock=FakeClock()))
    with kc.ingest() as wc:
        kc.put("x", 1)
        kc.put_all({"y": 2, "z": None})
        kc.delete("x")
        assert kc.get("x") is None and kc.is_deleted("x") is True
        assert kc.get("y") == 2 and kc.contains_key("y")
        assert wc.flushes == 0
    assert kc.map == {"y": 2}


# ------------------------------------------------------------- sharded


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 (virtual) devices")
def test_sharded_staged_matches_single_device_unbatched():
    mesh = make_fanin_mesh(2, 4)
    sharded = ShardedDenseCrdt("n", N, mesh, wall_clock=frozen())
    plain = DenseCrdt("n", N, wall_clock=frozen())
    _write_script(plain)
    with sharded.ingest():
        _write_script(sharded)
    assert sharded.canonical_time == plain.canonical_time
    a, b = sharded.record_map(), plain.record_map()
    assert a.keys() == b.keys()
    for slot in a:
        assert (a[slot].hlc, a[slot].value) == \
            (b[slot].hlc, b[slot].value), slot
    # the fused commit must land already laid out — one consistent
    # NamedSharding across every lane, same as before the window
    shardings = {str(getattr(sharded.store, f).sharding)
                 for f in sharded.store._fields}
    assert len(shardings) == 1
    assert "key" in shardings.pop()
