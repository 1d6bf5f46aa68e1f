"""Resident split planes (ops/pallas_scatter.py, docs/FASTPATH.md):
where the touched-tile commit engages, a `DenseCrdt` holds its store
as the nine 2-D `SplitStore` planes. These tests pin that the layout
changes nothing anyone can observe — lanes, answers, packs, digests,
GC, compaction, growth and checkpoints are bit-identical to the XLA
executor's — and that the served path (flush + point read) never
materializes the joined view."""

import socket
import time

import numpy as np
import pytest

from crdt_tpu import DenseCrdt, ServeTier
from crdt_tpu.net import (BINOP_DELETE, BINOP_GET, BINOP_PUT,
                          BINOP_ST_OK, BINOP_ST_OK_NULL, FrameCodec,
                          binop_round, recv_frame, send_frame)
from crdt_tpu.obs.device import default_ledger
from crdt_tpu.ops.pallas_merge import TILE
from crdt_tpu.testing import FakeClock

BASE = 1_700_000_000_000
N = 2 * TILE
FLUSH = "pallas.ingest_scatter_planes[interpret]"


def _make(node="a", n_slots=N, **kw):
    return DenseCrdt(node, n_slots=n_slots,
                     wall_clock=FakeClock(start=BASE), **kw)


def _delta(before, after):
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(before) | set(after)
            if after.get(k, 0) != before.get(k, 0)}


def _assert_same(a, ref):
    assert a.n_slots == ref.n_slots
    for f, l1, l2 in zip(ref.store._fields, a.store, ref.store):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2),
                                      err_msg=f)
    assert a.canonical_time == ref.canonical_time


@pytest.mark.parametrize("executor", ["xla", "pallas-interpret"])
def test_lanes_and_answers_bit_identical_to_xla(executor, tmp_path):
    a, ref = _make(executor=executor), _make(executor="xla")
    assert (a._planes is not None) == (executor == "pallas-interpret")
    assert ref._planes is None
    rng = np.random.default_rng(7)
    model = {}
    slots = rng.choice(N, size=300, replace=False)
    vals = rng.integers(-(1 << 62), 1 << 62, size=300)
    for c in (a, ref):
        with c.ingest() as wc:
            c.put_batch(slots[:200], vals[:200])
            c.delete_batch(slots[:20])
            wc.flush()
            c.put_batch(slots[200:], vals[200:],
                        tombs=np.arange(100) % 7 == 0)
        c.put_batch([slots[0], N - 1], [5, -6])      # unstaged
        c.delete_batch([slots[30]])
    model.update(zip(slots[:200].tolist(), vals[:200].tolist()))
    model.update((int(s), None) for s in slots[:20])
    model.update((int(s), None if i % 7 == 0 else int(v)) for i, (s, v)
                 in enumerate(zip(slots[200:], vals[200:])))
    model.update({int(slots[0]): 5, N - 1: -6, int(slots[30]): None})
    _assert_same(a, ref)
    probe = [int(s) for s in slots[::11]] + [N - 1, 0, 1234]
    for s in probe:
        assert a.get(s) == ref.get(s) == model.get(s)
        assert a.get_slot_record(s) == ref.get_slot_record(s)
        assert a.contains_slot(s) == ref.contains_slot(s)
        assert a.is_deleted(s) == ref.is_deleted(s)

    # merge_many from a peer that overwrites some slots, adds others
    peer = _make("p", executor="xla")
    peer.put_batch(rng.choice(N, size=64, replace=False),
                   rng.integers(0, 1 << 40, size=64))
    cs, ids = peer.export_delta()
    seen = []
    for c in (a, ref):
        stream = c.watch().record()
        c.merge_many([(cs, ids)])
        seen.append(sorted((e.key, e.value) for e in stream.events))
        stream.cancel()
    assert seen[0] == seen[1] and seen[0]
    _assert_same(a, ref)
    # the pre-split and the pipelined merge routes
    peer.put_batch(rng.choice(N, size=32, replace=False),
                   rng.integers(0, 1 << 40, size=32))
    scs, sids = peer.export_split_delta()
    for c in (a, ref):
        c.merge_split(scs, sids)
    _assert_same(a, ref)
    peer.delete_batch(rng.choice(N, size=16, replace=False))
    cs, ids = peer.export_delta()
    for c in (a, ref):
        with c.pipelined():
            c.merge_many([(cs, ids)])
    _assert_same(a, ref)

    pa, ia = a.pack_since(None)
    pr, ir = ref.pack_since(None)
    assert ia == ir
    for f in pr._fields:
        x, y = getattr(pa, f), getattr(pr, f)
        assert (x is None) == (y is None)
        if y is not None:
            np.testing.assert_array_equal(x, y, err_msg=f)
    assert int(a.digest_tree().root) == int(ref.digest_tree().root)

    stability = ref.canonical_time
    assert (a.gc_purge(stability, drift_slack_ms=0)
            == ref.gc_purge(stability, drift_slack_ms=0) > 0)
    _assert_same(a, ref)
    np.testing.assert_array_equal(a.compact(), ref.compact())
    _assert_same(a, ref)

    for c in (a, ref):
        c.grow(3 * TILE)
        c.put_batch([N + 3], [77])
    _assert_same(a, ref)
    assert a.get(N + 3) == 77 and a.get(N + 4) is None
    assert (a._planes is not None) == (executor == "pallas-interpret")

    path = str(tmp_path / "snap")
    a.save(path)
    back = DenseCrdt.load("a", path, wall_clock=FakeClock(start=BASE),
                          executor=executor)
    assert (back._planes is not None) == (executor == "pallas-interpret")
    _assert_same(back, ref)
    assert int(back.digest_tree().root) == int(ref.digest_tree().root)


def test_escaped_store_snapshot_survives_later_commits():
    a = _make(executor="pallas-interpret")
    with a.ingest():
        a.put_batch([1, 2, TILE + 1], [10, 20, 30])
    snap, vals = a.store, a.values
    with a.ingest() as wc:
        a.put_batch([1, TILE + 1], [99, 98])
        wc.flush()
        a.delete_batch([2])
    a.put_batch([2], [21])
    assert [int(snap.val[i]) for i in (1, 2, TILE + 1)] == [10, 20, 30]
    assert int(vals[1]) == 10 and not bool(snap.tomb[2])
    assert [a.get(i) for i in (1, 2, TILE + 1)] == [99, 21, 98]


def test_flush_and_get_loop_never_builds_the_view():
    led = default_ledger()
    a = _make(executor="pallas-interpret")
    with a.ingest() as wc:
        before = led.as_dict()
        for tick in range(4):
            a.put_batch([tick, TILE + tick], [tick, -tick])
            if tick == 2:
                a.delete_batch([0])
            wc.flush()
            assert a.get(0) == (None if tick >= 2 else 0)
            assert a.get(TILE + tick) == -tick
        moved = _delta(before, led.as_dict())
    assert moved.get("dense.store_view", 0) == 0
    assert moved.get("dense.store_split", 0) == 0
    assert moved[FLUSH] == 4
    assert moved["dense.point_read"] == 8


def test_store_is_held_in_one_form_at_a_time():
    # A cold path moves the store to lanes and drops the planes; the
    # next point read or flush moves it back and drops the lanes, so
    # the store is held twice only while a move runs.
    led = default_ledger()
    a = _make(executor="pallas-interpret")
    with a.ingest():
        a.put_batch([1, TILE + 2], [10, 20])
    assert a._planes is not None and a._store_lanes is None
    before = led.as_dict()
    root = a.digest_tree().root
    assert a._planes is None and a._store_lanes is not None
    assert a.digest_tree().root == root       # cached: no second move
    assert a.get(1) == 10
    assert a._planes is not None and a._store_lanes is None
    with a.ingest():
        a.put_batch([3], [30])
    assert _delta(before, led.as_dict()).get("dense.store_view") == 1
    assert _delta(before, led.as_dict()).get("dense.store_split") == 1
    assert a._planes is not None and a._store_lanes is None
    # A given store is split at construction and not kept.
    b = DenseCrdt("b", n_slots=N, store=a.store, executor="pallas-interpret",
                  node_ids=a._table.ids())
    assert b._planes is not None and b._store_lanes is None
    assert [b.get(s) for s in (1, 3, TILE + 2)] == [10, 30, 20]


def _connect(tier):
    sock = socket.create_connection((tier.host, tier.port), timeout=10.0)
    sock.settimeout(10.0)
    return sock


def _req(sock, obj, codec=None):
    send_frame(sock, obj, None, codec)
    return recv_frame(sock, deadline=time.monotonic() + 10.0, codec=codec)


def test_serve_tier_over_planes_reads_back_every_acked_write():
    crdt = _make(executor="pallas-interpret")
    led = default_ledger()
    rng = np.random.default_rng(11)
    keys = rng.choice(N, size=48, replace=False)
    vals = rng.integers(-(1 << 62), 1 << 62, size=48)
    acked = {}
    with ServeTier(crdt, flush_interval=0.002) as tier:
        before = led.as_dict()
        with _connect(tier) as sock:
            for k, v in zip(keys[:16], vals[:16]):
                assert _req(sock, {"op": "put", "slot": int(k),
                                   "value": int(v)}) == {"ok": True}
                acked[int(k)] = int(v)
            assert _req(sock, {"op": "delete",
                               "slot": int(keys[0])}) == {"ok": True}
            acked[int(keys[0])] = None
            for k in keys[:16]:
                got = _req(sock, {"op": "get", "slot": int(k)})
                assert got == {"ok": True, "value": acked[int(k)]}
            send_frame(sock, {"op": "bye"})
        with _connect(tier) as sock:
            assert _req(sock, {"op": "hello", "proto": 1,
                               "caps": ["binop"]})["ok"]
            codec = FrameCodec(compress=False)
            ks, vs = keys[16:], vals[16:]
            status, _, _ = binop_round(
                sock, [BINOP_PUT] * len(ks), ks, vs,
                deadline=time.monotonic() + 10.0, codec=codec)
            assert list(status) == [BINOP_ST_OK] * len(ks)
            acked.update(zip(ks.tolist(), vs.tolist()))
            status, _, _ = binop_round(
                sock, [BINOP_DELETE, BINOP_PUT], ks[:2], [0, 5],
                deadline=time.monotonic() + 10.0, codec=codec)
            assert list(status) == [BINOP_ST_OK] * 2
            acked[int(ks[0])], acked[int(ks[1])] = None, 5
            every = np.asarray(list(acked))
            status, got, _ = binop_round(
                sock, [BINOP_GET] * len(every), every,
                np.zeros(len(every), np.int64),
                deadline=time.monotonic() + 10.0, codec=codec)
            for s, st, v in zip(every.tolist(), status, got):
                if acked[s] is None:
                    assert st == BINOP_ST_OK_NULL
                else:
                    assert st == BINOP_ST_OK and int(v) == acked[s]
            send_frame(sock, {"op": "bye"}, None, codec)
        moved = _delta(before, led.as_dict())
    assert moved.get("dense.store_view", 0) == 0
    assert moved.get(FLUSH, 0) >= 1
    assert crdt._planes is not None
    for s, v in acked.items():
        assert crdt.get(s) == v
