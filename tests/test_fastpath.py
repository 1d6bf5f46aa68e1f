"""Zero-copy fast path (docs/FASTPATH.md): arena-packed deltas whose
buffers reach ``sendmsg`` unchanged, the fused merge+repack relay
dispatch, the bounded pack cache, and the single-dispatch ingest
commits (touched-tile Mosaic kernel, sharded shard_map program).

The acceptance checks the ISSUE pins live here: buffer identity across
pack → frame (no hidden copy re-materializes a lane), bit-identical
``PackedDelta`` round-trips, and fused-relay equivalence with the
two-dispatch path it replaced."""

import socket
import struct
import threading

import numpy as np
import pytest

import jax

from crdt_tpu import DenseCrdt, FrameCodec
from crdt_tpu.models.dense_crdt import ShardedDenseCrdt
from crdt_tpu.net import recv_bytes_frame, send_bytes_frame
from crdt_tpu.obs.registry import default_registry
from crdt_tpu.ops.packing import (PackedDelta, arena_of, pack_rows,
                                  unpack_rows)
from crdt_tpu.parallel import make_fanin_mesh
from crdt_tpu.testing import FakeClock

pytestmark = pytest.mark.fastpath

BASE = 1_700_000_000_000
N = 64


def _copy_counter():
    return default_registry().counter(
        "crdt_tpu_pack_copy_bytes_total",
        "bytes copied between pack and frame (zero on the "
        "arena fast path)")


def _make(node="n", n_slots=N, **kw):
    return DenseCrdt(node, n_slots=n_slots,
                     wall_clock=FakeClock(start=BASE), **kw)


# ------------------------------------------------ zero-copy pack path


def test_pack_since_lanes_share_one_arena_and_frame_zero_copy():
    """The acceptance check: every lane of one packed delta roots at
    ONE arena allocation, `pack_rows` frames that same storage (the
    memoryviews' owners walk back to the identical buffer), and the
    pack-path copy counter does not move — the gather wrote the bytes
    `sendmsg` would ship."""
    c = _make()
    c.put_batch(list(range(16)), [v * 10 for v in range(16)])
    c.delete_batch([3, 7])
    before = _copy_counter().value(stage="pack_rows")
    packed, _ = c.pack_since(None)
    arena = arena_of(packed.slots)
    for lane in packed:
        if lane is not None:
            assert arena_of(lane) is arena
    meta, bufs = pack_rows(packed)
    for mv in bufs:
        assert isinstance(mv, memoryview)
        assert arena_of(mv.obj) is arena
    assert _copy_counter().value(stage="pack_rows") == before
    # The buffer id is stable across repeated framing of the same
    # delta — no per-send re-materialization.
    _, bufs2 = pack_rows(packed)
    assert [m.obj is m2.obj for m, m2 in zip(bufs, bufs2)] \
        == [True] * len(bufs)


def test_foreign_lane_copy_is_counted():
    """Hand-built deltas with wrong dtypes take the one legitimate
    normalization copy — and the counter records exactly it."""
    d = PackedDelta(slots=np.array([1, 2], np.int64),   # wrong: int64
                    lt=np.array([5, 6], np.int64),
                    node=np.array([0, 0], np.int32),
                    val=np.array([10, 20], np.int64),
                    tomb=np.array([0, 0], np.uint8))
    before = _copy_counter().value(stage="pack_rows")
    pack_rows(d)
    # only the slots lane (2 × int32 after normalization) was copied
    assert _copy_counter().value(stage="pack_rows") == before + 8


def test_packed_roundtrip_bit_identical():
    c = _make()
    c.put_batch(list(range(0, 40, 3)), list(range(100, 140, 3)))
    c.delete_batch([6, 12])
    packed, ids = c.pack_since(None)
    meta, bufs = pack_rows(packed)
    back = unpack_rows(meta, b"".join(bytes(b) for b in bufs))
    for a, b in zip(packed, back):
        if a is None:
            assert b is None
        else:
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
    # And the round-tripped delta merges to the identical store.
    r1 = _make("r")
    r2 = _make("r")
    r1.merge_packed(packed, ids)
    r2.merge_packed(back, ids)
    for l1, l2 in zip(r1.store, r2.store):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    assert r1.canonical_time == r2.canonical_time


# -------------------------------------------- frame layer regressions


def _recv_thread(sock, out):
    out.append(recv_bytes_frame(sock))


def test_multidim_memoryview_frames_by_nbytes():
    """Regression for the flat-cast trap: ``len()`` of a 2-D
    memoryview counts first-dimension ELEMENTS, so sizing the length
    prefix with it truncates the frame. A 2-D sem-style lane must
    frame all of its nbytes."""
    lane = np.arange(48, dtype=np.uint8).reshape(4, 12)
    mv = memoryview(lane)
    assert len(mv) == 4 and mv.nbytes == 48       # the trap, on record
    a, b = socket.socketpair()
    try:
        out = []
        t = threading.Thread(target=_recv_thread, args=(b, out))
        t.start()
        send_bytes_frame(a, [mv])
        t.join(5)
        assert out and out[0] == lane.tobytes()
    finally:
        a.close()
        b.close()


def test_codec_encode_sizes_multidim_bodies_by_nbytes():
    """`FrameCodec.encode`'s compress threshold and the zlib feed both
    consume buffer pieces via nbytes — a 2-D body above the threshold
    compresses and round-trips intact."""
    c = FrameCodec(compress=True, min_compress_bytes=64)
    body = np.zeros((8, 128), np.uint8)            # 1024 compressible B
    pieces = c.encode([memoryview(body)])
    assert pieces[0] == FrameCodec.TAG_ZLIB
    joined = b"".join(bytes(p) for p in pieces)
    assert c.decode(joined) == body.tobytes()


def test_vectored_send_many_buffers_loopback():
    """One frame scattered over many small views (the shape the arena
    pack emits) survives the vectored `sendmsg` path, partial sends
    and all."""
    rng = np.random.default_rng(7)
    parts = [rng.integers(0, 256, size=n, dtype=np.uint8)
             for n in (0, 3, 8192, 1, 65536, 0, 17)]
    a, b = socket.socketpair()
    try:
        out = []
        t = threading.Thread(target=_recv_thread, args=(b, out))
        t.start()
        send_bytes_frame(a, [memoryview(p) for p in parts])
        t.join(5)
        assert out and out[0] == b"".join(p.tobytes() for p in parts)
    finally:
        a.close()
        b.close()


# --------------------------------------------------- pack cache bound


def test_pack_cache_bounded_under_watermark_churn():
    """A churn storm — 100 rounds each advancing the canonical time —
    must leave the cache at its depth bound, with every overflow
    recorded in the evictions counter."""
    from crdt_tpu.hlc import Hlc
    ev = default_registry().counter(
        "crdt_tpu_pack_cache_evictions_total",
        "pack_since cache entries LRU-evicted at the "
        "PACK_CACHE_SLOTS depth bound")
    c = _make("churn")
    c.put_batch(list(range(8)), list(range(8)))
    before = ev.value(node="churn")
    # 100 peers at 100 distinct watermarks against one static store:
    # every `since` is a fresh cache key (a local write would instead
    # CLEAR the cache — invalidation, not eviction).
    for i in range(100):
        c.pack_since(Hlc(BASE - 1000 + i, 0, "peer"))
        assert len(c._pack_cache) <= c.PACK_CACHE_SLOTS
    assert ev.value(node="churn") >= before + (100 - c.PACK_CACHE_SLOTS)


# ------------------------------------------------- fused merge+repack


def test_merge_and_repack_matches_two_dispatch_path():
    """The fused relay must be observationally identical to
    `merge_packed` + `pack_since`: same store lanes, same canonical,
    bit-identical packed output."""
    src = _make("src")
    src.put_batch([2, 9, 30], [20, 90, 300])
    src.delete_batch([9])
    packed, ids = src.pack_since(None)

    fused = _make("r")
    plain = _make("r")
    for r in (fused, plain):
        r.put_batch([1], [11])
    watermark = fused.canonical_time
    assert watermark == plain.canonical_time

    out_f, ids_f = fused.merge_and_repack(packed, ids, since=watermark)
    plain.merge_packed(packed, ids)
    out_p, ids_p = plain.pack_since(watermark)

    for l1, l2 in zip(fused.store, plain.store):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    assert fused.canonical_time == plain.canonical_time
    assert ids_f == ids_p
    for a, b in zip(out_f, out_p):
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(a, b)


def test_merge_and_repack_seeds_next_round_pack_cache():
    """The fused dispatch caches the NEXT round's pack under the
    round's watermark — the follow-up `pack_since` is a hit returning
    the very same object, with no new dispatch."""
    hits = default_registry().counter("crdt_tpu_pack_cache_total", "")
    fused_ctr = default_registry().counter(
        "crdt_tpu_fused_repack_total",
        "gossip relays served by the fused merge+repack dispatch")
    src = _make("src2")
    src.put_batch([4, 5], [44, 55])
    packed, ids = src.pack_since(None)

    r = _make("relay")
    r.put_batch([0], [7])
    watermark = r.canonical_time
    f0 = fused_ctr.value(node="relay")
    seeded, _ = r.merge_and_repack(packed, ids, since=watermark)
    assert fused_ctr.value(node="relay") == f0 + 1
    h0 = hits.value(outcome="hit", node="relay")
    again, _ = r.pack_since(watermark)
    assert hits.value(outcome="hit", node="relay") == h0 + 1
    assert again is seeded                      # the exact seeded object
    # The seeded pack is what a peer at `watermark` needs: the rows
    # merged this round plus the relay's own at-watermark write (the
    # bound is inclusive, map_crdt.dart:44-45).
    assert set(seeded.slots.tolist()) == {0, 4, 5}


def test_merge_and_repack_empty_delta_falls_back():
    """k == 0 takes the fallback (`pack_since`), not the fused kernel —
    and still returns a well-formed (possibly empty) delta."""
    r = _make("fb")
    empty = PackedDelta(slots=np.empty(0, np.int32),
                        lt=np.empty(0, np.int64),
                        node=np.empty(0, np.int32),
                        val=np.empty(0, np.int64),
                        tomb=np.empty(0, np.uint8))
    out, ids = r.merge_and_repack(empty, [], since=None)
    assert out.k == 0 and ids == ["fb"]


# ------------------------------------------- single-dispatch ingest


def test_pallas_interpret_ingest_flush_matches_xla():
    """The touched-tile Mosaic scatter (interpret mode off-TPU) commits
    the identical store the lax scatter does."""
    from crdt_tpu.ops.pallas_merge import TILE
    a = DenseCrdt("i", n_slots=TILE, wall_clock=FakeClock(start=BASE),
                  executor="pallas-interpret")
    b = DenseCrdt("i", n_slots=TILE, wall_clock=FakeClock(start=BASE),
                  executor="xla")
    assert a._resident_for(TILE) and not b._resident_for(TILE)
    for c in (a, b):
        with c.ingest() as wc:
            c.put_batch([0, 1, TILE - 1], [10, 11, 12])
            c.put_batch([1, 700], [13, 14], tombs=[False, True])
        assert wc.flushes >= 1
    for l1, l2 in zip(a.store, b.store):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    assert a.canonical_time == b.canonical_time


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 (virtual) devices")
def test_sharded_ingest_flush_matches_plain():
    """The one-shard_map-program ingest commit matches the plain
    replica's flush bit for bit (occupied lanes)."""
    mesh = make_fanin_mesh(2, 4)
    sharded = ShardedDenseCrdt("s", N, mesh,
                               wall_clock=FakeClock(start=BASE))
    plain = DenseCrdt("s", N, wall_clock=FakeClock(start=BASE))
    for c in (sharded, plain):
        with c.ingest():
            c.put_batch(list(range(0, N, 5)), list(range(0, N, 5)))
            c.put_batch([0, 7], [100, 200], tombs=[True, False])
    occ = np.asarray(plain.store.occupied)
    np.testing.assert_array_equal(np.asarray(sharded.store.occupied),
                                  occ)
    for l1, l2 in zip(sharded.store, plain.store):
        np.testing.assert_array_equal(np.asarray(l1)[occ],
                                      np.asarray(l2)[occ])
    assert sharded.get(0) is None and sharded.get(7) == 200
