"""Serving-tier suite (docs/SERVING.md): session multiplexing onto
the combiner tick, admission watermark, cold-lane bounds, and wire
compatibility with every client generation — negotiated
`PeerConnection` sessions (packed + merkle) and pre-hello legacy
peers — in both directions."""

import json
import socket
import time

import numpy as np
import pytest

from crdt_tpu import (DenseCrdt, FederatedTier, PeerConnection,
                      ServeTier, SyncTransportError, default_registry,
                      fetch_metrics, sync_merkle_over_conn,
                      sync_over_tcp, sync_packed_over_conn)
from crdt_tpu.net import (BINOP_DELETE, BINOP_GET, BINOP_PUT,
                          BINOP_ST_MOVED, BINOP_ST_OK,
                          BINOP_ST_OK_NULL, BINOP_ST_REJECTED,
                          FrameCodec, binop_round,
                          encode_binop_request, recv_frame,
                          send_bytes_frame, send_frame)
from crdt_tpu.testing import FaultProxy, ScriptedSchedule

pytestmark = pytest.mark.serve


def _connect(tier):
    sock = socket.create_connection((tier.host, tier.port),
                                    timeout=10.0)
    sock.settimeout(10.0)
    return sock


def _req(sock, obj, codec=None):
    send_frame(sock, obj, None, codec)
    return recv_frame(sock, deadline=time.monotonic() + 10.0,
                      codec=codec)


def _binop_session(host, port, extra_caps=()):
    """Negotiated binary-lane session: hello offering binop (plus any
    extra caps), post-hello tagged framing with no compression."""
    sock = socket.create_connection((host, port), timeout=10.0)
    sock.settimeout(10.0)
    reply = _req(sock, {"op": "hello", "proto": 1,
                        "caps": ["binop", *extra_caps]})
    assert reply["ok"] and "binop" in reply["caps"]
    return sock, FrameCodec(compress=False)


# --- serve-only ops: put / get / delete over the framed wire ---

def test_put_get_delete_roundtrip():
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt, flush_interval=0.002) as tier:
        with _connect(tier) as sock:
            assert _req(sock, {"op": "put", "slot": 3,
                               "value": 42}) == {"ok": True}
            # read-your-writes: the ack resolved AFTER the commit, so
            # the overlay/store answers immediately.
            assert _req(sock, {"op": "get", "slot": 3}) \
                == {"ok": True, "value": 42}
            assert _req(sock, {"op": "delete", "slot": 3}) \
                == {"ok": True}
            assert _req(sock, {"op": "get", "slot": 3})["value"] is None
            send_frame(sock, {"op": "bye"})
    # tier stopped -> ingest window closed; direct reads are safe.
    assert crdt.get(3) is None


def test_malformed_write_rejected_session_survives():
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        with _connect(tier) as sock:
            for bad in ({"op": "put", "slot": 999, "value": 1},
                        {"op": "put", "slot": -1, "value": 1},
                        {"op": "put", "slot": 1, "value": "x"},
                        {"op": "get", "slot": "nope"}):
                reply = _req(sock, bad)
                assert reply["ok"] is False
                assert reply["code"] == "write_rejected"
            # ...and the session is still alive afterwards.
            assert _req(sock, {"op": "put", "slot": 5,
                               "value": 7}) == {"ok": True}
            send_frame(sock, {"op": "bye"})
    assert crdt.get(5) == 7


def test_out_of_range_and_bool_writes_rejected_flusher_survives():
    """An int outside int64 passes `isinstance(value, int)` but would
    blow up the flush tick's np.int64 conversion — it must be rejected
    per-write at the session, and the flusher must survive even if
    something slips through (a dead flusher hangs EVERY later ack).
    JSON true/false are ints to isinstance and must be rejected too."""
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        with _connect(tier) as sock:
            for bad in ({"op": "put", "slot": 1, "value": 2 ** 63},
                        {"op": "put", "slot": 1, "value": -(2 ** 63) - 1},
                        {"op": "put", "slot": 1, "value": 2 ** 200},
                        {"op": "put", "slot": True, "value": 1},
                        {"op": "put", "slot": 1, "value": False},
                        {"op": "delete", "slot": False},
                        {"op": "get", "slot": True}):
                reply = _req(sock, bad)
                assert reply["ok"] is False
                assert reply["code"] == "write_rejected"
            # int64 boundaries themselves are legal...
            assert _req(sock, {"op": "put", "slot": 2,
                               "value": 2 ** 63 - 1}) == {"ok": True}
            # ...and the flusher is still ticking afterwards.
            assert _req(sock, {"op": "put", "slot": 5,
                               "value": 7}) == {"ok": True}
            assert _req(sock, {"op": "get", "slot": 5}) \
                == {"ok": True, "value": 7}
            send_frame(sock, {"op": "bye"})
    assert crdt.get(5) == 7


def test_malformed_digest_more_replies_merkle_rejected():
    """A 'more' entry that is not a [level, idx] pair must get the
    merkle_rejected reply (like SyncServer), not an unhandled
    TypeError that kills the session without a reply."""
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        for more in ([5], ["xy"], [[0]], [[0, [0], 9]]):
            with _connect(tier) as sock:
                reply = _req(sock, {"op": "digest", "level": 0,
                                    "idx": [0], "more": more})
                assert reply["code"] == "merkle_rejected"


def test_idle_timeout_is_clean_close_not_a_drop():
    """Routine idle expiry must not inflate dropped_sessions — the
    bench's zero-dropped acceptance criterion reads that counter."""
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt, idle_timeout=0.2) as tier:
        with _connect(tier) as sock:
            assert _req(sock, {"op": "put", "slot": 1,
                               "value": 1}) == {"ok": True}
            # park past idle_timeout: the server closes cleanly (EOF)
            assert recv_frame(sock,
                              deadline=time.monotonic() + 10.0) is None
        assert tier.idle_closed_sessions == 1
        assert tier.dropped_sessions == 0


def test_unknown_op_hangs_up():
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        with _connect(tier) as sock:
            reply = _req(sock, {"op": "frobnicate"})
            assert reply["code"] == "unknown_op"
            assert recv_frame(sock,
                              deadline=time.monotonic() + 10.0) is None


# --- the tentpole property: N writers, ONE combiner tick ---

def test_many_sessions_share_one_combiner_tick():
    crdt = DenseCrdt("a", n_slots=256)
    flushes = default_registry().counter(
        "crdt_tpu_ingest_flush_total",
        "write-combiner flushes by trigger")
    before = flushes.value(trigger="tick", node="a")
    with ServeTier(crdt, flush_interval=0.05) as tier:
        socks = [_connect(tier) for _ in range(8)]
        try:
            # All eight sessions write BEFORE any reads its ack: the
            # writes land in the same queue window and commit as one
            # put_batch + one combiner flush.
            for i, s in enumerate(socks):
                send_frame(s, {"op": "put", "slot": i, "value": i * 10})
            for s in socks:
                assert recv_frame(
                    s, deadline=time.monotonic() + 10.0) == {"ok": True}
            ticks = flushes.value(trigger="tick", node="a") - before
            # 8 writers, at most 2 ticks (2 only if a tick boundary
            # happened to split the sends) — never one flush per write.
            assert 1 <= ticks <= 2
        finally:
            for s in socks:
                s.close()
    for i in range(8):
        assert crdt.get(i) == i * 10
    assert tier.dropped_sessions == 0


# --- admission watermark ---

def test_admission_watermark_sheds_with_busy():
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt, max_sessions=2) as tier:
        c1 = PeerConnection(tier.host, tier.port, timeout=5.0)
        c2 = PeerConnection(tier.host, tier.port, timeout=5.0)
        c3 = PeerConnection(tier.host, tier.port, timeout=5.0)
        try:
            c1.ensure()
            c2.ensure()
            with pytest.raises(SyncTransportError, match="busy"):
                c3.ensure()
            # Retryable refusal, NOT the legacy-downgrade signal.
            assert c3.legacy is False
            assert tier.shed_count >= 1
            shed = default_registry().counter(
                "crdt_tpu_serve_shed_total",
                "requests shed for backpressure (admission watermark "
                "or cold-join lane bound)")
            assert shed.value(lane="admission", node="a") >= 1
            # Freeing a slot readmits the shed client (bye is
            # processed asynchronously server-side, so poll).
            c1.close()
            for _ in range(500):
                try:
                    c3.ensure()
                    break
                except SyncTransportError:
                    time.sleep(0.01)
            else:
                raise AssertionError("slot never freed after close")
            assert "packed" in c3.caps
        finally:
            for c in (c1, c2, c3):
                c.close()


def test_hello_negotiates_full_caps():
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        with PeerConnection(tier.host, tier.port, timeout=5.0) as conn:
            conn.ensure()
            assert {"zlib", "packed", "semantics",
                    "merkle", "trace"} <= conn.caps
            assert conn.codec is not None


# --- cold-join slow lane ---

def test_cold_lane_bound_sheds_digest_with_busy():
    crdt = DenseCrdt("a", n_slots=64)
    crdt.put_batch([1], [1])
    joiner = DenseCrdt("b", n_slots=64)
    with ServeTier(crdt, cold_lane_depth=0) as tier:
        with PeerConnection(tier.host, tier.port, timeout=5.0) as conn:
            with pytest.raises(SyncTransportError, match="busy"):
                sync_merkle_over_conn(joiner, conn)
        assert tier.shed_count >= 1
        shed = default_registry().counter(
            "crdt_tpu_serve_shed_total",
            "requests shed for backpressure (admission watermark "
            "or cold-join lane bound)")
        assert shed.value(lane="cold", node="a") >= 1


def test_merkle_cold_join_through_tier():
    crdt = DenseCrdt("a", n_slots=64)
    slots = list(range(0, 64, 7))
    crdt.put_batch(slots, [s * 3 + 1 for s in slots])
    joiner = DenseCrdt("b", n_slots=64)
    with ServeTier(crdt) as tier:
        with PeerConnection(tier.host, tier.port, timeout=5.0) as conn:
            stats = {}
            sync_merkle_over_conn(joiner, conn, _stats=stats)
            assert stats["rounds"] >= 1
    for s in slots:
        assert joiner.get(s) == s * 3 + 1


# --- wire compat: negotiated packed sessions, both directions ---

def test_packed_round_through_tier_converges_both_ways():
    served = DenseCrdt("a", n_slots=64)
    client = DenseCrdt("b", n_slots=64)
    served.put_batch([1, 2], [10, 20])
    client.put_batch([5], [50])
    with ServeTier(served) as tier:
        with PeerConnection(tier.host, tier.port, timeout=5.0) as conn:
            mark = sync_packed_over_conn(client, conn, since=None)
            assert client.get(1) == 10 and client.get(2) == 20
            for _ in range(6):
                with tier.lock:
                    before = (str(served.canonical_time),
                              str(client.canonical_time))
                mark = sync_packed_over_conn(client, conn, since=mark)
                with tier.lock:
                    after = (str(served.canonical_time),
                             str(client.canonical_time))
                if after == before:
                    break
            else:
                raise AssertionError(
                    "clocks never settled through the tier")
    assert served.get(5) == 50
    assert client.get(5) == 50
    assert served.get(1) == 10 and served.get(2) == 20


def test_empty_packed_delta_does_not_wedge_stop():
    # An empty delta's frame carries zero-length lane views; handed to
    # CPython 3.12's vectored writelines they made sendmsg return 0
    # forever and stop() time out behind the wedged transport.
    served = DenseCrdt("a", n_slots=64)
    client = DenseCrdt("b", n_slots=64)
    tier = ServeTier(served).start()
    try:
        with PeerConnection(tier.host, tier.port, timeout=5.0) as conn:
            mark = sync_packed_over_conn(client, conn, since=None)
            sync_packed_over_conn(client, conn, since=mark)
    finally:
        t0 = time.monotonic()
        tier.stop()
    assert time.monotonic() - t0 < 5.0


def test_frame_pieces_drop_zero_length_views():
    from crdt_tpu.serve import frame_pieces
    pieces = frame_pieces([b"ab", memoryview(b""), np.zeros(0, np.int64)])
    assert [bytes(p) for p in pieces] == [b"\x00\x00\x00\x02", b"ab"]


def test_writes_landed_mid_session_reach_packed_pulls():
    served = DenseCrdt("a", n_slots=64)
    client = DenseCrdt("b", n_slots=64)
    with ServeTier(served) as tier:
        # A serve-session write...
        with _connect(tier) as wsock:
            assert _req(wsock, {"op": "put", "slot": 9,
                                "value": 99}) == {"ok": True}
            send_frame(wsock, {"op": "bye"})
        # ...is visible to a packed replication pull on the same tier
        # (the pack path drains the combiner as its barrier).
        with PeerConnection(tier.host, tier.port, timeout=5.0) as conn:
            sync_packed_over_conn(client, conn, since=None)
    assert client.get(9) == 99


# --- wire compat: pre-hello legacy JSON peers ---

def test_legacy_pre_hello_json_round():
    served = DenseCrdt("a", n_slots=64)
    legacy = DenseCrdt("b", n_slots=64)
    served.put_batch([2], [22])
    legacy.put_batch([4], [44])
    with ServeTier(served) as tier:
        # sync_over_tcp never sends hello: byte-identical legacy wire.
        sync_over_tcp(legacy, tier.host, tier.port)
        assert legacy.get(2) == 22
        with tier.lock:
            assert served.get(4) == 44
    assert served.get(4) == 44


# --- observability surface ---

def test_metrics_op_reports_serve_instruments():
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        with _connect(tier) as sock:
            assert _req(sock, {"op": "put", "slot": 1,
                               "value": 2}) == {"ok": True}
            assert _req(sock, {"op": "get", "slot": 1}) == {
                "ok": True, "value": 2}
            send_frame(sock, {"op": "bye"})
        snap = fetch_metrics(tier.host, tier.port)
    assert "crdt_tpu_serve_sessions" in snap["gauges"]
    assert "crdt_tpu_serve_ops_total" in snap["counters"]
    assert "crdt_tpu_serve_ack_seconds" in snap["histograms"]
    assert "crdt_tpu_serve_read_phase_seconds" in snap["histograms"]


# --- ack attribution: queue_wait / replica_wait / stamp / scatter /
# ack_write ---

ACK_PHASES = ("queue_wait", "replica_wait", "stamp", "scatter",
              "ack_write")


def test_ack_phase_attribution_sums_to_ack():
    """Every acked write decomposes into queue_wait + replica_wait +
    stamp + scatter + ack_write; the phase-histogram sums must
    reconstruct the ack histogram's sum (per-write observation, shared
    tick legs)."""
    crdt = DenseCrdt("phase-a", n_slots=64)
    node = str(crdt.node_id)
    reg = default_registry()
    ack = reg.histogram("crdt_tpu_serve_ack_seconds")
    phase = reg.histogram("crdt_tpu_serve_ack_phase_seconds")

    def _sum(h, **labels):
        return sum(s["sum"] for s in h.samples()
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    ack0 = _sum(ack, node=node)
    with ServeTier(crdt, flush_interval=0.002) as tier:
        with _connect(tier) as sock:
            for i in range(20):
                assert _req(sock, {"op": "put", "slot": i,
                                   "value": i})["ok"] is True
            send_frame(sock, {"op": "bye"})
    ack_sum = _sum(ack, node=node) - ack0
    phases = {p: _sum(phase, node=node, phase=p) for p in ACK_PHASES}
    counts = {p: sum(s["count"] for s in phase.samples()
                     if s["labels"] == {"node": node, "phase": p})
              for p in ACK_PHASES}
    # one observation per phase per acked write
    assert counts["queue_wait"] == 20
    assert counts == {p: 20 for p in counts}
    assert phases["stamp"] > 0 and phases["scatter"] > 0
    total = sum(phases.values())
    assert total == pytest.approx(ack_sum, rel=0.10), \
        (phases, ack_sum)


def test_rejected_tick_observes_ack_but_not_phases():
    """A failed tick still acks (with the rejection) but attributes
    nothing — phase sums must only ever cover committed writes."""
    crdt = DenseCrdt("phase-r", n_slots=64)
    node = str(crdt.node_id)
    reg = default_registry()
    phase = reg.histogram("crdt_tpu_serve_ack_phase_seconds")

    def _count(**labels):
        return sum(s["count"] for s in phase.samples()
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    before = _count(node=node)
    with ServeTier(crdt, flush_interval=0.002) as tier:
        # an in-range slot whose value overflows int64 passes the
        # session-side guard shape but np.fromiter(int64) rejects the
        # WHOLE tick
        import crdt_tpu.serve as serve_mod
        orig = serve_mod._value_ok
        serve_mod._value_ok = lambda v: True
        try:
            with _connect(tier) as sock:
                reply = _req(sock, {"op": "put", "slot": 1,
                                    "value": 1 << 80})
                assert reply["ok"] is False
                assert reply["code"] == "write_rejected"
                send_frame(sock, {"op": "bye"})
        finally:
            serve_mod._value_ok = orig
    assert _count(node=node) == before


# --- binary client op lane (docs/WIRE.md) ---

def test_binop_batched_roundtrip_reads_own_frame():
    """One frame of puts + a delete + gets; one reply frame; gets
    observe writes from the SAME batch (read-your-writes extends into
    the frame — gets run after the batch commits)."""
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt, flush_interval=0.002) as tier:
        sock, codec = _binop_session(tier.host, tier.port)
        with sock:
            ops = [BINOP_PUT, BINOP_PUT, BINOP_PUT, BINOP_DELETE,
                   BINOP_GET, BINOP_GET]
            slots = [3, 4, 5, 4, 3, 4]
            vals = [30, 40, 50, 0, 0, 0]
            status, values, details = binop_round(
                sock, ops, slots, vals,
                deadline=time.monotonic() + 10.0, codec=codec)
            assert list(status) == [BINOP_ST_OK] * 4 \
                + [BINOP_ST_OK, BINOP_ST_OK_NULL]
            assert values is not None and int(values[4]) == 30
            assert details == []
            send_frame(sock, {"op": "bye"}, None, codec)
    assert crdt.get(3) == 30
    assert crdt.get(4) is None
    assert crdt.get(5) == 50


def test_binop_per_op_error_isolation():
    """A bad slot inside a well-formed frame fails ITS status byte
    with an indexed detail; its batchmates commit and the session
    stays open for the next frame."""
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt, flush_interval=0.002) as tier:
        sock, codec = _binop_session(tier.host, tier.port)
        with sock:
            status, values, details = binop_round(
                sock, [BINOP_PUT, BINOP_PUT, BINOP_PUT],
                [1, 9999, 2], [10, 1, 20],
                deadline=time.monotonic() + 10.0, codec=codec)
            assert list(status) == [BINOP_ST_OK, BINOP_ST_REJECTED,
                                    BINOP_ST_OK]
            assert details == [{"i": 1, "code": "write_rejected",
                                "error": "bad slot"}]
            # ...and the next frame on the same session still works.
            status, _, details = binop_round(
                sock, [BINOP_GET], [1], [0],
                deadline=time.monotonic() + 10.0, codec=codec)
            assert list(status) == [BINOP_ST_OK]
            send_frame(sock, {"op": "bye"}, None, codec)
    assert crdt.get(1) == 10
    assert crdt.get(2) == 20


def test_binop_malformed_frame_is_protocol_violation():
    """A structurally bad binop frame (truncated rows) hangs the
    session up — protocol violation, not a per-op error — and the
    tier survives it."""
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        sock, codec = _binop_session(tier.host, tier.port)
        with sock:
            pieces = encode_binop_request([BINOP_PUT, BINOP_PUT],
                                          [1, 2], [10, 20])
            body = b"".join(bytes(p) for p in pieces)[:-5]
            send_bytes_frame(sock, [body], None, codec)
            assert recv_frame(sock, deadline=time.monotonic() + 10.0,
                              codec=codec) is None
        # the tier is still serving
        with _connect(tier) as sock2:
            assert _req(sock2, {"op": "put", "slot": 7,
                                "value": 70}) == {"ok": True}
            send_frame(sock2, {"op": "bye"})
    assert crdt.get(7) == 70


def test_binop_frame_without_negotiation_hangs_up():
    """A session that never agreed `binop` sending a 0xB1 frame is a
    protocol violation (the server parses it as JSON and fails) —
    byte-compat: pre-binop behavior is fully governed by hello."""
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        with _connect(tier) as sock:
            send_bytes_frame(sock, encode_binop_request(
                [BINOP_PUT], [1], [10]))
            assert recv_frame(
                sock, deadline=time.monotonic() + 10.0) is None
        with _connect(tier) as sock2:
            assert _req(sock2, {"op": "put", "slot": 1,
                                "value": 11}) == {"ok": True}
            send_frame(sock2, {"op": "bye"})
    assert crdt.get(1) == 11


def test_binop_wire_compat_new_client_pre_binop_server():
    """A new client offering `binop` against a pre-binop server: the
    cap is simply not agreed and the session speaks today's JSON
    dialect byte-identically."""
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        # Simulate the pre-binop server generation: same caps surface
        # minus the new lane.
        orig = ServeTier._caps
        ServeTier._caps = lambda self: orig(self) - {"binop"}
        try:
            with _connect(tier) as sock:
                reply = _req(sock, {"op": "hello", "proto": 1,
                                    "caps": ["binop", "packed"]})
                assert reply["ok"] is True
                assert "binop" not in reply["caps"]
                assert "packed" in reply["caps"]
                codec = FrameCodec(compress=False)
                assert _req(sock, {"op": "put", "slot": 2,
                                   "value": 22},
                            codec) == {"ok": True}
                assert _req(sock, {"op": "get", "slot": 2}, codec) \
                    == {"ok": True, "value": 22}
                send_frame(sock, {"op": "bye"}, None, codec)
        finally:
            ServeTier._caps = orig
    assert crdt.get(2) == 22


def test_binop_moved_and_stale_epoch_redirects():
    """Foreign slots in a binop frame answer MOVED (detail carries the
    owner + epoch), local ops in the same frame commit; a stale frame
    epoch refuses the whole batch with MOVED, same taxonomy as the
    JSON lane."""
    with FederatedTier(256, partitions=2,
                       flush_interval=0.002) as fed:
        tier = fed.tiers[0]
        own = next(s for s in range(256)
                   if fed.table.owner_of(s) == tier.router.addr)
        foreign = next(s for s in range(256)
                       if fed.table.owner_of(s) != tier.router.addr)
        sock, codec = _binop_session(tier.host, tier.port,
                                     extra_caps=["federation"])
        with sock:
            status, _, details = binop_round(
                sock, [BINOP_PUT, BINOP_PUT], [own, foreign],
                [5, 6], epoch=fed.table.epoch,
                deadline=time.monotonic() + 10.0, codec=codec)
            assert status[0] == BINOP_ST_OK
            assert status[1] == BINOP_ST_MOVED
            moved = [d for d in details if d.get("i") == 1]
            assert moved and moved[0]["code"] == "moved"
            assert moved[0]["owner"] != tier.router.addr
            # stale epoch: the WHOLE frame is refused
            status, _, details = binop_round(
                sock, [BINOP_PUT], [own], [7],
                epoch=fed.table.epoch + 1,
                deadline=time.monotonic() + 10.0, codec=codec)
            assert status[0] == BINOP_ST_MOVED
            send_frame(sock, {"op": "bye"}, None, codec)
        with tier.lock:
            assert tier.crdt.get(own) == 5


# --- fault injection on the client wire ---

def test_fault_mid_hello_truncate_tier_survives():
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        sched = ScriptedSchedule([{"kind": "truncate", "after": 6}])
        with FaultProxy(tier.host, tier.port,
                        schedule=sched) as proxy:
            sock = socket.create_connection((proxy.host, proxy.port),
                                            timeout=10.0)
            sock.settimeout(10.0)
            with sock:
                send_frame(sock, {"op": "hello", "proto": 1,
                                  "caps": ["binop"]})
                assert recv_frame(
                    sock, deadline=time.monotonic() + 10.0) is None
            assert proxy.counters.get("truncate", 0) == 1
        # the tier took a half-hello and kept serving
        with _connect(tier) as sock2:
            assert _req(sock2, {"op": "put", "slot": 3,
                                "value": 33}) == {"ok": True}
            send_frame(sock2, {"op": "bye"})
    assert crdt.get(3) == 33


def test_fault_mid_batch_truncate_tier_survives():
    """The cut lands INSIDE a binop batch frame (after a clean hello):
    the client sees a dead socket, the tier sees a partial frame and
    drops the session — and keeps serving everyone else."""
    crdt = DenseCrdt("a", n_slots=64)
    with ServeTier(crdt) as tier:
        hello = {"op": "hello", "proto": 1, "caps": ["binop"]}
        hello_bytes = 4 + len(json.dumps(hello).encode())
        sched = ScriptedSchedule(
            [{"kind": "truncate", "after": hello_bytes + 9}])
        with FaultProxy(tier.host, tier.port,
                        schedule=sched) as proxy:
            sock = socket.create_connection((proxy.host, proxy.port),
                                            timeout=10.0)
            sock.settimeout(10.0)
            with sock:
                reply = _req(sock, hello)
                assert reply["ok"] and "binop" in reply["caps"]
                codec = FrameCodec(compress=False)
                with pytest.raises(SyncTransportError):
                    binop_round(sock,
                                [BINOP_PUT] * 4, [1, 2, 3, 4],
                                [10, 20, 30, 40],
                                deadline=time.monotonic() + 10.0,
                                codec=codec)
            assert proxy.counters.get("truncate", 0) == 1
        with _connect(tier) as sock2:
            assert _req(sock2, {"op": "put", "slot": 9,
                                "value": 90}) == {"ok": True}
            send_frame(sock2, {"op": "bye"})
    assert crdt.get(9) == 90
    assert crdt.get(1) is None   # the truncated batch never landed


# --- per-lane observability ---

def test_binop_lane_counters_and_sketches():
    crdt = DenseCrdt("lane-a", n_slots=64)
    node = str(crdt.node_id)
    reg = default_registry()
    ops = reg.counter("crdt_tpu_serve_ops_total")
    lane_sk = reg.sketch("crdt_tpu_serve_ack_lane_seconds_sketch")
    with ServeTier(crdt, flush_interval=0.002) as tier:
        with _connect(tier) as jsock:
            assert _req(jsock, {"op": "put", "slot": 1,
                                "value": 1}) == {"ok": True}
            send_frame(jsock, {"op": "bye"})
        bsock, codec = _binop_session(tier.host, tier.port)
        with bsock:
            status, _, _ = binop_round(
                bsock, [BINOP_PUT, BINOP_DELETE, BINOP_GET],
                [2, 3, 2], [20, 0, 0],
                deadline=time.monotonic() + 10.0, codec=codec)
            assert list(status)[:2] == [BINOP_ST_OK, BINOP_ST_OK]
            send_frame(bsock, {"op": "bye"}, None, codec)
    assert ops.value(op="put", lane="json", node=node) == 1
    assert ops.value(op="put", lane="bin", node=node) == 1
    assert ops.value(op="delete", lane="bin", node=node) == 1
    assert ops.value(op="get", lane="bin", node=node) == 1
    assert lane_sk.quantile(0.99, lane="json", node=node) is not None
    assert lane_sk.quantile(0.99, lane="bin", node=node) is not None


def test_binop_ack_phases_include_decode_and_reconstruct():
    """The binary lane adds a `decode` phase (frame decode +
    admission) and the phase sums still reconstruct the ack sum
    within 10% — the PR 11 property, extended."""
    crdt = DenseCrdt("binphase-a", n_slots=64)
    node = str(crdt.node_id)
    reg = default_registry()
    ack = reg.histogram("crdt_tpu_serve_ack_seconds")
    phase = reg.histogram("crdt_tpu_serve_ack_phase_seconds")

    def _sum(h, **labels):
        return sum(s["sum"] for s in h.samples()
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    names = ("decode",) + ACK_PHASES
    ack0 = _sum(ack, node=node)
    frames = 10
    with ServeTier(crdt, flush_interval=0.002) as tier:
        sock, codec = _binop_session(tier.host, tier.port)
        with sock:
            for i in range(frames):
                status, _, _ = binop_round(
                    sock, [BINOP_PUT] * 4,
                    [4 * i % 64, (4 * i + 1) % 64,
                     (4 * i + 2) % 64, (4 * i + 3) % 64],
                    [i, i, i, i],
                    deadline=time.monotonic() + 10.0, codec=codec)
                assert list(status) == [BINOP_ST_OK] * 4
            send_frame(sock, {"op": "bye"}, None, codec)
    ack_sum = _sum(ack, node=node) - ack0
    counts = {p: sum(s["count"] for s in phase.samples()
                     if s["labels"] == {"node": node, "phase": p})
              for p in names}
    # one observation per phase per acked FRAME (the batch is the
    # client-visible ack unit)
    assert counts == {p: frames for p in names}
    total = sum(_sum(phase, node=node, phase=p) for p in names)
    assert total == pytest.approx(ack_sum, rel=0.10), \
        (counts, total, ack_sum)


# --- read attribution: decode / replica_wait / store_wait / fetch /
# reply ---

READ_PHASES = ("replica_wait", "store_wait", "fetch", "reply")


def _spy_reads(tier) -> list:
    """The phases of every read frame the tier observes, in order."""
    seen = []
    observe = tier._observe_read

    def spy(phases, t_end):
        observe(phases, t_end)
        seen.append(dict(phases))

    tier._observe_read = spy
    return seen


def _phase_totals(name, node):
    """``phase -> (count, sum)`` of one phase histogram for a node."""
    return {s["labels"]["phase"]: (s["count"], s["sum"])
            for s in default_registry().histogram(name).samples()
            if s["labels"].get("node") == node}


def test_read_phases_observed_once_per_frame_within_round_trip():
    """Every read frame through the replica executor, binary or JSON,
    observes each of its phases exactly once; the phases are
    non-negative and together take no longer than the client's round
    trip."""
    crdt = DenseCrdt("readphase-a", n_slots=64)
    node = str(crdt.node_id)
    rtts = []
    with ServeTier(crdt, flush_interval=0.002) as tier:
        seen = _spy_reads(tier)
        bsock, codec = _binop_session(tier.host, tier.port)
        with bsock, _connect(tier) as jsock:
            assert _req(jsock, {"op": "put", "slot": 5,
                                "value": 50}) == {"ok": True}
            for i in range(3):
                t = time.perf_counter()
                status, values, _ = binop_round(
                    bsock, [BINOP_GET, BINOP_GET], [5, 6 + i], [0, 0],
                    deadline=time.monotonic() + 10.0, codec=codec)
                rtts.append(time.perf_counter() - t)
                assert list(status) == [BINOP_ST_OK, BINOP_ST_OK_NULL]
                assert int(values[0]) == 50
            for i in range(2):
                t = time.perf_counter()
                assert _req(jsock, {"op": "get", "slot": 5}) == {
                    "ok": True, "value": 50}
                rtts.append(time.perf_counter() - t)
            send_frame(bsock, {"op": "bye"}, None, codec)
            send_frame(jsock, {"op": "bye"})
    counts = {p: c for p, (c, _) in _phase_totals(
        "crdt_tpu_serve_read_phase_seconds", node).items()}
    assert counts == dict({p: 5 for p in READ_PHASES}, decode=3)
    assert [sorted(p) for p in seen] == \
        [sorted(("decode",) + READ_PHASES)] * 3 \
        + [sorted(READ_PHASES)] * 2
    for phases, rtt in zip(seen, rtts):
        assert all(v >= 0.0 for v in phases.values()), phases
        assert sum(phases.values()) <= rtt, (phases, rtt)


def test_replica_wait_names_a_busy_replica_executor():
    """A read and a commit queued behind a 0.3 s task on the replica
    executor show that wait as their `replica_wait` phase, and only
    there."""
    crdt = DenseCrdt("replwait-a", n_slots=64)
    node = str(crdt.node_id)
    ack_name = "crdt_tpu_serve_ack_phase_seconds"
    with ServeTier(crdt, flush_interval=0.002) as tier:
        seen = _spy_reads(tier)
        with _connect(tier) as sock:
            # The first commit and the first read compile their
            # programs: warm both before timing anything.
            assert _req(sock, {"op": "put", "slot": 1,
                               "value": 1}) == {"ok": True}
            assert _req(sock, {"op": "get", "slot": 2}) == {
                "ok": True, "value": None}
            tier._replica_pool.submit(time.sleep, 0.3)
            assert _req(sock, {"op": "get", "slot": 1}) == {
                "ok": True, "value": 1}
            ack0 = _phase_totals(ack_name, node)
            tier._replica_pool.submit(time.sleep, 0.3)
            assert _req(sock, {"op": "put", "slot": 3,
                               "value": 3}) == {"ok": True}
            send_frame(sock, {"op": "bye"})
    read = seen[-1]
    assert read["replica_wait"] >= 0.2, read
    assert all(v < 0.1 for p, v in read.items()
               if p != "replica_wait"), read
    ack1 = _phase_totals(ack_name, node)
    ack = {p: ack1[p][1] - ack0[p][1] for p in ACK_PHASES}
    assert {p: ack1[p][0] - ack0[p][0] for p in ACK_PHASES} == \
        {p: 1 for p in ACK_PHASES}
    assert ack["replica_wait"] >= 0.2, ack
    assert all(v < 0.1 for p, v in ack.items()
               if p != "replica_wait"), ack


def test_serve_spans_carry_read_and_write_counts():
    """With the trace ring on, the commit and the read on the replica
    executor emit `serve.commit` with the tick's write count and
    `serve.read` with the frame's read count."""
    from crdt_tpu.obs.trace import tracer
    ring = tracer()
    was_enabled = ring.enabled
    ring.enable()
    try:
        crdt = DenseCrdt("spans-a", n_slots=64)
        with ServeTier(crdt, flush_interval=0.002) as tier:
            sock, codec = _binop_session(tier.host, tier.port)
            with sock:
                status, _, _ = binop_round(
                    sock, [BINOP_PUT, BINOP_PUT], [1, 2], [10, 20],
                    deadline=time.monotonic() + 10.0, codec=codec)
                assert list(status) == [BINOP_ST_OK] * 2
                status, _, _ = binop_round(
                    sock, [BINOP_GET] * 3, [1, 2, 3], [0, 0, 0],
                    deadline=time.monotonic() + 10.0, codec=codec)
                assert list(status) == [BINOP_ST_OK] * 2 \
                    + [BINOP_ST_OK_NULL]
                send_frame(sock, {"op": "bye"}, None, codec)
        events = [e for e in ring.events("serve")
                  if e.get("node") == "spans-a"]
    finally:
        if not was_enabled:
            ring.disable()
    assert [(e["span"], e.get("writes"), e.get("reads"))
            for e in events] == [("serve.commit", 2, None),
                                 ("serve.read", None, 3)]
    assert all(e["dur_s"] >= 0.0 for e in events)


# --- SO_REUSEPORT multi-loop serving ---

def test_multi_loop_acks_and_single_tick_invariant():
    if not hasattr(socket, "SO_REUSEPORT"):
        pytest.skip("platform has no SO_REUSEPORT")
    crdt = DenseCrdt("ml-a", n_slots=256)
    node = str(crdt.node_id)
    reg = default_registry()
    flushes = reg.counter("crdt_tpu_ingest_flush_total")
    before = flushes.value(trigger="tick", node=node)
    loops_g = reg.gauge("crdt_tpu_serve_loops")
    with ServeTier(crdt, flush_interval=0.05, loops=2) as tier:
        assert tier.loops_effective == 2
        assert loops_g.value(node=node) == 2
        # Many connections: the kernel spreads accepts across both
        # loops, so writes (and their acks) cross the MPSC seam.
        socks = [_connect(tier) for _ in range(12)]
        try:
            for i, s in enumerate(socks):
                send_frame(s, {"op": "put", "slot": i,
                               "value": i * 10})
            for s in socks:
                assert recv_frame(
                    s, deadline=time.monotonic() + 10.0) == {"ok": True}
            # 12 writers across 2 loops, still a handful of combiner
            # ticks — never one flush per write, and the dispatch
            # ledger (runtime-asserted) saw ONE ingest_scatter per
            # tick.
            ticks = flushes.value(trigger="tick", node=node) - before
            assert 1 <= ticks <= 4
        finally:
            for s in socks:
                s.close()
    for i in range(12):
        assert crdt.get(i) == i * 10
    assert tier.dropped_sessions == 0


def test_multi_loop_binop_lane():
    if not hasattr(socket, "SO_REUSEPORT"):
        pytest.skip("platform has no SO_REUSEPORT")
    crdt = DenseCrdt("ml-b", n_slots=256)
    with ServeTier(crdt, flush_interval=0.01, loops=2) as tier:
        sessions = [_binop_session(tier.host, tier.port)
                    for _ in range(6)]
        try:
            for k, (sock, codec) in enumerate(sessions):
                status, _, _ = binop_round(
                    sock, [BINOP_PUT] * 4,
                    [4 * k, 4 * k + 1, 4 * k + 2, 4 * k + 3],
                    [k, k, k, k],
                    deadline=time.monotonic() + 10.0, codec=codec)
                assert list(status) == [BINOP_ST_OK] * 4
        finally:
            for sock, _ in sessions:
                sock.close()
    for k in range(6):
        for j in range(4):
            assert crdt.get(4 * k + j) == k


def test_reuseport_less_platform_falls_back_counted(monkeypatch):
    """No SO_REUSEPORT -> ONE loop, and the loop gauge says so (no
    silent downscale)."""
    monkeypatch.delattr(socket, "SO_REUSEPORT", raising=False)
    crdt = DenseCrdt("fb-a", n_slots=64)
    node = str(crdt.node_id)
    loops_g = default_registry().gauge("crdt_tpu_serve_loops")
    with ServeTier(crdt, loops=4) as tier:
        assert tier.loops_effective == 1
        assert loops_g.value(node=node) == 1
        with _connect(tier) as sock:
            assert _req(sock, {"op": "put", "slot": 1,
                               "value": 5}) == {"ok": True}
            send_frame(sock, {"op": "bye"})
    assert crdt.get(1) == 5
