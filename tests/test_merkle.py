"""Merkle anti-entropy (docs/ANTIENTROPY.md): on-device digest trees,
the O(log n) subtree walk, the slot-range pack it feeds, and the
fourth gossip wire mode — over real sockets, with the fault proxy
partitioning the link the walk claims to recover from.

The acceptance checks the ISSUE pins live here: a cold or partitioned
peer converges shipping bytes proportional to DIVERGENCE (asserted
against the full-scan pack it replaces), range packs are bit-identical
slices of the full pack, legacy peers downgrade cleanly in both
directions, and an unchanged store answers digest_tree() from cache
with zero new dispatches."""

import numpy as np
import pytest

from crdt_tpu import (DenseCrdt, GossipNode, RetryPolicy, SyncServer,
                      PeerConnection, SyncProtocolError, WireTally,
                      sync_merkle, sync_merkle_over_conn)
from crdt_tpu.gossip import Peer
from crdt_tpu.obs.registry import default_registry
from crdt_tpu.ops import digest as digest_ops
from crdt_tpu.ops.digest import (PREFETCH_LEVELS, coalesce_leaf_ranges,
                                 walk_divergent_leaves)
from crdt_tpu.sync import _packed_nbytes
from crdt_tpu.testing import (FakeClock, FaultProxy, ScriptedSchedule)

pytestmark = pytest.mark.merkle

BASE = 1_700_000_000_000
NO_SLEEP = lambda _s: None


def _make(node="n", n_slots=64, **kw):
    return DenseCrdt(node, n_slots=n_slots,
                     wall_clock=FakeClock(start=BASE), **kw)


def _node(crdt, **kw):
    kw.setdefault("sleep", NO_SLEEP)
    return GossipNode(crdt, **kw)


def _stores_equal(a, b):
    # Replicated lanes only: node/mod_* are replica-local ordinals and
    # bookkeeping — converged stores legitimately differ there (which
    # is exactly why the digest excludes them).
    for lane in ("lt", "val", "tomb", "occupied"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.store, lane)),
            np.asarray(getattr(b.store, lane)), err_msg=lane)


class _LegacyDense(DenseCrdt):
    """A pre-merkle replica: packs, but has no digest surface, so its
    server never advertises the 'merkle' cap."""
    digest_tree = None


class _BrokenDigestDense(DenseCrdt):
    """Advertises merkle (digest_tree is callable) but every walk
    fails server-side — the sticky-downgrade trigger."""

    def digest_tree(self):
        raise RuntimeError("digest surface wedged")


# ------------------------------------------------ digest tree + walk

def test_walk_localizes_single_slot_divergence():
    a = _make("a", 256)
    b = _make("b", 256)
    ids = list(range(0, 256, 2))
    a.put_batch(ids, [i * 10 for i in ids])
    packed, pids = a.pack_since(None)
    b.merge_packed(packed, pids)
    ta, tb = a.digest_tree(), b.digest_tree()
    assert ta.levels[0][0] == tb.levels[0][0]      # converged: equal roots
    b.put_batch([37], [999])
    tb = b.digest_tree()
    leaves, rounds, fetched = walk_divergent_leaves(ta, tb.values)
    # single-level fetch (the pre-prefetch wire op): one round/level
    assert rounds == ta.depth
    spans = coalesce_leaf_ranges(leaves, ta.leaf_width, ta.n_slots)
    assert len(spans) == 1
    lo, hi = spans[0]
    assert lo <= 37 < hi and hi - lo == ta.leaf_width
    # the walk touches one path, not the whole bottom level
    assert fetched < 3 * ta.depth
    # batched frontier prefetch: PREFETCH_LEVELS levels per round
    # trip, same leaves, and the speculative fan-out stays bounded by
    # (2^P - 1) digests per frontier node per round
    leaves_p, rounds_p, fetched_p = walk_divergent_leaves(
        ta, None, fetch_levels=tb.values_levels)
    assert sorted(leaves_p) == sorted(leaves)
    assert rounds_p == -(-ta.depth // PREFETCH_LEVELS)
    assert fetched_p <= rounds_p * 2 * (2 ** PREFETCH_LEVELS - 1)


def test_clean_walk_costs_one_round():
    a = _make("a", 128)
    a.put_batch([1, 2, 3], [10, 20, 30])
    t = a.digest_tree()
    leaves, rounds, fetched = walk_divergent_leaves(t, t.values)
    assert leaves == [] and rounds == 1 and fetched == 1
    # prefetch: still ONE round trip — matching roots end the walk at
    # level 0; the speculative descendants rode along (2^l digests at
    # each prefetched level l) and were simply unused
    leaves, rounds, fetched = walk_divergent_leaves(
        t, None, fetch_levels=t.values_levels)
    assert leaves == [] and rounds == 1
    assert fetched == sum(
        2 ** l for l in range(min(PREFETCH_LEVELS, t.depth)))


def _host_levels(lt, val, tomb, occupied, sem, leaf_width):
    """The digest tree rebuilt in numpy, root-first: per-slot mix, leaf
    sums, zero-padded to a power of two, pairwise combines. Uses the
    module's own mix and salts; u64 array arithmetic wraps as on the
    device."""
    d = digest_ops
    idx = np.arange(lt.shape[0], dtype=np.uint64)
    h = d._mix64(lt.astype(np.uint64) + d._GOLDEN * (idx + np.uint64(1)))
    h = h ^ d._mix64(val.astype(np.uint64) ^ d._MIX_B)
    h = h ^ np.where(tomb, d._TOMB_SALT, np.uint64(0))
    if sem is not None:
        h = h ^ d._mix64(sem.astype(np.uint64) + d._SEM_SALT)
    h = np.where(occupied, d._mix64(h), np.uint64(0))
    n_leaves = -(-h.shape[0] // leaf_width)
    h = np.concatenate([h, np.zeros(n_leaves * leaf_width - h.shape[0],
                                    np.uint64)])
    leaves = h.reshape(n_leaves, leaf_width).sum(axis=1, dtype=np.uint64)
    width = 1 << (n_leaves - 1).bit_length()
    levels = [np.concatenate([leaves, np.zeros(width - n_leaves,
                                               np.uint64)])]
    while levels[-1].shape[0] > 1:
        c = levels[-1]
        levels.append(d._mix64(c[0::2] + d._GOLDEN * c[1::2] + d._MIX_A))
    return levels[::-1]


@pytest.mark.parametrize("with_sem", [False, True])
@pytest.mark.parametrize("n_leaves", [1, 2, 3, 1000, 4101])
def test_digest_levels_match_host_reference(n_leaves, with_sem):
    # Every level, not just the root: peers walk levels across
    # versions, so each node's value and position is wire format.
    import jax.numpy as jnp
    w = digest_ops.DEFAULT_LEAF_WIDTH
    n = w * n_leaves - n_leaves % 5          # ragged tail leaf
    rng = np.random.default_rng([26, n_leaves])
    lt = rng.integers(-2 ** 63, 2 ** 63 - 1, n, dtype=np.int64)
    val = rng.integers(-2 ** 63, 2 ** 63 - 1, n, dtype=np.int64)
    tomb = rng.random(n) < 0.2
    occupied = rng.random(n) < 0.7
    sem = (rng.integers(-128, 128, n, dtype=np.int64).astype(np.int8)
           if with_sem else None)
    want = _host_levels(lt, val, tomb, occupied, sem, w)
    args = [jnp.asarray(a) for a in (lt, val, tomb, occupied)]
    if with_sem:
        args.append(jnp.asarray(sem))
    got = digest_ops._digest_tree_jit(w, with_sem)(*args)
    assert len(got) == len(want)
    for level, (g, e) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(np.asarray(g), e,
                                      err_msg=f"level {level}")
    # The interior levels alone, from the reference's padded leaves.
    got = digest_ops.tree_levels_from_leaves(jnp.asarray(want[-1]))
    for level, (g, e) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(np.asarray(g), e,
                                      err_msg=f"level {level}")


def test_digest_root_of_seeded_store_is_pinned():
    # A literal root: every other test compares device trees with
    # device trees, so a change of tree shape or mix would pass them
    # all while breaking walks against peers of other versions.
    c = _make("pin", 1 << 12)
    rng = np.random.default_rng(20260)
    slots = rng.choice(1 << 12, 3000, replace=False)
    c.put_batch(slots, rng.integers(-2 ** 62, 2 ** 62, 3000))
    c.delete_batch(slots[:300])
    tree = c.digest_tree()
    assert tree.depth == 10
    assert tree.root == 13733892194618584479


# ------------------------------------------------ range pack

def test_full_range_pack_bit_identical_to_pack_since():
    c = _make("c", 96)
    c.put_batch(list(range(0, 90, 3)), list(range(100, 190, 3)))
    c.delete_batch([6, 12])
    full, fids = c.pack_since(None)
    ranged, rids = c.pack_since(None, ranges=((0, 96),))
    assert fids == rids
    for lf, lr in zip(full, ranged):
        if lf is None:
            assert lr is None
        else:
            assert lf.dtype == lr.dtype
            np.testing.assert_array_equal(np.asarray(lf),
                                          np.asarray(lr))


def test_subrange_packs_union_to_full_convergence():
    src = _make("src", 128)
    src.put_batch(list(range(128)), list(range(1000, 1128)))
    via_full = _make("rf", 128)
    via_ranges = _make("rr", 128)
    packed, ids = src.pack_since(None)
    via_full.merge_packed(packed, ids)
    for span in ((0, 40), (40, 128)):
        p, i = src.pack_since(None, ranges=(span,))
        via_ranges.merge_packed(p, i)
    _stores_equal(via_full, via_ranges)


def test_range_validation_rejects_out_of_bounds():
    c = _make("c", 32)
    with pytest.raises(ValueError):
        c.pack_since(None, ranges=((0, 33),))
    with pytest.raises(ValueError):
        c.pack_since(None, ranges=((-1, 4),))


# ------------------------------------------------ digest cache

def test_unchanged_store_answers_digest_from_cache():
    ctr = default_registry().counter("crdt_tpu_digest_cache_total", "")
    c = _make("cache", 64)
    c.put_batch([1, 2], [11, 22])
    m0 = ctr.value(outcome="miss", node="cache")
    h0 = ctr.value(outcome="hit", node="cache")
    t1 = c.digest_tree()
    assert ctr.value(outcome="miss", node="cache") == m0 + 1
    t2 = c.digest_tree()
    # the exact cached object — no rebuild, no new digest dispatch
    assert t2 is t1
    assert ctr.value(outcome="hit", node="cache") == h0 + 1
    c.put_batch([3], [33])                       # store moved: invalidated
    t3 = c.digest_tree()
    assert t3 is not t1
    assert ctr.value(outcome="miss", node="cache") == m0 + 2


def test_restart_answers_first_walk_from_persisted_digest(tmp_path):
    """Digest-tree persistence: `DenseCrdt.save` writes the tree under
    its cache key; `load` re-seeds the cache, so the restarted
    replica's FIRST digest_tree() is a cache hit — zero digest
    dispatches before the first walk — and the tree is level-for-level
    identical to the one saved."""
    ctr = default_registry().counter("crdt_tpu_digest_cache_total", "")
    c = _make("boot", 64)
    c.put_batch(list(range(0, 64, 4)), list(range(16)))
    c.delete_batch([8])
    t_saved = c.digest_tree()
    path = str(tmp_path / "snap.npz")
    c.save(path)
    r = DenseCrdt.load("boot", path, wall_clock=FakeClock(start=BASE))
    h0 = ctr.value(outcome="hit", node="boot")
    m0 = ctr.value(outcome="miss", node="boot")
    t = r.digest_tree()
    assert ctr.value(outcome="hit", node="boot") == h0 + 1
    assert ctr.value(outcome="miss", node="boot") == m0   # no rebuild
    assert t.same_geometry(t_saved.n_slots, t_saved.leaf_width,
                           t_saved.depth)
    for saved_lvl, got_lvl in zip(t_saved.levels, t.levels):
        np.testing.assert_array_equal(np.asarray(saved_lvl),
                                      np.asarray(got_lvl))
    # and the seeded cache obeys the usual invalidation discipline
    r.put_batch([1], [999])
    assert r.digest_tree() is not t
    assert ctr.value(outcome="miss", node="boot") == m0 + 1


def test_pre_digest_snapshot_loads_and_rebuilds(tmp_path):
    """A snapshot saved WITHOUT a digest (store-level `save_dense`,
    i.e. every pre-persistence snapshot) still loads; the first walk
    simply rebuilds — a missing cache, never a failed restore."""
    from crdt_tpu.checkpoint import load_dense_digest, save_dense
    c = _make("old", 32)
    c.put_batch([1, 2], [10, 20])
    path = str(tmp_path / "old.npz")
    save_dense(c.store, path, node_ids=["old"])
    assert load_dense_digest(path) is None
    ctr = default_registry().counter("crdt_tpu_digest_cache_total", "")
    r = DenseCrdt.load("old", path, wall_clock=FakeClock(start=BASE))
    m0 = ctr.value(outcome="miss", node="old")
    r.digest_tree()
    assert ctr.value(outcome="miss", node="old") == m0 + 1


# ------------------------------------------------ socket path

def test_cold_empty_peer_converges_over_socket():
    server_crdt = _make("srv", 256)
    ids = list(range(0, 256, 3))
    server_crdt.put_batch(ids, [i + 7 for i in ids])
    server_crdt.delete_batch([3, 9])
    client = _make("cli", 256)
    stats = {}
    with SyncServer(server_crdt) as server:
        with PeerConnection(server.host, server.port,
                            timeout=5.0) as conn:
            sync_merkle_over_conn(client, conn, _stats=stats)
    _stores_equal(client, server_crdt)
    assert client.digest_tree().root == server_crdt.digest_tree().root
    # frontier prefetch batches PREFETCH_LEVELS tree levels per round
    # trip, so a cold join walks the whole tree in ceil(depth/P)
    # rounds — the pinned wire-round budget for high-RTT links
    depth = client.digest_tree().depth
    assert stats["rounds"] == -(-depth // PREFETCH_LEVELS)
    assert stats["pulled_rows"] == len(ids)


def test_clean_peers_exchange_zero_payload():
    a = _make("a", 128)
    b = _make("b", 128)
    a.put_batch([5, 6], [50, 60])
    packed, ids = a.pack_since(None)
    b.merge_packed(packed, ids)
    stats = {}
    with SyncServer(b) as server:
        with PeerConnection(server.host, server.port,
                            timeout=5.0) as conn:
            sync_merkle_over_conn(a, conn, _stats=stats)
    assert stats["rounds"] == 1                  # roots matched
    assert not stats["ranges"]
    assert stats["pushed_rows"] == 0 and stats["pulled_rows"] == 0


def test_divergence_proportional_bytes_vs_full_scan():
    """The acceptance ratio: a converged pair diverging in one small
    slot window re-syncs for <10% of the full-scan pack bytes. The
    walk's fixed cost is logarithmic meta traffic, so the ratio only
    tightens as the store grows."""
    n = 2048
    a = _make("a", n)
    b = _make("b", n)
    ids = list(range(n))
    a.put_batch(ids, [i * 3 for i in ids])
    packed, pids = a.pack_since(None)
    b.merge_packed(packed, pids)
    # partition-era writes: 8 slots, clustered (interning order makes
    # divergence contiguous in slot space)
    b.put_batch(list(range(500, 508)), [0] * 8)
    full_scan = _packed_nbytes(b.pack_since(None)[0])
    tally = WireTally()
    stats = {}
    with SyncServer(b) as server:
        with PeerConnection(server.host, server.port,
                            timeout=5.0) as conn:
            sync_merkle_over_conn(a, conn, tally=tally, _stats=stats)
    _stores_equal(a, b)
    moved = tally.sent + tally.received
    assert moved < 0.10 * full_scan, \
        f"merkle moved {moved}B vs full-scan {full_scan}B"
    assert stats["pulled_rows"] <= 16            # leaf-rounded, not 1024


def test_prefetch_client_degrades_against_pre_prefetch_server(
        monkeypatch):
    """Mixed versions, new-client/old-server direction: a previous
    release advertises the same 'merkle' cap but ignores the 'more'
    prefetch groups and omits 'ks' from digest_resp. The walk must
    degrade to single-level rounds (sticky per session) and still
    converge — never abort with a framing error. Simulated by
    stripping exactly those fields at the module frame helpers the
    server resolves at call time."""
    import crdt_tpu.net as net_mod
    server_crdt = _make("srv", 256)
    ids = list(range(0, 256, 3))
    server_crdt.put_batch(ids, [i + 7 for i in ids])
    client = _make("cli", 256)

    real_recv, real_send = net_mod.recv_frame, net_mod.send_frame

    def legacy_recv(sock, *a, **kw):
        msg = real_recv(sock, *a, **kw)
        if isinstance(msg, dict) and msg.get("op") == "digest":
            msg.pop("more", None)        # server-side: never parsed
        return msg

    def legacy_send(sock, obj, tally=None, codec=None):
        if isinstance(obj, dict) and obj.get("op") == "digest_resp":
            obj = {k: v for k, v in obj.items() if k != "ks"}
        return real_send(sock, obj, tally, codec)

    monkeypatch.setattr(net_mod, "recv_frame", legacy_recv)
    monkeypatch.setattr(net_mod, "send_frame", legacy_send)
    stats = {}
    with SyncServer(server_crdt) as server:
        with PeerConnection(server.host, server.port,
                            timeout=5.0) as conn:
            sync_merkle_over_conn(client, conn, _stats=stats)
            # the degrade is sticky: the NEXT walk on this session
            # skips the futile multi-level probe entirely
            assert conn.digest_prefetch is False
            depth = client.digest_tree().depth
            # one aborted prefetch probe + one single-level round per
            # tree level
            assert stats["rounds"] == depth + 1
            stats2 = {}
            sync_merkle_over_conn(client, conn, _stats=stats2)
            assert stats2["rounds"] == 1         # converged roots
    _stores_equal(client, server_crdt)
    for s in ids:
        assert client.get(s) == s + 7


def test_legacy_server_rejects_merkle_before_payload():
    legacy = _LegacyDense("old", n_slots=32,
                          wall_clock=FakeClock(start=BASE))
    client = _make("new", 32)
    with SyncServer(legacy) as server:
        with PeerConnection(server.host, server.port,
                            timeout=5.0) as conn:
            conn.ensure()
            assert "merkle" not in conn.caps     # never advertised
            with pytest.raises(SyncProtocolError) as ei:
                sync_merkle_over_conn(client, conn)
            assert ei.value.code == "merkle_rejected"


def test_geometry_mismatch_is_rejected_in_process():
    a = _make("a", 64)
    b = _make("b", 128)
    with pytest.raises(ValueError, match="geometry"):
        sync_merkle(a, b)


def test_sync_merkle_report_accounts_traffic():
    a = _make("a", 256)
    b = _make("b", 256)
    ids = list(range(256))
    a.put_batch(ids, ids)
    p, i = a.pack_since(None)
    b.merge_packed(p, i)
    clean = sync_merkle(a, b)
    assert clean.ranges == () and clean.payload_bytes == 0
    assert clean.rounds == 1 and clean.total_bytes == 16
    b.put_batch([100], [-1])
    diverged = sync_merkle(a, b)
    assert len(diverged.ranges) == 1
    assert diverged.pulled_rows >= 1
    full = _packed_nbytes(b.pack_since(None)[0])
    assert diverged.total_bytes < 0.10 * full
    _stores_equal(a, b)


# ------------------------------------------------ gossip integration

def test_gossip_cold_join_walks_then_warm_rounds_pack():
    clk = FakeClock()
    b = _node(_make("b", 64))
    a = _node(_make("a", 64))
    with a, b:
        with b.lock:
            b.crdt.put_batch([1, 2], [10, 20])
        peer = a.add_peer("b", b.host, b.port)
        assert peer.mode == "merkle"             # fastest form by default
        assert a.sync_peer("b") == "ok"
        assert peer.last_attempt == "merkle"     # cold join = the walk
        with a.lock:
            a.crdt.put_batch([3], [30])
        assert a.sync_peer("b") == "ok"
        # warm session: watermark set, the incremental packed round is
        # strictly cheaper — mode still aims at merkle
        assert peer.last_attempt == "packed"
        assert peer.mode == "merkle"
        assert peer.stats.fallbacks == 0
    assert a.crdt.get(1) == 10 and b.crdt.get(3) == 30


def test_gossip_legacy_peer_capability_selected_without_fallback():
    clk = FakeClock(start=BASE)
    b = _node(_LegacyDense("b", n_slots=64, wall_clock=clk))
    a = _node(_make("a", 64))
    with a, b:
        with b.lock:
            b.crdt.put_batch([9], [90])
        peer = a.add_peer("b", b.host, b.port)
        with a.lock:
            a.crdt.put_batch([4], [40])
        assert a.sync_peer("b") == "ok"
        # no 'merkle' cap in the hello -> the walk is never offered;
        # that is selection, not failure
        assert peer.last_attempt == "packed"
        assert peer.stats.fallbacks == 0
        assert peer.mode == "merkle"
    assert a.crdt.get(9) == 90 and b.crdt.get(4) == 40


def test_gossip_digest_failure_downgrades_sticky_to_packed():
    clk = FakeClock(start=BASE)
    b = _node(_BrokenDigestDense("b", n_slots=64, wall_clock=clk))
    a = _node(_make("a", 64))
    with a, b:
        with b.lock:
            b.crdt.put_batch([7], [70])
        peer = a.add_peer("b", b.host, b.port)
        assert a.sync_peer("b") == "ok"          # fell back in-round
        assert peer.stats.fallbacks == 1
        assert peer.mode == "packed"             # sticky downgrade
        with a.lock:
            a.crdt.put_batch([8], [80])
        assert a.sync_peer("b") == "ok"
        assert peer.stats.fallbacks == 1         # no second fallback
    assert a.crdt.get(7) == 70 and b.crdt.get(8) == 80


def test_partitioned_peer_reconverges_by_walk_through_fault_proxy():
    clk = FakeClock()
    n = 1024
    b = _node(DenseCrdt("b", n_slots=n, wall_clock=clk))
    with b:
        with b.lock:
            ids = list(range(0, n, 2))
            b.crdt.put_batch(ids, [i + 1 for i in ids])
        sched = ScriptedSchedule([{"kind": "drop"}, None])
        with FaultProxy(b.host, b.port, sched) as proxy:
            a = _node(DenseCrdt("a", n_slots=n, wall_clock=clk),
                      retry=RetryPolicy(max_attempts=3,
                                        base_delay=0.001))
            with a:
                peer = a.add_peer("b", proxy.host, proxy.port)
                # cold join survives the dropped connection and walks
                assert a.sync_peer("b") == "ok"
                assert peer.stats.retries == 1
                assert peer.last_attempt == "merkle"
                assert proxy.counters.get("drop") == 1
                cold_recv = peer.stats.bytes_received
                # --- partition: both sides move, no rounds run; the
                # resumed replica also lost its watermark state
                with b.lock:
                    b.crdt.put_batch([101, 103], [5101, 5103])
                with a.lock:
                    a.crdt.put_batch([200], [5200])
                peer.watermark = None
                assert a.sync_peer("b") == "ok"
                assert peer.last_attempt == "merkle"
                heal_recv = peer.stats.bytes_received - cold_recv
                # the healing walk pulls the divergent leaves, not the
                # half-full store the cold join shipped (the tight
                # <10% ratio is asserted at socket level above; through
                # gossip the walk's per-round meta frames ride along)
                assert heal_recv < 0.5 * cold_recv, \
                    f"healed with {heal_recv}B vs cold {cold_recv}B"
        _stores_equal(a.crdt, b.crdt)
        assert a.crdt.get(101) == 5101 and b.crdt.get(200) == 5200


def test_three_replica_mixed_mode_soak():
    """One mesh, three wire forms: a->b walks (merkle), b->c stays on
    watermark packing, c->a is pinned to the legacy dense split. Every
    replica writes every round; everyone converges."""
    clk = FakeClock()
    nodes = {name: _node(DenseCrdt(name, n_slots=64, wall_clock=clk))
             for name in ("a", "b", "c")}
    a, b, c = nodes["a"], nodes["b"], nodes["c"]
    with a, b, c:
        a.add_peer("b", b.host, b.port)                  # merkle
        b.add_peer("c", c.host, c.port, mode="packed")
        c.add_peer("a", a.host, a.port, mode="dense")
        for r in range(4):
            for i, node in enumerate(nodes.values()):
                with node.lock:
                    node.crdt.put_batch([r * 8 + i], [100 * r + i])
            for node in nodes.values():
                outcomes = node.run_round()
                assert set(outcomes.values()) == {"ok"}
        # settle sweep so last-round writes reach every replica
        for node in nodes.values():
            assert set(node.run_round().values()) == {"ok"}
        for node in nodes.values():
            assert set(node.run_round().values()) == {"ok"}
        for node in nodes.values():
            assert all(p.stats.fallbacks == 0
                       for p in node.peers.values())
    _stores_equal(a.crdt, b.crdt)
    _stores_equal(b.crdt, c.crdt)


# ------------------------------------------------ Peer.dense back-compat

def test_dense_setter_preserves_faster_modes():
    """Regression: the old setter collapsed ANY binary mode to 'dense',
    silently downgrading merkle/packed peers that touched the legacy
    flag. `dense = True` now only upgrades json; False still forces
    json."""
    from crdt_tpu.gossip import BreakerPolicy, CircuitBreaker
    from crdt_tpu.utils.stats import PeerSyncStats
    p = Peer("p", "127.0.0.1", 1, mode="merkle",
             breaker=CircuitBreaker(BreakerPolicy()),
             stats=PeerSyncStats())
    for mode in ("merkle", "packed", "dense"):
        p.mode = mode
        p.dense = True
        assert p.mode == mode                    # preserved, not collapsed
        assert p.dense is True
    p.mode = "json"
    p.dense = True
    assert p.mode == "dense"                     # json upgrades to floor
    p.mode = "merkle"
    p.dense = False
    assert p.mode == "json"                      # escape hatch intact
