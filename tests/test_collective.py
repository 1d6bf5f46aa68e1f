"""Pod-local collective anti-entropy suite (docs/COLLECTIVE.md).

The load-bearing property: an N-member `CollectiveGroup.join` — ONE
device dispatch, zero wire bytes — lands every member on a state
bit-identical to pairwise `sync_packed` convergence of the same
writes, across mixed slot semantics and mid-window joiners. Plus the
group's contract surface (geometry/identity/semantics validation) and
the `GossipNode` fast lane (address-keyed detection, counted socket
fallback, `attach_group` re-scan).
"""

import random

import jax
import numpy as np
import pytest

from cells import ycsb
from cells.reference import pod_fold
from crdt_tpu import DenseCrdt, GossipNode, collective, default_registry
from crdt_tpu.collective import CollectiveGroup
from crdt_tpu.obs.device import default_ledger
from crdt_tpu.ops.packing import PackedDelta
from crdt_tpu.parallel.collective import REPACK_ROWS, compact_rows
from crdt_tpu.sync import sync_collective, sync_packed
from crdt_tpu.testing import FakeClock

N = 64
BASE = 1_700_000_000_000
KERNEL = "parallel.collective_join"

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="collective join needs a multi-device (virtual) mesh")


def _pack_copy_bytes():
    c = default_registry().counter("crdt_tpu_pack_copy_bytes_total")
    return sum(s["value"] for s in c.samples())


def _lanes(c):
    s = c._store
    return jax.device_get((s.lt, s.node, s.val, s.tomb, s.occupied))


def _build_replicas(n_members=3, seed=0, mixed_sem=True):
    """One deterministic universe of writes: identical FakeClock bases
    and op sequences give bit-identical stamps, so a second call
    builds an exact twin set for the wire-path oracle."""
    rng = random.Random(seed)
    names = [chr(ord("a") + i) for i in range(n_members)]
    reps = [DenseCrdt(nm, N, wall_clock=FakeClock(start=BASE))
            for nm in names]
    if mixed_sem:
        for c in reps:
            c.set_semantics([0], "gcounter")
            c.set_semantics([1], "pncounter")
            c.set_semantics([2], "orset")
            c.set_semantics([3], "mvreg")
    for c in reps:
        slots = rng.sample(range(8, N), 6)
        c.put_batch(slots, [rng.randrange(1, 10_000) for _ in slots])
        c.delete_batch(slots[:1])
        if mixed_sem:
            c.counter_add(0, rng.randrange(1, 50))
            c.counter_add(1, rng.randrange(-20, 20))
            c.orset_add(2, rng.randrange(16))
            c.mvreg_put(3, rng.randrange(1, 100))
    return reps


def _wire_converge(reps):
    """Socket-path oracle: full (since=None) pairwise exchanges until
    every pair has seen every write. since=None sidesteps the
    same-round pull bound (a peer's writes stamped below the local
    pre-push watermark are invisible to a delta pull when every
    FakeClock shares one base)."""
    for _ in range(2):
        for i in range(len(reps)):
            for j in range(i + 1, len(reps)):
                sync_packed(reps[i], reps[j], since=None)
    return reps


def _assert_bit_identical(wire, coll):
    for wx, cx in zip(wire, coll):
        wl, cl = _lanes(wx), _lanes(cx)
        for lane, w, c in zip(("lt", "node", "val", "tomb", "occ"),
                              wl, cl):
            assert np.array_equal(w, c), (wx.node_id, lane)
        assert np.array_equal(wx._sem_host(), cx._sem_host())
    roots_w = {x.digest_tree().root for x in wire}
    roots_c = {x.digest_tree().root for x in coll}
    assert len(roots_c) == 1 and roots_c == roots_w


# --- the equivalence property ---

def test_collective_join_bit_identical_to_pairwise_packed():
    wire = _wire_converge(_build_replicas(seed=1))
    coll = _build_replicas(seed=1)
    group = CollectiveGroup(coll)
    report = group.join()
    assert report.members == 3 and report.adopted > 0
    assert report.bytes_to_wire == 0
    _assert_bit_identical(wire, coll)
    assert report.digest_root == wire[0].digest_tree().root


@pytest.mark.parametrize("seed", [2, 3, 4])
def test_collective_join_property_lww_only(seed):
    wire = _wire_converge(_build_replicas(seed=seed, mixed_sem=False))
    coll = _build_replicas(seed=seed, mixed_sem=False)
    CollectiveGroup(coll).join()
    _assert_bit_identical(wire, coll)


def test_collective_join_is_one_dispatch_and_zero_pack_bytes():
    coll = _build_replicas(seed=5)
    group = CollectiveGroup(coll)
    led = default_ledger()
    before_k = led.dispatches(kernel=KERNEL)
    before_bytes = _pack_copy_bytes()
    report = group.join()
    # The invariant the PR exists for: ONE collective dispatch per
    # round, and pack-path copy accounting does not move (cache
    # seeding is a host-side column select, not a wire stage).
    assert led.dispatches(kernel=KERNEL) - before_k == 1
    assert _pack_copy_bytes() == before_bytes
    assert report.bytes_to_wire == 0
    # Pre-seeded caches: digest_tree() and the watermark-aligned pack
    # must both come back without ANY further device dispatch.
    total = led.dispatches()
    for m in coll:
        m.digest_tree()
    assert led.dispatches() == total, "digest cache was cold"
    for m in coll:
        assert len(m._pack_cache) == 1


def test_collective_join_over_resident_planes_matches_xla():
    # Members holding resident split planes (ops/pallas_scatter.py)
    # join bit for bit like lane-held members, and every member's
    # store stays on its own mesh device through the join and the
    # commits and point reads after it.
    from crdt_tpu.ops.pallas_merge import TILE

    def build(executor):
        rng = random.Random(9)
        reps = [DenseCrdt(nm, TILE, wall_clock=FakeClock(start=BASE),
                          executor=executor) for nm in "abc"]
        for c in reps:
            slots = rng.sample(range(TILE), 40)
            with c.ingest():
                c.put_batch(slots, [rng.randrange(1, 10_000)
                                    for _ in slots])
                c.delete_batch(slots[:3])
        return reps

    planes, ref = build("pallas-interpret"), build("xla")
    assert all(m._planes is not None for m in planes)
    assert all(m._planes is None for m in ref)
    groups = [CollectiveGroup(planes), CollectiveGroup(ref)]
    for rnd in range(2):
        for g in groups:
            g.join()
        for i, m in enumerate(planes):
            dev = groups[0].mesh.devices.flat[i]
            assert m.get(TILE - 1) == ref[i].get(TILE - 1)
            assert m._planes is not None
            assert {d for p in m._planes for d in p.devices()} == {dev}
        _assert_bit_identical(ref, planes)
        for reps in (planes, ref):
            for j, m in enumerate(reps):
                with m.ingest():
                    m.put_batch([TILE - 1 - j], [10 * rnd + j])
        for i, m in enumerate(planes):
            dev = groups[0].mesh.devices.flat[i]
            assert {d for p in m._planes for d in p.devices()} == {dev}


def test_second_join_is_idempotent():
    coll = _build_replicas(seed=6)
    group = CollectiveGroup(coll)
    first = group.join()
    again = group.join()
    assert again.adopted == 0
    assert again.digest_root == first.digest_root
    assert again.new_canonical == first.new_canonical


def test_mid_window_joiner_has_ingest_drained():
    wire = _wire_converge(_build_replicas(seed=7))
    coll = _build_replicas(seed=7)
    # Twin the staged writes on the wire oracle (window closed) and
    # the collective member (window still OPEN at join time): join()
    # must drain the overlay, so the staged rows participate.
    wire_w = DenseCrdt("w", N, wall_clock=FakeClock(start=BASE + 9))
    coll_w = DenseCrdt("w", N, wall_clock=FakeClock(start=BASE + 9))
    for c in (wire_w, coll_w):
        c.set_semantics([0], "gcounter")
        c.set_semantics([1], "pncounter")
        c.set_semantics([2], "orset")
        c.set_semantics([3], "mvreg")
    with wire_w.ingest():
        wire_w.put_batch([4, 5], [777, 888])
    for r in wire:
        sync_packed(r, wire_w, since=None)
    _wire_converge(wire)
    group = CollectiveGroup(coll + [coll_w])
    with coll_w.ingest():
        coll_w.put_batch([4, 5], [777, 888])
        group.join()
    _assert_bit_identical(wire + [wire_w], coll + [coll_w])


def test_sync_collective_wraps_group_join():
    coll = _build_replicas(seed=8)
    report = sync_collective(CollectiveGroup(coll))
    assert report.adopted > 0
    roots = {m.digest_tree().root for m in coll}
    assert len(roots) == 1


# --- the pod deployment against its plain fold ---

POD_SLOTS = 8192


def _counter_total(name):
    return sum(s["value"] for s in
               default_registry().counter(name).samples())


def _pod(n_slots, seed=0):
    """Four members, each on its own clock starting at the same
    millisecond (so equal stamps meet on hot keys), loaded with one
    seeded record in every slot by member m0, as the pod4.join
    cell's members are."""
    names = [f"m{i}" for i in range(4)]
    load = DenseCrdt("m0", n_slots, wall_clock=FakeClock(start=BASE - 9))
    slots = np.arange(n_slots)
    load.put_batch(slots, pod_fold.loaded_value(slots, seed))
    base_lt = load.canonical_time.logical_time
    members = [DenseCrdt(nm, n_slots, store=load.store, node_ids=names,
                         wall_clock=FakeClock(start=BASE))
               for nm in names]
    return members, base_lt


def _zipf_rounds(group, rounds, seed, log, n_keys=POD_SLOTS):
    for r in range(rounds):
        for i, m in enumerate(group.members):
            rng = np.random.default_rng([seed, r, i])
            slots = np.unique(ycsb.scrambled_zipfian(rng, 256, n_keys))
            vals = rng.integers(-(1 << 62), 1 << 62, len(slots))
            m.put_batch(slots, vals)
            log.append((i, m.canonical_time.logical_time, slots, vals))
        group.join()


def test_pod_rounds_match_the_plain_fold():
    members, base_lt = _pod(POD_SLOTS, seed=3)
    group = CollectiveGroup(members)
    log = []
    _zipf_rounds(group, 3, seed=3, log=log)
    stamps = {}
    for rank, lt, slots, _ in log:
        for s in slots:
            stamps.setdefault(int(s), set()).add((lt, rank))
    # The hot keys conflict, and across members under equal stamps.
    assert any(len({lt for lt, _ in v}) < len(v) for v in stamps.values())
    want = pod_fold.fold(log, base_lt, 0, seed=3)
    lanes = [_lanes(m) for m in members]
    for got in lanes[1:]:
        for a, b in zip(lanes[0], got):
            assert np.array_equal(a, b)
    lt, node, val, tomb, occ = (x[want["slots"]] for x in lanes[0])
    got = {"lt": lt, "rank": node, "val": val, "tomb": tomb,
           "occupied": occ}
    assert pod_fold.mismatches(got, want) == 0
    untouched = np.setdiff1d(np.arange(POD_SLOTS), want["slots"])
    assert np.array_equal(lanes[0][2][untouched],
                          pod_fold.loaded_value(untouched, 3))


@pytest.mark.parametrize("mixed_sem", [False, True])
@pytest.mark.parametrize("repack_rows", [REPACK_ROWS, 2])
def test_seeded_pack_is_byte_identical_to_uncached_pack_since(
        monkeypatch, mixed_sem, repack_rows):
    # The compacted rows (and, past the bound, the whole-lane
    # fallback) seed exactly what an uncached pack_since builds.
    monkeypatch.setattr(collective, "REPACK_ROWS", repack_rows)
    coll = _build_replicas(seed=11, mixed_sem=mixed_sem)
    marks = [m.canonical_time for m in coll]
    CollectiveGroup(coll).join()
    mode = "include" if mixed_sem else "auto"
    for m, mark in zip(coll, marks):
        hits = _counter_total("crdt_tpu_pack_cache_total")
        seeded, ids = m.pack_since(mark, sem_mode=mode)
        assert _counter_total("crdt_tpu_pack_cache_total") == hits + 1
        assert len(seeded.slots) > 0
        m._pack_cache.clear()
        fresh, fresh_ids = m.pack_since(mark, sem_mode=mode)
        assert ids == fresh_ids
        for lane in PackedDelta._fields:
            a, b = getattr(seeded, lane), getattr(fresh, lane)
            assert (a is None) == (b is None), lane
            if a is not None:
                assert a.dtype == b.dtype, lane
                assert a.tobytes() == b.tobytes(), lane


def test_join_fetch_bytes_do_not_grow_with_the_store():
    per_join = []
    for n in (1 << 13, 1 << 16):
        members = [DenseCrdt(f"m{i}", n, wall_clock=FakeClock(start=BASE))
                   for i in range(4)]
        group = CollectiveGroup(members)
        _zipf_rounds(group, 1, seed=5, log=[], n_keys=1 << 13)
        before = _counter_total("crdt_tpu_collective_fetch_bytes_total")
        joins = _counter_total("crdt_tpu_collective_join_total")
        _zipf_rounds(group, 1, seed=6, log=[], n_keys=1 << 13)
        assert _counter_total("crdt_tpu_collective_join_total") == joins + 1
        per_join.append(_counter_total(
            "crdt_tpu_collective_fetch_bytes_total") - before)
    assert per_join[0] == per_join[1] > 0


def test_repack_rows_past_the_bound_fall_back_counted(monkeypatch):
    monkeypatch.setattr(collective, "REPACK_ROWS", 2)
    coll = _build_replicas(seed=12, mixed_sem=False)
    group = CollectiveGroup(coll)
    falls = _counter_total("crdt_tpu_collective_pack_fallback_total")
    adopted = _counter_total("crdt_tpu_collective_adopted_rows_total")
    report = group.join()
    # Every member re-packs its own six rows and what it adopted.
    assert _counter_total(
        "crdt_tpu_collective_pack_fallback_total") == falls + 3
    assert _counter_total(
        "crdt_tpu_collective_adopted_rows_total") == adopted + \
        report.adopted > adopted
    for m in coll:
        assert len(m._pack_cache) == 1


@pytest.mark.parametrize("n,k", [(64, 8), (100, 64), (1 << 12, 300)])
def test_compact_rows_is_the_first_k_set_positions(n, k):
    rng = np.random.default_rng(n)
    for density in (0.0, 0.02, 0.5, 1.0):
        mask = rng.random(n) < density
        pos, count = jax.device_get(jax.jit(
            compact_rows, static_argnums=1)(mask, k))
        want = np.nonzero(mask)[0]
        assert int(count) == len(want)
        head = want[:k]
        assert np.array_equal(pos[:len(head)], head)
        assert (pos[len(head):] == n).all()


# --- contract surface ---

def test_group_rejects_fewer_than_two_members():
    (only,) = _build_replicas(seed=9)[:1]
    with pytest.raises(ValueError, match=">= 2 members"):
        CollectiveGroup([only])


def test_group_rejects_duplicate_node_ids():
    a = DenseCrdt("dup", N, wall_clock=FakeClock(start=BASE))
    b = DenseCrdt("dup", N, wall_clock=FakeClock(start=BASE))
    with pytest.raises(ValueError, match="distinct node ids"):
        CollectiveGroup([a, b])


def test_group_rejects_geometry_mismatch():
    a = DenseCrdt("a", N, wall_clock=FakeClock(start=BASE))
    b = DenseCrdt("b", N * 2, wall_clock=FakeClock(start=BASE))
    with pytest.raises(ValueError, match="n_slots"):
        CollectiveGroup([a, b])


def test_group_rejects_addresses_for_non_members():
    a, b, _ = _build_replicas(seed=10)
    with pytest.raises(ValueError, match="non-member"):
        CollectiveGroup([a, b], addresses={"ghost": "h:1"})


def test_join_rejects_semantics_mismatch():
    a = DenseCrdt("a", N, wall_clock=FakeClock(start=BASE))
    b = DenseCrdt("b", N, wall_clock=FakeClock(start=BASE))
    a.set_semantics([5], "orset")
    b.set_semantics([5], "gcounter")
    group = CollectiveGroup([a, b])
    with pytest.raises(ValueError, match="semantics tag mismatch"):
        group.join()


# --- GossipNode fast lane ---

def _gossip_pair():
    a = DenseCrdt("ga", N, wall_clock=FakeClock(start=BASE))
    b = DenseCrdt("gb", N, wall_clock=FakeClock(start=BASE))
    na = GossipNode(a, rng=random.Random(7))
    nb = GossipNode(b, rng=random.Random(7))
    return a, b, na, nb


def test_gossip_routes_co_located_peer_through_collective():
    a, b, na, nb = _gossip_pair()
    with na, nb:
        group = CollectiveGroup(
            [a, b], addresses={"ga": f"{na.host}:{na.port}",
                               "gb": f"{nb.host}:{nb.port}"})
        na.attach_group(group)
        peer = na.add_peer("gb", nb.host, nb.port)
        assert peer.collective
        a.put_batch([1], [11])
        b.put_batch([2], [22])
        led = default_ledger()
        before = led.dispatches(kernel=KERNEL)
        assert na.run_round() == {"gb": "ok"}
        assert led.dispatches(kernel=KERNEL) - before == 1
        assert peer.last_attempt == "collective"
        assert peer.stats.rounds_ok == 1
        assert peer.stats.bytes_sent == 0 and peer.stats.bytes_received == 0
        assert a.get(1) == b.get(1) == 11
        assert a.get(2) == b.get(2) == 22


def test_gossip_attach_group_rescans_existing_peers():
    a, b, na, nb = _gossip_pair()
    with na, nb:
        peer = na.add_peer("gb", nb.host, nb.port)
        assert not peer.collective
        group = CollectiveGroup(
            [a, b], addresses={"gb": f"{nb.host}:{nb.port}"})
        na.attach_group(group)
        assert peer.collective
        na.attach_group(None)
        assert not peer.collective


def test_gossip_node_rejects_group_without_its_replica():
    a, b, na, nb = _gossip_pair()
    stranger = DenseCrdt("ga", N, wall_clock=FakeClock(start=BASE))
    group = CollectiveGroup([stranger, b])
    with pytest.raises(ValueError, match="does not contain"):
        na.attach_group(group)


def test_gossip_collective_failure_falls_back_to_socket_counted():
    a, b, na, nb = _gossip_pair()
    with na, nb:
        group = CollectiveGroup(
            [a, b], addresses={"gb": f"{nb.host}:{nb.port}"})
        na.attach_group(group)
        peer = na.add_peer("gb", nb.host, nb.port)

        def boom(*args, **kwargs):
            raise RuntimeError("mesh went away")

        group.join = boom
        a.put_batch([1], [11])
        fb = default_registry().counter(
            "crdt_tpu_collective_fallback_total")
        before = sum(s["value"] for s in fb.samples())
        assert na.run_round() == {"gb": "ok"}
        # Downgrade is visible: counted per peer (reason label), peer
        # stats bumped, and the round still converged over the socket.
        assert sum(s["value"] for s in fb.samples()) > before
        assert peer.stats.fallbacks >= 1
        assert peer.last_attempt != "collective"
        assert b.get(1) == 11
