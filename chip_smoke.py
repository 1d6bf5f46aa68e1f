"""Chip smoke: the served CRDT store on a TPU, through its entry points.

One chip (the default, what the driver runs):

1. a 16M-slot `DenseCrdt` (~570 MB of lanes) on the TPU, with
   ``executor="auto"`` taking the Mosaic merge and ingest kernels;
2. `ServeTier` on loopback, 32 client sessions over both lanes (JSON
   put/delete/get and 16-op binop frames), a few thousand writes
   spread over the keyspace, every acked write read back over the
   wire and checked against a scalar `MapCrdt` oracle fed the same
   operations;
3. packed anti-entropy with a second 16M-slot peer through the tier,
   both ways, until a pull returns an empty delta;
4. an 8-peer `merge_many` fan-in (8 x 16M changeset rows, ~3 GB),
   lane for lane equal to the same merge on ``executor="xla"`` and to
   the oracle on the touched keys;
5. wire deletes, ``tier.gc_pass(drift_slack_ms=0)`` and ``compact``,
   with every touched key read back.

``--chips 4`` runs only the multi-chip phase: a `ShardedDenseCrdt` of
64M slots key-sharded over 4 chips merged through the sharded Mosaic
fan-in, and a `CollectiveGroup` join over 4 members, each compared
with single-device `DenseCrdt` merges of the same inputs.

Earlier lines are one JSON object per phase; the last line is
``{"ok": true, "device": {...}}``. A missing TPU, a failed phase or a
wrong answer exits non-zero without that line. ``--rehearse`` runs the
same control flow at a tiny size under ``JAX_PLATFORMS=cpu`` (Pallas
in interpret mode) and never reports ``ok``.
"""

from __future__ import annotations

import argparse
import json
import socket
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np

from crdt_tpu import (CollectiveGroup, DenseCrdt, Hlc, MapCrdt,
                      PeerConnection, Record, ServeTier, ShardedDenseCrdt,
                      sync_packed_over_conn)
from crdt_tpu.compile_cache import enable_compile_cache
from crdt_tpu.hlc import SHIFT
from crdt_tpu.net import (BINOP_DELETE, BINOP_GET, BINOP_PUT, BINOP_ST_OK,
                          BINOP_ST_OK_NULL, FrameCodec, binop_round,
                          recv_frame, send_frame)
from crdt_tpu.obs.device import default_ledger
from crdt_tpu.ops.dense import DenseChangeset, DenseStore
from crdt_tpu.parallel import changeset_sharding, make_fanin_mesh
from crdt_tpu.parallel.collective import make_collective_mesh


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileMeter:
    """Backend compile seconds (total and by jitted function) and
    persistent-cache hits/misses, from JAX's own monitoring events."""

    def __init__(self):
        self.by_fun = {}
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            n, t = self.by_fun.get(fun_name, (0, 0.0))
            self.by_fun[fun_name] = (n + 1, t + secs)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        top = sorted(self.by_fun.items(), key=lambda kv: -kv[1][1])[:3]
        return {"compile_s": sum(t for _, t in self.by_fun.values()),
                "compiles": sum(n for n, _ in self.by_fun.values()),
                "slowest_compiles": [[f, n, t] for f, (n, t) in top],
                "cache_hits": self.hits, "cache_misses": self.misses}


class Spans:
    """Wall seconds by name within one phase; `take` hands them over
    and starts the next phase from zero."""

    def __init__(self):
        self._s = {}

    @contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self._s[name] = (self._s.get(name, 0.0)
                             + time.perf_counter() - t)

    def take(self) -> dict:
        out, self._s = self._s, {}
        return {"spans_s": out}


# --- wire clients -----------------------------------------------------

def _json_session(tier):
    sock = socket.create_connection((tier.host, tier.port), timeout=60)
    sock.settimeout(60)
    return sock


def _json_req(sock, msg):
    send_frame(sock, msg)
    return recv_frame(sock, deadline=time.monotonic() + 60)


def _binop_session(tier):
    sock = _json_session(tier)
    send_frame(sock, {"op": "hello", "proto": 1, "caps": ["binop"]})
    reply = recv_frame(sock, deadline=time.monotonic() + 60)
    check(reply and reply.get("ok") and "binop" in reply["caps"],
          f"binop not negotiated: {reply}")
    return sock, FrameCodec(compress=False)


def _binop(sock, codec, ops, slots, vals):
    return binop_round(sock, ops, slots, vals,
                       deadline=time.monotonic() + 60, codec=codec)


def read_over_wire(tier, slots, sessions: int = 8) -> dict:
    """Every slot read back through binop GET frames of 16."""
    slots = [int(s) for s in slots]
    parts = [slots[i::sessions] for i in range(sessions)]

    def run(part):
        out = {}
        sock, codec = _binop_session(tier)
        with sock:
            for i in range(0, len(part), 16):
                chunk = part[i:i + 16]
                st, vals, det = _binop(sock, codec,
                                       [BINOP_GET] * len(chunk), chunk,
                                       [0] * len(chunk))
                for j, s in enumerate(chunk):
                    check(st[j] in (BINOP_ST_OK, BINOP_ST_OK_NULL),
                          f"get {s} failed: {det}")
                    out[s] = (None if st[j] == BINOP_ST_OK_NULL
                              else int(vals[j]))
        return out

    got = {}
    with ThreadPoolExecutor(sessions) as pool:
        for part_out in pool.map(run, parts):
            got.update(part_out)
    return got


def check_reads(got: dict, want: dict, what: str) -> None:
    bad = [(k, got.get(k), v) for k, v in want.items() if got.get(k) != v]
    check(not bad, f"{what}: {len(bad)} keys differ from the oracle, "
          f"first {bad[:5]}")


# --- device-side data --------------------------------------------------

def peer_changesets(n, peers, seed, old_ms, new_ms, density,
                    plant_every=None, sharding=None):
    """``peers`` one-row changesets made on the device from ``seed``:
    ``density`` of the slots valid, half the stamps at ``old_ms`` and
    half at ``new_ms`` (plus up to 1 s), 10% tombstones.
    ``plant_every`` puts peer 0's top stamp at the first slot of every
    block of that size, so each key shard sees the global max clock."""

    def gen(i, old_ms, new_ms):
        k = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(seed), i), 6)
        shape = (1, n)
        valid = jax.random.uniform(k[0], shape) < density
        newer = jax.random.uniform(k[1], shape) < 0.5
        ms = (jnp.where(newer, new_ms, old_ms)
              + jax.random.randint(k[2], shape, 0, 1000)).astype(jnp.int64)
        lt = (ms << SHIFT) | jax.random.randint(k[3], shape, 0, 16
                                                ).astype(jnp.int64)
        val = jax.random.randint(k[4], shape, -(1 << 30), 1 << 30
                                 ).astype(jnp.int64)
        tomb = jax.random.uniform(k[5], shape) < 0.1
        if plant_every is not None:
            at = ((jnp.arange(n) % plant_every == 0) & (i == 0))[None]
            lt = jnp.where(at, (new_ms + 2000) << SHIFT, lt)
            valid = valid | at
            tomb = tomb & ~at
        return DenseChangeset(lt=lt, node=jnp.zeros(shape, jnp.int32),
                              val=val, tomb=tomb, valid=valid)

    # One program for every peer and every run: the peer index and the
    # clock are arguments, not constants, so the compile cache hits.
    make = jax.jit(gen, out_shardings=sharding)
    return [(make(jnp.int32(i), jnp.int64(old_ms), jnp.int64(new_ms)),
             [f"p{i}"]) for i in range(peers)]


def changeset_records(changesets, keys):
    """The oracle's view of the changesets on ``keys``: one
    slot -> Record dict per peer, in merge order."""
    idx = jnp.asarray(np.asarray(keys, np.int64))
    out = []
    for cs, ids in changesets:
        lt, val, tomb, valid = jax.device_get(
            (cs.lt[0, idx], cs.val[0, idx], cs.tomb[0, idx],
             cs.valid[0, idx]))
        recs = {}
        for j in np.nonzero(valid)[0]:
            hlc = Hlc.from_logical_time(int(lt[j]), ids[0])
            recs[int(keys[j])] = Record(
                hlc, None if tomb[j] else int(val[j]), hlc)
        out.append(recs)
    return out


def key_blocks(lane, axis: int = 0) -> list:
    """A sharded lane's per-device blocks in key order."""
    return sorted(lane.addressable_shards,
                  key=lambda b: b.index[axis].start or 0)


def lanes_equal(a, b) -> bool:
    return all(bool(jnp.array_equal(x, y)) for x, y in zip(a, b))


def copy_store(store):
    return DenseStore(*(jnp.copy(x) for x in store))


# --- one chip -----------------------------------------------------------

def one_chip(rehearse: bool, seed: int, meter: CompileMeter) -> None:

    n = 1 << 15 if rehearse else 1 << 24
    executor = "pallas-interpret" if rehearse else "auto"
    rng = np.random.default_rng(seed)
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    start_ms = int(time.time() * 1000)

    served = DenseCrdt("a", n, executor=executor)
    placed = {d for lane in served._held for d in lane.devices()}
    check(placed == {dev}, f"store lanes on {placed}, not {dev}")
    check(served._use_pallas() and served._planes is not None,
          "the merge or ingest gate did not take the Mosaic kernel")
    resident = sum(int(lane.nbytes) for lane in served._held)
    emit("setup", n_slots=n, store_bytes=resident, executor=executor,
         seconds=time.perf_counter() - t0, **meter.snapshot())

    # --- serve: 32 sessions over both lanes ---
    # 16 JSON sessions of 64 keys, then 16 binop sessions of 128, each
    # on its own keys, so the oracle can replay session by session.
    edges = np.cumsum([0] + [64] * 16 + [128] * 16)
    keys = rng.choice(n, size=int(edges[-1]), replace=False)
    vals = rng.integers(-(1 << 62), 1 << 62, size=len(keys))
    blocks = [("json" if i < 16 else "bin", keys[lo:hi], vals[lo:hi])
              for i, (lo, hi) in enumerate(zip(edges[:-1], edges[1:]))]

    def json_client(ks, vs):
        log = []
        with _json_session(tier) as sock:
            for k, v in zip(ks, vs):
                r = _json_req(sock, {"op": "put", "slot": int(k),
                                     "value": int(v)})
                check(r == {"ok": True}, f"put {k}: {r}")
                log.append((int(k), int(v)))
            for k in ks[:8]:
                r = _json_req(sock, {"op": "delete", "slot": int(k)})
                check(r == {"ok": True}, f"delete {k}: {r}")
                log.append((int(k), None))
            for k, v in zip(ks[8:16], vs[8:16]):
                r = _json_req(sock, {"op": "get", "slot": int(k)})
                check(r == {"ok": True, "value": int(v)}, f"get {k}: {r}")
        return log

    def bin_client(ks, vs):
        log = []
        sock, codec = _binop_session(tier)
        with sock:
            for i in range(0, len(ks), 16):
                sl, va = ks[i:i + 16], vs[i:i + 16]
                st, _, det = _binop(sock, codec, [BINOP_PUT] * 16, sl, va)
                check(all(s == BINOP_ST_OK for s in st),
                      f"binop puts: {det}")
                log += [(int(k), int(v)) for k, v in zip(sl, va)]
            # One mixed frame: 8 deletes and 8 overwrites.
            sl = np.concatenate([ks[:8], ks[16:24]])
            va = np.concatenate([np.zeros(8, np.int64), vs[16:24] + 1])
            st, _, det = _binop(sock, codec,
                                [BINOP_DELETE] * 8 + [BINOP_PUT] * 8,
                                sl, va)
            check(all(s == BINOP_ST_OK for s in st), f"binop mix: {det}")
            log += [(int(k), None) for k in ks[:8]]
            log += [(int(k), int(v) + 1) for k, v in zip(ks[16:24],
                                                         vs[16:24])]
        return log

    spans = Spans()

    def read(slots):
        with spans("readback"):
            return read_over_wire(tier, slots)

    t = time.perf_counter()
    tier = ServeTier(served).start()
    stopped = False
    views0 = default_ledger().dispatches("dense.store_view")
    try:
        with ThreadPoolExecutor(len(blocks)) as pool:
            logs = list(pool.map(
                lambda b: (json_client if b[0] == "json"
                           else bin_client)(b[1], b[2]), blocks))
        acked = sum(len(log) for log in logs)
        oracle = MapCrdt("a")
        for log in logs:          # keys are disjoint across sessions
            for k, v in log:
                oracle.put(k, v)
        touched = set(int(k) for k in keys)
        check_reads(read(sorted(touched)),
                    {k: oracle.get(k) for k in touched}, "serve readback")
        # Served writes and reads run on the resident planes: no
        # joined view of the store is built for them.
        views = default_ledger().dispatches("dense.store_view") - views0
        check(views == 0, f"the served phase built {views} store views")
        emit("serve", sessions=len(blocks), ops_acked=acked,
             keys=len(touched), store_views=views,
             seconds=time.perf_counter() - t,
             **spans.take(), **meter.snapshot())

        # --- anti-entropy with a second 16M-slot peer ---
        t = time.perf_counter()
        time.sleep(0.005)
        peer = DenseCrdt("b", n, executor=executor)
        live = [k for k in sorted(touched) if oracle.get(k) is not None]
        b_keys = np.concatenate([
            rng.choice(live, size=256, replace=False),
            rng.choice(n, size=768, replace=False)]).astype(np.int64)
        b_keys = np.unique(b_keys)
        b_vals = rng.integers(-(1 << 62), 1 << 62, size=len(b_keys))
        peer.put_batch(b_keys, b_vals)
        peer.delete_batch(b_keys[:64])
        time.sleep(0.005)
        oracle_b = MapCrdt("b")
        oracle_b.put_all({int(k): int(v) for k, v in zip(b_keys, b_vals)})
        oracle_b.put_all({int(k): None for k in b_keys[:64]})
        oracle.merge(oracle_b.record_map())
        touched |= set(int(k) for k in b_keys)

        rounds, empty = 0, False
        with PeerConnection(tier.host, tier.port, timeout=120) as conn:
            mark = None
            while not empty:
                check(rounds < 8, "anti-entropy never reached an empty "
                      "delta")
                before = peer.stats.merges
                mark = sync_packed_over_conn(peer, conn, since=mark)
                rounds += 1
                empty = rounds > 1 and peer.stats.merges == before
        want = {k: oracle.get(k) for k in touched}
        check_reads(read(sorted(touched)), want,
                    "served after sync")
        check_reads({k: peer.get(k) for k in touched}, want,
                    "peer after sync")
        del peer
        emit("anti_entropy", rounds=rounds, empty_delta=empty,
             seconds=time.perf_counter() - t, **spans.take(),
             **meter.snapshot())

        # --- 8-peer fan-in, Mosaic against XLA and the oracle ---
        t = time.perf_counter()
        now_ms = int(time.time() * 1000)
        changesets = peer_changesets(n, 8, seed, start_ms - 20_000,
                                     now_ms + 5_000, 1 / 8)
        sample = set(int(k) for k in rng.choice(n, size=4096,
                                                replace=False))
        check_keys = sorted(touched | sample)
        for recs in changeset_records(changesets, check_keys):
            oracle.merge(recs)
        with tier.lock:
            twin = DenseCrdt("a", n, store=copy_store(served.store),
                             node_ids=served._table.ids(),
                             executor="xla")
            ledger0 = default_ledger().as_dict()
            with spans("mosaic_merge"):
                served.merge_many(changesets)
                jax.block_until_ready(served.store)
            ledger1 = default_ledger().as_dict()
            with spans("xla_merge"):
                twin.merge_many(changesets)
                jax.block_until_ready(twin.store)
            check(lanes_equal(served.store, twin.store),
                  "Mosaic merge_many differs from executor='xla'")
            occupied = int(jnp.sum(served.store.occupied))
        merged_rows = sum(int(jnp.sum(cs.valid)) for cs, _ in changesets)
        del twin, changesets
        kernel = "pallas.model_fanin_batch" + (
            "[interpret]" if rehearse else "")
        check(ledger1.get(kernel, 0) > ledger0.get(kernel, 0),
              f"merge_many did not dispatch {kernel}")
        check_reads(read(check_keys),
                    {k: oracle.get(k) for k in check_keys},
                    "served after merge_many")
        emit("merge_many", peers=8, changeset_rows=8 * n,
             record_merges=merged_rows, occupied=occupied,
             seconds=time.perf_counter() - t, **spans.take(),
             **meter.snapshot())

        # --- deletes, epoch GC, compaction ---
        t = time.perf_counter()
        live = [k for k in sorted(touched) if oracle.get(k) is not None]
        doomed = rng.choice(live, size=256, replace=False)
        with _json_session(tier) as sock:
            for k in doomed:
                r = _json_req(sock, {"op": "delete", "slot": int(k)})
                check(r == {"ok": True}, f"delete {k}: {r}")
                oracle.delete(int(k))
        with tier.lock:
            served.drain_ingest()
            tombs = int(jnp.sum(served.store.occupied
                                & served.store.tomb))
        with spans("gc"):
            purged = tier.gc_pass(drift_slack_ms=0)
        check(purged == tombs and purged > 0,
              f"gc purged {purged} of {tombs} tombstones")
        want = {k: oracle.get(k) for k in check_keys}
        check_reads(read(check_keys), want, "after gc")
        with tier.lock:
            with spans("compact"):
                tr = served.compact()
            live_rows = int(jnp.sum(served.store.occupied))
        moved = np.sort(tr[tr >= 0])
        check(len(moved) == live_rows
              and np.array_equal(moved, np.arange(live_rows)),
              "compaction did not pack the live rows into a prefix")
        gone = [k for k, v in want.items() if v is None and tr[k] >= 0]
        check(not gone, f"deleted keys survived compaction: {gone[:5]}")
        got = read([int(tr[k]) for k, v in want.items()
                    if v is not None])
        check_reads({k: got[int(tr[k])] for k, v in want.items()
                     if v is not None},
                    {k: v for k, v in want.items() if v is not None},
                    "after compaction")
        tail = rng.integers(live_rows, n, size=64) if live_rows < n else []
        check(all(v is None for v in read(tail).values()),
              "slots past the compacted prefix are not empty")
        emit("gc_compact", deleted=len(doomed), purged=purged,
             live_rows=live_rows, seconds=time.perf_counter() - t,
             **spans.take(), **meter.snapshot())

        t = time.perf_counter()
        tier.stop()
        stopped = True
        check(time.perf_counter() - t < 10, "tier.stop() was slow")
    finally:
        if not stopped:
            tier.kill()

    names = default_ledger().as_dict()
    mosaic = sorted(k for k in names if k.startswith("pallas."))
    interpreted = [k for k in names if k.endswith("[interpret]")]
    check("pallas.ingest_scatter_planes" + ("[interpret]" if rehearse
                                            else "") in names,
          "no ingest commit went through the Mosaic kernel")
    check(bool(interpreted) == rehearse,
          f"interpret-mode dispatches on the chip: {interpreted}")
    stats = dev.memory_stats() or {}
    emit("ledger", mosaic_kernels={k: names[k] for k in mosaic},
         dispatches=sum(names.values()),
         bytes_in_use=stats.get("bytes_in_use"),
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         device_kind=dev.device_kind)


# --- four chips ---------------------------------------------------------

def four_chips(rehearse: bool, seed: int, meter: CompileMeter) -> None:

    devs = jax.devices()[:4]
    per = 1 << 13 if rehearse else 1 << 24
    n = 4 * per
    executor = "pallas-interpret" if rehearse else "auto"
    rng = np.random.default_rng(seed)
    start_ms = int(time.time() * 1000)

    spans = Spans()

    # --- key-sharded store, sharded Mosaic fan-in ---
    t = time.perf_counter()
    mesh = make_fanin_mesh(1, 4, devs)
    sh = ShardedDenseCrdt("s", n, mesh, executor=executor)
    keys = rng.choice(n, size=65536 if not rehearse else 512,
                      replace=False)
    sh.put_batch(keys, rng.integers(-(1 << 62), 1 << 62, size=len(keys)))
    for lane in sh.store:
        homes = {b.device for b in key_blocks(lane)}
        check(len(homes) == 4
              and all(b.data.shape == (per,) for b in key_blocks(lane)),
              f"store lane not one {per}-slot shard per device: {homes}")
    check(sh._use_pallas_sharded(),
          "the sharded fan-in did not take the Mosaic kernel")
    ids0 = sh._table.ids()
    before = [copy_store([b.data for b in blocks]) for blocks in
              zip(*(key_blocks(lane) for lane in sh.store))]
    changesets = peer_changesets(n, 8, seed, start_ms - 20_000,
                                 int(time.time() * 1000) + 5_000, 1 / 8,
                                 plant_every=per,
                                 sharding=changeset_sharding(mesh))
    with spans("sharded_merge"):
        sh.merge_many(changesets)
        jax.block_until_ready(sh.store)
    after = list(zip(*(key_blocks(lane) for lane in sh.store)))
    parts = [list(zip(*(key_blocks(lane, 1) for lane in cs)))
             for cs, _ in changesets]
    for d in range(4):
        # The single-device merge of key range d, on the chip that
        # holds it; peer 0 plants the global max clock in every range,
        # so each range sees the sharded merge's canonical.
        twin = DenseCrdt("s", per, store=before[d], node_ids=ids0,
                         executor=executor)
        with spans("single_device_merges"):
            twin.merge_many([(type(cs)(*(b.data for b in parts[i][d])),
                              ids) for i, (cs, ids) in enumerate(changesets)])
            jax.block_until_ready(twin.store)
        check(twin._table.ids() == sh._table.ids(),
              "node tables diverged")
        check(lanes_equal([b.data for b in after[d]], twin.store),
              f"key shard {d} differs from the single-device merge")
        del twin
    del before, parts, changesets
    emit("sharded_fanin", n_slots=n, shards=4, per_shard=per,
         devices=[str(blocks[0].device) for blocks in after],
         seconds=time.perf_counter() - t, **spans.take(),
         **meter.snapshot())

    # --- collective join over 4 members ---
    t = time.perf_counter()
    del sh, after
    members = []
    shared = rng.choice(per, size=1024 if not rehearse else 64,
                        replace=False)
    for i in range(4):
        m = DenseCrdt(f"m{i}", per, executor=executor)
        own = rng.choice(per, size=4096 if not rehearse else 128,
                         replace=False)
        m.put_batch(np.unique(np.concatenate([own, shared])),
                    rng.integers(-(1 << 62), 1 << 62,
                                 size=len(np.unique(np.concatenate(
                                     [own, shared])))))
        m.delete_batch(own[:16])
        members.append(m)
        time.sleep(0.002)
    ref = DenseCrdt("m0", per, store=copy_store(members[0].store),
                    node_ids=members[0]._table.ids(), executor=executor)
    ref.merge_many([m.export_delta() for m in members[1:]])
    group = CollectiveGroup(members, mesh=make_collective_mesh(4, devs))
    with spans("collective_join"):
        report = group.join()
    want = ref.store
    for m in members:
        check(m._table.ids() == ref._table.ids(), "node tables diverged")
        got = m.store
        # Record lanes where a record lives; never-written slots carry
        # each replica's own filler.
        check(bool(jnp.array_equal(got.occupied, want.occupied))
              and lanes_equal(
                  [jnp.where(got.occupied, x, 0) for x in
                   (got.lt, got.node, got.val, got.tomb)],
                  [jnp.where(want.occupied, x, 0) for x in
                   (want.lt, want.node, want.val, want.tomb)]),
              f"member {m.node_id} differs from the single-device merge")
    homes = [sorted(str(d) for lane in m.store for d in lane.devices())
             for m in members]
    check(all(len(set(h)) == 1 for h in homes)
          and len({h[0] for h in homes}) == 4,
          f"members not one per device after the join: {homes}")
    homes = [h[0] for h in homes]
    emit("collective_join", members=4, n_slots=per,
         adopted=report.adopted, member_devices=homes,
         seconds=time.perf_counter() - t, **spans.take(),
         **meter.snapshot())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded and collective phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU rehearsal of the control flow "
                         "(JAX_PLATFORMS=cpu); never reports ok")
    args = ap.parse_args()

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    cache = None if args.rehearse else enable_compile_cache()
    meter = CompileMeter()
    emit("start", platform=platform, device_kind=devices[0].device_kind,
         devices=len(devices), chips=args.chips, compile_cache=cache)
    if args.chips == 4:
        four_chips(args.rehearse, args.seed, meter)
    else:
        one_chip(args.rehearse, args.seed, meter)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
