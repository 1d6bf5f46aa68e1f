"""Pod-local replica groups: N co-located `DenseCrdt`s converged by
ONE collective dispatch (docs/COLLECTIVE.md).

`CollectiveGroup` is the host-side owner of the
`parallel.collective.make_collective_join` program: it pins the
member replicas to a 1-D member mesh, keeps their node tables and
semantics columns aligned (two replicas must never join one slot
under two different lattices — the same contract `merge_packed`
enforces on the wire), and exposes :meth:`join`, after which every
member's replicated lanes are bit-identical to the socket-path merge
of the same deltas.

One ``join()`` is one device dispatch. Everything the pairwise relay
path gets from `merge_and_repack` rides the same program: per-member
``mod`` stamps, the next round's repack rows, compacted on the device
(pack caches are pre-seeded under each member's pre-join watermark),
and the post-join digest-tree levels, left on the device until a walk
asks (digest caches are pre-seeded too) — so a follow-up cross-pod
socket round packs and walks from warm caches without dispatching
anything, and a round moves kilobytes to the host, not the stores.

Group membership is declared at construction, optionally with the
``"host:port"`` addresses the routing layer speaks (`routing.py`),
so `GossipNode` can detect mesh-co-located peers by address and route
intra-pod rounds here while cross-pod peers keep the
merkle→packed→dense→json ladder.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .hlc import Hlc
from .obs.registry import default_registry
from .ops.digest import DeviceDigestLevels
from .parallel.collective import (MEMBER_AXIS, REPACK_ROWS,
                                  make_collective_join,
                                  make_collective_mesh)


class CollectiveJoinReport:
    """What one collective round did — the in-process accounting twin
    of `sync.MerkleSyncReport`, for benches and invariant probes.
    ``bytes_to_wire`` is identically 0: the lattice join moved over
    the mesh, not a socket."""

    __slots__ = ("new_canonical", "win_counts", "digest_root",
                 "members")
    bytes_to_wire = 0

    def __init__(self, new_canonical: int, win_counts: List[int],
                 digest_root: int, members: int):
        self.new_canonical = new_canonical
        self.win_counts = win_counts
        self.digest_root = digest_root
        self.members = members

    @property
    def adopted(self) -> int:
        return sum(self.win_counts)


class CollectiveGroup:
    """N mesh-co-located `DenseCrdt` replicas joined as one collective.

    ``members`` are the live replica objects (>= 2, equal geometry,
    distinct node ids). ``mesh`` defaults to a 1-D member mesh over
    the first N devices. ``addresses`` optionally maps each member's
    node id to the ``"host:port"`` string its `GossipNode` server
    answers on — the routing-layer identity co-location detection
    keys on (consistent with `routing.py`, so replica groups per
    partition can adopt the same declaration)."""

    # Checked by analysis/concurrency.py: the collective path holds NO
    # host locks — the single-dispatch join serializes on the device
    # stream, and member stores are quiesced by the caller
    # (docs/COLLECTIVE.md). The empty contract makes "lock-free by
    # design" a checked statement rather than prose.
    _CRDTLINT_LOCK_ORDER: tuple = ()

    def __init__(self, members: Sequence[Any], mesh=None,
                 addresses: Optional[Dict[Any, str]] = None):
        members = list(members)
        if len(members) < 2:
            raise ValueError(
                f"a collective group needs >= 2 members, got "
                f"{len(members)}")
        ids = [m.node_id for m in members]
        if len(set(ids)) != len(ids):
            raise ValueError(
                f"collective group members must carry distinct node "
                f"ids, got {ids}")
        first = members[0]
        for m in members[1:]:
            if m.n_slots != first.n_slots:
                raise ValueError(
                    f"collective group members disagree on n_slots: "
                    f"{first.n_slots} vs {m.n_slots}")
            if m._value_width != first._value_width:
                raise ValueError(
                    "collective group members disagree on value_width")
            if m.DIGEST_LEAF_WIDTH != first.DIGEST_LEAF_WIDTH:
                raise ValueError(
                    "collective group members disagree on digest "
                    "leaf width")
        if mesh is None:
            mesh = make_collective_mesh(len(members))
        if mesh.shape[MEMBER_AXIS] != len(members):
            raise ValueError(
                f"mesh member extent {mesh.shape[MEMBER_AXIS]} != "
                f"{len(members)} members")
        self.members = members
        self.mesh = mesh
        self.addresses = dict(addresses or {})
        unknown = set(self.addresses) - set(ids)
        if unknown:
            raise ValueError(
                f"addresses name non-member node ids: {sorted(unknown)}")
        self._member_ids = set(id(m) for m in members)
        self._align_tables()

    # --- membership surface (what GossipNode's fast lane keys on) ---

    def contains(self, crdt: Any) -> bool:
        """Is this live replica object a group member (identity, not
        equality — a copy with the same node id is NOT co-located)."""
        return id(crdt) in self._member_ids

    def address_of(self, node_id: Any) -> Optional[str]:
        return self.addresses.get(node_id)

    def member_addresses(self) -> frozenset:
        """The declared ``"host:port"`` identities of the group —
        `GossipNode.add_peer` marks a peer collective when its
        address lands in this set."""
        return frozenset(self.addresses.values())

    # --- alignment: shared table, shared lattice ---

    def _align_tables(self) -> None:
        """Union-intern every member's node ids into every member.
        Node ordinals are replica-local (`ops.packing.NodeTable`), so
        the device compare of node lanes is only meaningful once all
        members hold the SAME sorted table; `_intern_ids` re-encodes
        stored lanes when ordinals shift (a dispatch — which is why
        steady-state rounds, where tables already agree, stay at one
        dispatch for the join itself)."""
        union: set = set()
        for m in self.members:
            union.update(m._table.ids())
        union_list = sorted(union, key=lambda x: (str(type(x)), str(x)))
        for m in self.members:
            if len(m._table) != len(union):
                m._intern_ids(union_list)

    def _check_semantics(self) -> bool:
        """All members must govern every slot by the same lattice
        before lanes may join — the collective twin of the packed
        wire's tag-mismatch rejection. Untyped members all agree: no
        tag column to compare."""
        if all(m._sem is None for m in self.members):
            return False
        sems = [m._sem_host() for m in self.members]
        ref = sems[0]
        for m, sem in zip(self.members[1:], sems[1:]):
            mism = sem != ref
            if bool(mism.any()):
                slot = int(np.nonzero(mism)[0][0])
                raise ValueError(
                    f"semantics tag mismatch at slot {slot}: member "
                    f"{self.members[0].node_id!r} holds tag "
                    f"{int(ref[slot])}, member {m.node_id!r} holds "
                    f"{int(sem[slot])}; run the same set_semantics "
                    "migration on every group member before joining")
        return bool(ref.any())

    # --- the round ---

    def join(self, seed_packs: bool = True) -> CollectiveJoinReport:
        """One collective anti-entropy round: drain ingest overlays,
        run the single-dispatch lattice join, land every member on the
        joined store with its canonical clock, digest cache and (when
        ``seed_packs``) pack cache pre-seeded — the `merge_and_repack`
        contract, amortized over the whole group in one program.

        What moves to the host is small and does not grow with
        ``n_slots``: the members' adopted and repack counts, the
        clock, the digest root and (when ``seed_packs``) each member's
        compacted repack rows, at most `REPACK_ROWS` a member, in one
        ``device_get``. A member past that bound is packed from its
        whole lanes. The digest levels stay on the device until a walk
        asks (`DeviceDigestLevels`). Bytes fetched, rows adopted and
        whole-lane pack fallbacks are counted in
        ``crdt_tpu_collective_*_total``."""
        from .obs.trace import round_id, span, tracer
        members = self.members
        for m in members:
            m.drain_ingest()
        self._align_tables()
        has_sem = self._check_semantics()

        watermarks = [m.canonical_time for m in members]
        table = members[0]._table
        me = np.asarray([table.ordinal(m.node_id) for m in members],
                        np.int32)
        since = np.asarray([w.logical_time for w in watermarks],
                           np.int64)
        canonical_in = jnp.int64(max(w.logical_time
                                     for w in watermarks))
        leaf_width = members[0].DIGEST_LEAF_WIDTH
        # CPU ignores donation (with a warning per call); only donate
        # when every member's snapshot is donatable on this backend.
        donate = all(m._donate_writes() for m in members)
        k = REPACK_ROWS
        step = make_collective_join(self.mesh, has_sem, leaf_width,
                                    donate=donate, repack_rows=k)

        node = str(members[0].node_id)
        rid = {"rid": round_id(node)} if tracer().enabled else {}
        with span("collective_join", kind="sync", node=node,
                  hlc=lambda: members[0].canonical_time,
                  members=len(members), **rid):
            stores = tuple(m._store for m in members)
            args = ((members[0]._sem_device(),) if has_sem else ())
            new_stores, res = step(stores, *args, since, me,
                                   canonical_in)
            with span("collective.fetch", kind="sync", node=node):
                # ONE batched fetch of what the host needs; the rows
                # only when they seed pack caches.
                want = (res.win_count, res.repack_count,
                        res.new_canonical, res.levels[0],
                        tuple(res.rows) if seed_packs else ())
                got = jax.device_get(want)
                fetched = sum(np.asarray(a).nbytes
                              for a in jax.tree_util.tree_leaves(got))
            win_h, repack_h, canonical, root, rows_h = got
            canonical = int(canonical)
            levels = DeviceDigestLevels(members[0].n_slots, leaf_width,
                                        res.levels)

        fallbacks = 0
        for i, m in enumerate(members):
            m._store = new_stores[i]        # setter clears both caches
            m._store_escaped = False
            # Clock lands without a refresh dispatch: the program's
            # canonical IS max(member canonicals, every lt joined).
            m._canonical_time = Hlc.from_logical_time(canonical,
                                                      m.node_id)
            m._digest_cache = ((canonical, m._sem_version,
                                m._store_gen), levels)
            m.stats.merges += 1
        if seed_packs:
            with span("collective.seed_pack", kind="pack", node=node):
                for i, m in enumerate(members):
                    count = int(repack_h[i])
                    if count <= k:
                        slots, *cols = (lane[i * k:i * k + count]
                                        for lane in rows_h)
                        mask = np.ones(count, bool)
                    else:
                        # Past the bound: the member's delta mask and
                        # whole lanes, as an uncached pack_since.
                        st = m._store
                        mask, *cols = jax.device_get(
                            (m._delta_mask(watermarks[i]), st.lt,
                             st.node, st.val, st.tomb))
                        slots = None
                        fetched += mask.nbytes + sum(c.nbytes
                                                     for c in cols)
                        fallbacks += 1
                    self._seed_pack(m, watermarks[i], mask, *cols,
                                    canonical, has_sem, slots=slots)
        reg = default_registry()
        reg.counter("crdt_tpu_collective_join_total",
                    "collective join rounds").inc(node=node)
        reg.counter("crdt_tpu_collective_fetch_bytes_total",
                    "bytes collective joins fetched from the devices "
                    "to the host").inc(fetched, node=node)
        reg.counter("crdt_tpu_collective_adopted_rows_total",
                    "rows members adopted in collective joins").inc(
                        int(win_h.sum()), node=node)
        if fallbacks:
            reg.counter("crdt_tpu_collective_pack_fallback_total",
                        "members whose repack rows passed the "
                        "collective join's bound, packed from whole "
                        "lanes").inc(fallbacks, node=node)
        return CollectiveJoinReport(
            new_canonical=canonical,
            win_counts=[int(c) for c in win_h],
            digest_root=int(root[0]), members=len(members))

    @staticmethod
    def _seed_pack(m, watermark: Hlc, mask, lt, node, val, tomb,
                   canonical: int, has_sem: bool,
                   slots: Optional[np.ndarray] = None) -> None:
        """Seed the member's pack cache under its pre-join watermark —
        the exact key the next watermark-aligned `pack_since` (a
        cross-pod peer resuming delta sync) presents. Host-side
        column select only (`_pack_host_columns`): no wire stage runs,
        so ``crdt_tpu_pack_copy_bytes_total`` does not move. With
        ``slots`` the columns are the member's compacted repack rows
        and these their slots; else whole host lanes."""
        resolved = m._resolve_sem_mode("include" if has_sem else "auto")
        packed = m._pack_host_columns(mask, lt, node, val, tomb,
                                      resolved, slots=slots)
        key = (watermark.logical_time, canonical, m._sem_version,
               m._store_gen, resolved, None)
        m._pack_cache_store(key, (packed, m._table.ids()))
