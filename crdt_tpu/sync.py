"""Anti-entropy sync rounds (C10) — the reference's replication protocol
as a library utility.

The reference keeps the sync round in its tests
(`test/map_crdt_test.dart:273-279`): capture the local canonical time,
full-push to the remote, then delta-pull everything the remote modified
at-or-after that time (inclusive bound, map_crdt.dart:44-45). Three-node
convergence through an intermediary relies on merged records being
re-stamped with the relay's ``modified`` time (crdt.dart:87) — the
relay's deltas then include records it learned from others.

Three transports:

- :func:`sync` — in-process record maps (replicas share a process, the
  reference's own test topology).
- :func:`sync_json` — the JSON wire format (crdt_json.dart), what
  crosses a real replica boundary; transport remains the application's
  job (example/crdt_example.dart:21-25).
- :func:`sync_packed` — the O(k) packed columnar form
  (`DenseCrdt.pack_since` / `merge_packed`), the in-process twin of
  `net.sync_packed_over_conn` — same one-watermark round shape, no
  sockets. Both replicas must speak the packed form.

Plus one group form: :func:`sync_collective` joins a whole
mesh-co-located `CollectiveGroup` in ONE device dispatch — no wire
form at all (docs/COLLECTIVE.md).
"""

from __future__ import annotations

from typing import Optional

from .crdt import Crdt
from .hlc import Hlc
from .record import (KeyDecoder, KeyEncoder, ValueDecoder, ValueEncoder)

# Default for ``since``: pull from the SAME round's pre-push canonical
# time — the reference's one-shot `_sync` shape. Distinct from None,
# which (matching `sync_over_tcp`) requests a cold-start FULL pull.
_SAME_ROUND = object()


def sync(local: Crdt, remote: Crdt, since=_SAME_ROUND) -> Hlc:
    """One push/pull anti-entropy round between two in-process replicas.

    After a round in each direction (or one round plus a later reverse
    round) the two replicas converge; N replicas converge through any
    connected gossip topology.

    ``since`` aligns this with :func:`crdt_tpu.net.sync_over_tcp`'s
    watermark contract: omit it for the reference's one-shot round
    (pull bounded by this round's pre-push canonical time), pass
    ``None`` for a cold-start full pull, or pass the watermark a
    previous round returned to resume delta sync."""
    watermark = local.canonical_time
    remote.merge(local.record_map())
    local.merge(remote.record_map(
        modified_since=watermark if since is _SAME_ROUND else since))
    return watermark


def sync_json(local: Crdt, remote: Crdt,
              key_encoder: Optional[KeyEncoder] = None,
              value_encoder: Optional[ValueEncoder] = None,
              key_decoder: Optional[KeyDecoder] = None,
              value_decoder: Optional[ValueDecoder] = None,
              since=_SAME_ROUND) -> Hlc:
    """The same round over the JSON wire format — full-state push, then
    delta pull keyed on the pre-push canonical time (crdt.dart:124-135).
    ``since`` follows :func:`sync`'s watermark contract."""
    watermark = local.canonical_time
    remote.merge_json(local.to_json(key_encoder=key_encoder,
                                    value_encoder=value_encoder),
                      key_decoder=key_decoder,
                      value_decoder=value_decoder)
    local.merge_json(remote.to_json(
        modified_since=watermark if since is _SAME_ROUND else since,
        key_encoder=key_encoder,
        value_encoder=value_encoder),
        key_decoder=key_decoder,
        value_decoder=value_decoder)
    return watermark


def sync_packed(local, remote, since=_SAME_ROUND) -> Hlc:
    """The same round on the packed columnar wire form: push only the
    rows the local replica modified since ``since``, pull only the
    rows the remote modified since the same watermark. ``since``
    follows :func:`sync`'s contract (omit: one-shot round bounded by
    this round's pre-push canonical time; ``None``: cold-start full
    exchange; a prior round's return: resume delta sync — the single
    watermark soundly bounds BOTH halves, exactly as
    `net.sync_packed_over_conn`). Empty halves (k == 0) skip the
    merge, keeping both clocks — and so both pack caches — still on
    a no-change round."""
    # Commit any ingest-window backlog before the watermark read:
    # pack_since drains internally, but that flush advances the
    # canonical AFTER a watermark captured here, and the stale bound
    # would re-send every flushed row on the next round.
    drain = getattr(local, "drain_ingest", None)
    if drain is not None:
        drain()
    watermark = local.canonical_time
    # One-shot shape: FULL push (the reference pushes its whole record
    # map), pull bounded by the pre-push canonical time. With an
    # explicit watermark, the same bound governs both halves.
    push_bound = None if since is _SAME_ROUND else since
    pull_bound = watermark if since is _SAME_ROUND else since
    # In-process twin of the hello negotiation: the sem tag lane rides
    # only when BOTH replicas expose the typed surface (docs/TYPES.md);
    # otherwise typed rows are withheld, never stripped of their tags.
    from .net import _pack_for_peer
    from .obs.trace import round_id, span, tracer
    sem_ok = (hasattr(local, "set_semantics")
              and hasattr(remote, "set_semantics"))
    node = str(getattr(local, "node_id", ""))
    # Same round id a socket round would carry in its trace context —
    # in-process rounds correlate in the one JSONL sink identically.
    rid = {"rid": round_id(node)} if tracer().enabled else {}
    with span("sync_packed", kind="sync", node=node,
              hlc=lambda: local.canonical_time,
              peer=str(getattr(remote, "node_id", "")), **rid):
        packed, ids = _pack_for_peer(local, push_bound, sem_ok)
        if packed.k:
            remote.merge_packed(packed, ids)
        pulled, pulled_ids = _pack_for_peer(remote, pull_bound, sem_ok)
        if pulled.k:
            if hasattr(local, "merge_and_repack"):
                # Fused merge+repack: the pull's join also computes
                # (and caches) the NEXT round's push pack under this
                # round's watermark — the exact `since` a resumed
                # delta round presents (docs/FASTPATH.md).
                local.merge_and_repack(
                    pulled, pulled_ids, since=watermark,
                    sem_mode="include" if sem_ok else "auto")
            else:
                local.merge_packed(pulled, pulled_ids)
    return watermark


def sync_collective(group):
    """One anti-entropy round over a whole mesh-co-located replica
    group as a SINGLE device dispatch — the in-process twin of the
    gossip fast lane's collective round, for benches and tests that
    want the group shape without a `GossipNode`.

    Where :func:`sync_packed` converges one replica *pair* per call
    (N replicas need O(N²) rounds through a connected topology), one
    ``sync_collective(group)`` call lands every member of the
    `crdt_tpu.collective.CollectiveGroup` on the joined state at once:
    zero bytes to any wire, zero pack-path copies, pack and digest
    caches pre-seeded (docs/COLLECTIVE.md). Returns the group's
    `CollectiveJoinReport`."""
    # `CollectiveGroup.join` carries its own "collective_join" span
    # (kind="sync", round id, member count) — the trace shape this
    # module's pairwise rounds set, one level up.
    return group.join()


class MerkleSyncReport:
    """What one in-process anti-entropy round cost
    (:func:`sync_merkle`) — the accounting the socket path keeps in
    metrics/WireTally, exposed as a plain object so a topology of
    in-process replicas can sum traffic without sockets.
    ``digest_bytes`` models the walk's wire cost (8 bytes per digest
    value, both directions); ``payload_bytes`` is the packed arenas'
    exact size. An empty ``ranges`` means the trees matched and no
    payload moved."""

    __slots__ = ("watermark", "rounds", "digests", "ranges",
                 "pushed_rows", "pulled_rows", "payload_bytes")

    def __init__(self, watermark, rounds, digests, ranges,
                 pushed_rows, pulled_rows, payload_bytes):
        self.watermark = watermark
        self.rounds = rounds
        self.digests = digests
        self.ranges = ranges
        self.pushed_rows = pushed_rows
        self.pulled_rows = pulled_rows
        self.payload_bytes = payload_bytes

    @property
    def digest_bytes(self) -> int:
        return 16 * self.digests   # 8B value out + 8B value back

    @property
    def total_bytes(self) -> int:
        return self.digest_bytes + self.payload_bytes


def _packed_nbytes(packed) -> int:
    total = 0
    for lane in packed:
        nbytes = getattr(lane, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
    return total


def sync_merkle(local, remote) -> MerkleSyncReport:
    """In-process twin of `net.sync_merkle_over_conn`
    (docs/ANTIENTROPY.md): compare digest trees, walk only differing
    subtrees (one `walk_divergent_leaves` level per simulated round
    trip), then exchange JUST the divergent leaf ranges through
    ``pack_since(ranges=...)`` both ways. Matching roots cost one
    probe and zero payload. Raises ValueError on tree geometry
    mismatch — the socket path's ``merkle_rejected``, where a full
    packed round is the right fallback."""
    from .ops.digest import coalesce_leaf_ranges, walk_divergent_leaves
    from .obs.trace import round_id, span, tracer
    drain = getattr(local, "drain_ingest", None)
    if drain is not None:
        drain()
    watermark = local.canonical_time
    node = str(getattr(local, "node_id", ""))
    rid = {"rid": round_id(node)} if tracer().enabled else {}
    with span("sync_merkle", kind="sync", node=node,
              hlc=lambda: local.canonical_time,
              peer=str(getattr(remote, "node_id", "")), **rid):
        tree = local.digest_tree()
        remote_tree = remote.digest_tree()
        if not tree.same_geometry(remote_tree.n_slots,
                                  remote_tree.leaf_width,
                                  remote_tree.depth):
            raise ValueError(
                f"merkle geometry mismatch: local ({tree.n_slots}, "
                f"{tree.leaf_width}) vs remote ({remote_tree.n_slots}, "
                f"{remote_tree.leaf_width})")
        leaves, rounds, fetched = walk_divergent_leaves(
            tree, remote_tree.values)
        if not leaves:
            return MerkleSyncReport(watermark, rounds, fetched, (),
                                    0, 0, 0)
        ranges = coalesce_leaf_ranges(leaves, tree.leaf_width,
                                      tree.n_slots)
        from .net import _pack_for_peer
        sem_ok = (hasattr(local, "set_semantics")
                  and hasattr(remote, "set_semantics"))
        packed, ids = _pack_for_peer(local, None, sem_ok,
                                     ranges=ranges)
        payload = _packed_nbytes(packed) if packed.k else 0
        if packed.k:
            remote.merge_packed(packed, ids)
        pulled, pulled_ids = _pack_for_peer(remote, None, sem_ok,
                                            ranges=ranges)
        payload += _packed_nbytes(pulled) if pulled.k else 0
        if pulled.k:
            if hasattr(local, "merge_and_repack"):
                local.merge_and_repack(
                    pulled, pulled_ids, since=watermark,
                    sem_mode="include" if sem_ok else "auto")
            else:
                local.merge_packed(pulled, pulled_ids)
    return MerkleSyncReport(watermark, rounds, fetched, ranges,
                            int(packed.k), int(pulled.k), payload)
