"""HLC lane packing: scalar Hlc <-> (int64 lt, int32 node ordinal).

The hard part (SURVEY.md §7 build step 1) is an order-preserving node-id
encoding: ``Hlc.compareTo`` tie-breaks on the node id's natural
comparison (hlc.dart:160), which for arbitrary-length strings cannot be
embedded into a fixed-width integer in general. Instead each store keeps
a :class:`NodeTable` — a sorted dictionary of every node id it has seen —
and carries the *ordinal* in the lane. Ordinal comparison then equals
string comparison exactly. When a new node id lands between existing
ones, previously issued ordinals shift; the table reports a remap vector
so stored lanes can be re-encoded with one gather (node counts are tiny —
they are replicas, not records).
"""

from __future__ import annotations

import bisect
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..hlc import SHIFT, MAX_COUNTER, Hlc


def pack_logical_time(millis: int, counter: int) -> int:
    """(millis, counter) -> int64 logicalTime (hlc.dart:16)."""
    return (millis << SHIFT) + counter


def unpack_logical_time(lt: int) -> Tuple[int, int]:
    return lt >> SHIFT, lt & MAX_COUNTER


class NodeTable:
    """Order-preserving node-id interning for one store.

    Ordinals are indices into the sorted id list, so
    ``ordinal(a) < ordinal(b)  <=>  a < b`` under the ids' natural
    comparison — the exact tie-break ``Hlc.compareTo`` uses
    (hlc.dart:158-161). Node ids must be mutually comparable (all str or
    all int, as in the reference).
    """

    def __init__(self, ids: Optional[Sequence[Any]] = None):
        self._sorted: List[Any] = sorted(set(ids)) if ids else []
        self._omap = {v: i for i, v in enumerate(self._sorted)}

    def __len__(self) -> int:
        return len(self._sorted)

    def __contains__(self, node_id: Any) -> bool:
        i = bisect.bisect_left(self._sorted, node_id)
        return i < len(self._sorted) and self._sorted[i] == node_id

    def ordinal(self, node_id: Any) -> int:
        """Ordinal of an already-interned id."""
        i = bisect.bisect_left(self._sorted, node_id)
        if i == len(self._sorted) or self._sorted[i] != node_id:
            raise KeyError(node_id)
        return i

    def id_of(self, ordinal: int) -> Any:
        return self._sorted[ordinal]

    def ids(self) -> List[Any]:
        """All interned ids in ordinal order (a copy)."""
        return list(self._sorted)

    def intern(self, node_ids: Sequence[Any]
               ) -> Optional[np.ndarray]:
        """Add any unseen ids. Returns an int32 remap vector mapping old
        ordinal -> new ordinal if existing ordinals shifted, else None.
        Apply it to stored node lanes via ``remap[lane]``."""
        new = sorted(set(node_ids) - set(self._sorted))
        if not new:
            return None
        old = self._sorted
        merged = sorted(old + new)
        remap = np.empty(len(old), dtype=np.int32)
        positions = {v: i for i, v in enumerate(merged)}
        for i, v in enumerate(old):
            remap[i] = positions[v]
        self._sorted = merged
        self._omap = positions
        if np.array_equal(remap, np.arange(len(old), dtype=np.int32)):
            return None  # new ids all sort after existing ones
        return remap

    def encode(self, node_ids: Sequence[Any]) -> np.ndarray:
        """Ordinals for already-interned ids — one maintained dict
        lookup per id, O(m) for an m-id batch (the vectorized host
        encode every backend shares). KeyError on uninterned ids.
        The C batch lookup runs ~5× the fromiter genexpr at 1M ids
        (and its identity memo rides the wire scanners' node-string
        dedup); the Python path is the exact fallback."""
        from .. import native
        codec = native.load()
        if codec is not None:
            if not isinstance(node_ids, list):
                node_ids = list(node_ids)
            return np.frombuffer(
                codec.ordinals(node_ids, self._omap), np.int32)
        omap = self._omap
        return np.fromiter((omap[n] for n in node_ids), np.int32,
                           count=len(node_ids))


# Exact host lane dtypes of the PACKED wire form, in field order.
# Anything else from a peer is a protocol violation (mirrors
# net._SPLIT_LANE_DTYPES: never trust np.dtype as a parser for
# untrusted dtype strings). The optional 6th lane ("sem", uint8
# semantics tags) rides only between peers that negotiated the
# "semantics" hello capability — a pre-semantics receiver's field
# check rejects 6-lane frames, which is exactly why senders withhold
# the lane (and the rows needing it) from un-negotiated sessions.
PACKED_LANE_DTYPES = ("int32", "int64", "int32", "int64", "uint8")
PACKED_SEM_DTYPE = "uint8"


class PackedDelta(NamedTuple):
    """Incremental columnar wire form: ONE row per modified slot.

    The dense binary form (`net.sync_dense_over_tcp`) always ships
    n_slots-wide lanes with a validity mask — O(store) bytes even for
    a 3-record delta. This form is the O(k) counterpart: host numpy
    lanes holding only the rows ``DenseCrdt.pack_since`` selected, so
    a steady-state gossip round costs bytes proportional to what
    actually changed (~25 B/row). ``node`` carries ordinals into the
    ``node_ids`` list that travels beside the delta; ``modified``
    stamps are local-only and never serialized (record.dart:28-31).

    ``sem`` (None on all-LWW deltas and from pre-semantics peers)
    carries each row's semantics tag (`crdt_tpu.semantics`): the
    receiver validates tags against its own per-slot column before
    merging, so two replicas can never silently join one slot under
    two different lattices."""

    slots: np.ndarray   # int32[k], unique (last-wins collapsed)
    lt: np.ndarray      # int64[k] packed logical times
    node: np.ndarray    # int32[k] ordinals into the wire node_ids
    val: np.ndarray     # int64[k] (0 where tombstoned)
    tomb: np.ndarray    # uint8[k] 0/1 tombstone flags
    sem: Optional[np.ndarray] = None  # uint8[k] semantics tags

    @property
    def k(self) -> int:
        return len(self.slots)

    @property
    def nbytes(self) -> int:
        return sum(lane.nbytes for lane in self if lane is not None)


def arena_of(lane: np.ndarray):
    """Walk a lane view's base chain to its owning allocation — the
    single uint8 arena for lanes `pack_into_arena` produced. Lets
    tests prove buffer identity across pack → frame (the zero-copy
    acceptance check): every lane of one delta roots at one arena,
    and `pack_rows`' memoryviews expose that same storage."""
    a = lane
    while getattr(a, "base", None) is not None:
        a = a.base
    return a


def pack_into_arena(idx: np.ndarray, lt: np.ndarray, node: np.ndarray,
                    val: np.ndarray, tomb: np.ndarray,
                    sem: Optional[np.ndarray] = None,
                    slot_ids: Optional[np.ndarray] = None
                    ) -> "PackedDelta":
    """Gather the rows selected by ``idx`` out of host store columns
    straight into ONE preallocated arena; the returned delta's lanes
    are aligned views into it, already in the exact wire dtypes
    (`PACKED_LANE_DTYPES`). `pack_rows` then frames those views as-is
    and `net.send_bytes_frame` vectors them to the socket — the bytes
    written by the gathers here are the bytes `sendmsg` ships, with
    zero intermediate ``bytes()``/``np.asarray`` copies in between.

    Column dtype contract (the host fetch of store lanes): ``lt``/
    ``val`` int64, ``node`` int32, ``tomb`` bool or (u)int8, ``sem``
    int8/uint8 — 1-byte lanes reinterpret via ``.view`` so even the
    bool→uint8 conversion is part of the gather, not an extra pass.

    Ownership: the arena belongs to the returned delta and is NEVER
    reused or resized — an evicted pack-cache entry may still be
    referenced by an in-flight send, so recycling arenas would
    corrupt frames already on the wire (docs/FASTPATH.md).

    ``slot_ids``: the columns are rows already selected (the collective
    join's compacted repack rows), ``idx`` picks among them and
    ``slot_ids[idx]`` are their slots; else ``idx`` are the slots."""
    specs = [("slots", np.dtype(np.int32)),
             ("lt", np.dtype(np.int64)),
             ("node", np.dtype(np.int32)),
             ("val", np.dtype(np.int64)),
             ("tomb", np.dtype(np.uint8))]
    if sem is not None:
        specs.append(("sem", np.dtype(np.uint8)))
    k = int(len(idx))
    offs = []
    total = 0
    for _, dt in specs:
        total = -(-total // 8) * 8      # 8-byte-align every lane
        offs.append(total)
        total += k * dt.itemsize
    arena = np.empty(total, np.uint8)
    views = {name: arena[off:off + k * dt.itemsize].view(dt)
             for (name, dt), off in zip(specs, offs)}
    views["slots"][:] = idx if slot_ids is None else slot_ids[idx]
    np.take(lt, idx, out=views["lt"])
    np.take(node, idx, out=views["node"])
    np.take(val, idx, out=views["val"])
    np.take(tomb if tomb.dtype == np.uint8 else tomb.view(np.uint8),
            idx, out=views["tomb"])
    if sem is not None:
        np.take(sem if sem.dtype == np.uint8 else sem.view(np.uint8),
                idx, out=views["sem"])
    return PackedDelta(**views)


def pack_rows(delta: "PackedDelta") -> Tuple[dict, List[memoryview]]:
    """(meta, bufs) for a packed delta: lane descriptors plus host
    buffers in field order — the shape `net.send_bytes_frame` ships as
    one raw binary frame. The ``sem`` lane is appended only when
    present (capability-gated by the caller).

    Zero-copy: a lane already holding its exact wire dtype contiguously
    (every `pack_into_arena` lane) is framed as a flat memoryview over
    its OWN storage — no intermediate buffer. Foreign lanes (wrong
    dtype or layout, e.g. hand-built test deltas) are normalized with
    one counted copy, reported in
    ``crdt_tpu_pack_copy_bytes_total{stage="pack_rows"}`` — the
    counter a zero-copy regression trips."""
    lanes = list(delta[:5])
    fields = list(PackedDelta._fields[:5])
    dtypes = list(PACKED_LANE_DTYPES)
    if delta.sem is not None:
        lanes.append(delta.sem)
        fields.append("sem")
        dtypes.append(PACKED_SEM_DTYPE)
    arrs = []
    copied = 0
    for lane, dtype in zip(lanes, dtypes):
        want = np.dtype(dtype)
        if (isinstance(lane, np.ndarray) and lane.dtype == want
                and lane.ndim == 1 and lane.flags.c_contiguous):
            arrs.append(lane)
            continue
        # crdtlint: disable=pack-path-extra-copy -- normalizing a foreign lane (wrong dtype/layout) is the one legitimate pack-path copy; counted below so regressions still surface
        a = np.ascontiguousarray(np.asarray(lane), want)
        copied += a.nbytes
        arrs.append(a)
    if copied:
        from ..obs.registry import default_registry
        default_registry().counter(
            "crdt_tpu_pack_copy_bytes_total",
            "bytes copied between pack and frame (zero on the "
            "arena fast path)").inc(copied, stage="pack_rows")
    meta = {"form": "packed",
            "lanes": [[f, str(a.dtype), [len(a)]]
                      for f, a in zip(fields, arrs)]}
    return meta, [a.data.cast("B") for a in arrs]


def unpack_rows(meta: Any, blob: bytes) -> "PackedDelta":
    """Validate + reconstruct the packed delta a peer announced.
    Raises ValueError on any structural violation (wrong fields or
    dtypes, ragged lane lengths, frame size mismatch) BEFORE the
    replica is touched. ``k == 0`` is a legal empty delta. Accepts
    the 5-lane legacy form and the 6-lane form with the trailing
    ``sem`` tag lane."""
    if not isinstance(meta, dict) or meta.get("form") != "packed":
        raise ValueError("bad packed meta")
    lanes_meta = meta.get("lanes")
    base = list(PackedDelta._fields[:5])
    if not isinstance(lanes_meta, list) \
            or [l[0] for l in lanes_meta] not in (base, base + ["sem"]):
        raise ValueError("packed lane fields mismatch")
    want_dtypes = PACKED_LANE_DTYPES + (
        (PACKED_SEM_DTYPE,) if len(lanes_meta) == 6 else ())
    lanes = []
    off = 0
    k = None
    for (_, dt, shape), want in zip(lanes_meta, want_dtypes):
        if dt != want:
            raise ValueError(f"lane dtype {dt!r} != expected {want!r}")
        if not isinstance(shape, list) or len(shape) != 1 \
                or int(shape[0]) < 0:
            raise ValueError("bad packed lane shape")
        n = int(shape[0])
        if k is None:
            k = n
        elif n != k:
            raise ValueError("ragged packed lanes")
        a = np.frombuffer(blob, np.dtype(dt), count=n, offset=off)
        off += a.nbytes
        lanes.append(a)
    if off != len(blob):
        raise ValueError(f"packed frame size mismatch: lanes describe "
                         f"{off} bytes, frame holds {len(blob)}")
    return PackedDelta(*lanes)


def pack_hlcs(hlcs: Sequence[Hlc], table: NodeTable
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Scalar Hlcs -> (lt int64, node int32) lanes. Ids must be interned."""
    lt = np.array([h.logical_time for h in hlcs], dtype=np.int64)
    node = table.encode([h.node_id for h in hlcs])
    return lt, node


def unpack_hlc(lt: int, node_ord: int, table: NodeTable) -> Hlc:
    return Hlc.from_logical_time(int(lt), table.id_of(int(node_ord)))
