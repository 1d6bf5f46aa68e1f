"""DenseCrdt — fully device-resident LWW map over a dense integer key
space.

`TpuMapCrdt` is the drop-in general backend (arbitrary keys/values,
host dict for key↔slot); this model is the high-throughput shape: keys
ARE slot indices ``[0, n_slots)`` and values are int64 scalars (or
indices into an application-side table, SURVEY.md §7 hard part 4), so
every operation is a batched array op with zero per-record host work —
the shape the fan-in cell's billions-of-merges/sec come from.

Replication model (C9/C10 on arrays):

- ``export_delta(since)`` → ``(DenseChangeset, node_ids)`` — the
  outbound half of the anti-entropy round; ordinals in the changeset
  index into the accompanying ``node_ids`` list so peers with different
  interning histories stay compatible.
- ``merge(changeset, node_ids)`` — remaps peer ordinals into the local
  `NodeTable` (one small host gather), then runs the fused fan-in
  lattice join. Recv guards raise the reference's exception types
  (hlc.dart:164-189).
- ``sync_dense(a, b)`` — the push/pull round (test/map_crdt_test.dart:
  273-279 semantics, inclusive delta bound).

The columnar store round-trips through `crdt_tpu.checkpoint.save_dense`.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import crdt_json
from ..analysis import sanitizer as _sanitizer
from ..hlc import (ClockDriftException, DuplicateNodeException, Hlc,
                   wall_clock_millis)
from ..ops.dense import (DenseChangeset, DenseStore, FaninResult, _NEG,
                         delete_scatter, dense_delta_mask,
                         dense_max_logical_time,
                         empty_dense_store, fanin_step, fanin_stream,
                         merge_repack_step, pad_replica_rows, put_scatter,
                         sparse_fanin_step, store_to_changeset)
from ..ops.merge import recv_guards
from ..ops.pallas_merge import SplitStore
from ..ops.packing import NodeTable, PackedDelta, pack_into_arena
from ..record import (KeyDecoder, KeyEncoder, Record, ValueDecoder,
                      ValueEncoder)
from ..utils.stats import MergeStats, merge_annotation
from ..watch import ChangeHub, ChangeStream


class PipelinedGuardError(Exception):
    """A clock guard tripped inside a ``DenseCrdt.pipelined()`` window.

    Pipelined merges trade first-offender diagnostics for zero
    per-merge host synchronization: guard flags accumulate on device
    and are checked once at the window's end, so all this error can
    say is WHICH guard class fired. Re-run the same batches
    unpipelined for the exact sequential diagnosis (the store already
    holds the merged state — merge is idempotent, a re-run is safe).
    """


class _PipeState:
    """Device-resident clock state threaded across a pipelined window."""

    __slots__ = ("canonical", "any_bad", "overflow", "drift",
                 "val_overflow", "first_flag_idx", "merges",
                 "exact", "ex_have", "ex_dup", "ex_lt", "ex_caf",
                 "ex_wall")

    def __init__(self, canonical_lt: int, exact: bool = False):
        self.canonical = jnp.int64(canonical_lt)
        self.any_bad = jnp.asarray(False)
        self.overflow = jnp.asarray(False)
        self.drift = jnp.asarray(False)
        self.val_overflow = jnp.asarray(False)
        # Index (0-based, in window order) of the first merge that set
        # ANY flag — the flush names it so "re-run unpipelined" can
        # start at the right batch instead of replaying the window.
        self.first_flag_idx = jnp.int32(-1)
        self.merges = 0
        # exact mode: the first offender's own fields, accumulated on
        # device in sequential visit order (recv_guards per merge,
        # seeded with the threaded canonical — identical flags to the
        # unpipelined path, no supersets). ex_wall is the OFFENDING
        # merge's wall read, captured alongside ex_lt so exception
        # payloads can't pair one merge's record with another's wall.
        self.exact = exact
        self.ex_have = jnp.asarray(False)
        self.ex_dup = jnp.asarray(False)
        self.ex_lt = jnp.int64(0)       # offending record's logicalTime
        self.ex_caf = jnp.int64(0)      # canonical just before it
        self.ex_wall = jnp.int64(0)     # that merge's wall read

    def note(self, flags, idx: Optional[int] = None) -> None:
        """Attribute freshly-raised flags to window slot ``idx``
        (default: the current merge counter)."""
        i = self.merges if idx is None else idx
        newly = ((self.first_flag_idx < 0) & flags).astype(jnp.bool_)
        self.first_flag_idx = jnp.where(newly, jnp.int32(i),
                                        self.first_flag_idx)


@jax.jit
def _pipe_exact_guards(lt, node, valid, canonical_lt, local_node, wall):
    """One exact recv-guard pass for a pipelined merge (the r-major
    running-cummax semantics of `ops.merge.recv_guards`, seeded with
    the THREADED device canonical — flag-identical to the unpipelined
    path) plus the offender's logicalTime, fetched in-jit."""
    any_b, first_bad, first_is_dup, caf = recv_guards(
        lt, node, valid, canonical_lt, local_node, wall)
    return any_b, lt.reshape(-1)[first_bad], first_is_dup, caf


class DenseCrdt:
    """LWW-map CRDT over slots ``[0, n_slots)`` with int64 values."""

    def __init__(self, node_id: Any, n_slots: int,
                 wall_clock: Optional[Callable[[], int]] = None,
                 store: Optional[DenseStore] = None,
                 node_ids: Optional[Sequence[Any]] = None,
                 executor: str = "auto", value_width: int = 64):
        if executor not in ("auto", "xla", "pallas", "pallas-interpret"):
            raise ValueError(f"unknown executor {executor!r}")
        if value_width not in (64, 32):
            raise ValueError(f"value_width must be 64 or 32, got "
                             f"{value_width}")
        # value_width=32 — the value-ref mode: values are int32-range
        # scalars or indices into an application-side payload table
        # (SURVEY.md §7 hard part 4). The Mosaic executor then carries
        # ONE int32 val lane (15 B/merge instead of 19; ~1.27× the
        # distinct-row throughput) and sign-extends into the int64
        # storage lane in-kernel. Out-of-range values are rejected:
        # host-side writes immediately, device changesets via a lazily
        # checked overflow flag (no extra sync).
        self._value_width = value_width
        if executor in ("pallas", "pallas-interpret"):
            # Validate eagerly (mirroring grow()): deferring to the
            # first merge's kernel-level check would mis-run silently
            # under `python -O` when that check was an assert.
            from ..ops.pallas_merge import TILE
            if n_slots % TILE:
                raise ValueError(
                    f"executor={executor!r} needs n_slots % {TILE} == 0; "
                    f"got {n_slots}")
        self._executor = executor
        self._node_id = node_id
        self._wall_clock = wall_clock or wall_clock_millis
        # A seeded store's ordinal lanes index sorted(node_ids); build
        # that exact table FIRST, then intern our own id — re-encoding
        # the lanes if the new id sorts into the middle (a resume under
        # a fresh node id must not shift attribution).
        self._table = NodeTable(node_ids or [])
        # A caller-supplied store counts as escaped: the caller may
        # still hold it, so write scatters must not donate its buffers.
        # Planes are never handed out, so it is False whenever the
        # store is held as planes: the split into planes clears it.
        self._store_escaped = store is not None
        # The store is held in ONE form at a time: `_planes` (the
        # resident `SplitStore` planes the served flush and point read
        # use, where `_resident_for` holds) or `_store_lanes`
        # (`DenseStore` lanes, which cold paths read); the other is
        # None. See the `_store` getter and `_served_planes`.
        self._planes: Optional[SplitStore] = None
        self._store_lanes: Optional[DenseStore] = None
        # pack_since cache (watermark key -> packed delta); must exist
        # before the first store assignment — the _store setter clears
        # it on every replacement.
        self._pack_cache: "OrderedDict[Any, Any]" = OrderedDict()
        # digest_tree cache: one (key, DigestTree) pair, same
        # invalidation discipline as the pack cache (docs/ANTIENTROPY.md).
        self._digest_cache: Optional[Tuple[Any, Any]] = None
        # Tombstone-GC state (docs/STORAGE.md). The generation counts
        # store replacements; gc_purge/compact advance it WITHOUT
        # advancing the canonical clock, so cache keys carry it — a
        # purely clock-keyed cache would alias across a purge. The
        # floor is the armed resurrection fence (merge paths drop
        # sub-floor rows targeting empty slots); the last-floor latch
        # makes an unadvanced watermark cost zero dispatches.
        self._store_gen = 0
        self._gc_floor_lt = 0
        self._last_gc_floor_lt = 0
        self._gc_purged: Optional[Tuple[np.ndarray, int]] = None
        # Device bool[n_slots]: slots epoch GC physically purged.
        # The resurrection fence drops sub-floor inbound rows ONLY on
        # these slots — an empty slot that was never purged has
        # nothing to resurrect, and legitimately receives old rows
        # for the first time (a migration stream re-homing an arc, a
        # peer's initial full sync). Retired by compact (the remap
        # invalidates slot identity).
        self._gc_fence_dev = None
        if store is not None and store.n_slots != n_slots:
            # must survive `python -O`
            raise ValueError(
                f"store holds {store.n_slots} slots but "
                f"n_slots={n_slots}")
        self._store = store if store is not None else self._empty_store(
            n_slots)
        # A given store moves to the planes now, and is not kept.
        self._served_planes()
        if node_id not in self._table:
            self._intern_ids([node_id])
        self.stats = MergeStats().register(backend="DenseCrdt",
                                           node=str(node_id))
        self._hub = ChangeHub()
        self._pipe: Optional[_PipeState] = None
        # Active ingest() write combiner, or None (models/ingest.py).
        self._ingest = None
        # Seconds point reads (`get`) have waited for device work
        # already queued on the store: a running total, read as a delta
        # (the serving tier's `store_wait` read phase).
        self.store_wait_seconds = 0.0
        self._pending_val_overflow = None
        # Per-slot semantics tags (`crdt_tpu.semantics`, docs/TYPES.md):
        # None == every slot is LWW (tag 0), the seed behavior. The
        # version counter keys outbound pack-cache entries, so a
        # semantics migration invalidates cached packs even when the
        # store lanes (and thus the canonical clock) are unchanged.
        self._sem: Optional[np.ndarray] = None
        self._sem_dev = None
        self._sem_version = 0
        self.refresh_canonical_time()

    # --- clock (crdt.dart:8-33,114-121) ---

    @property
    def node_id(self) -> Any:
        return self._node_id

    @property
    def n_slots(self) -> int:
        if self._planes is not None:
            return self._planes.hi.size
        return self._store_lanes.n_slots

    @property
    def canonical_time(self) -> Hlc:
        return self._canonical_time

    @property
    def _store(self) -> DenseStore:
        """The store as `DenseStore` lanes, the form every cold path
        (pack, digest, GC, compaction, checkpoint, grow, the XLA and
        sparse merges) reads; the Mosaic merges take either form.
        A store held as resident planes moves to lanes first: one
        O(store) join, after which the planes are dropped, so the
        store is never held twice. The next flush or point read moves
        it back (`_served_planes`)."""
        if self._planes is not None:
            from ..ops.pallas_scatter import planes_to_store
            self._store_lanes = planes_to_store(self._planes)
            self._planes = None
        return self._store_lanes

    @_store.setter
    def _store(self, store) -> None:
        # The ONE choke point every mutation path shares (puts,
        # deletes, merges, grow, intern remaps): any store replacement
        # invalidates cached outbound packs, so `pack_since` can trust
        # a cache hit without re-deriving what changed. The store is
        # held in the form it comes in: a commit's `SplitStore` as
        # planes, a `DenseStore` as lanes.
        if isinstance(store, SplitStore):
            self._planes, self._store_lanes = store, None
        else:
            self._planes, self._store_lanes = None, store
        self._store_gen = self.__dict__.get("_store_gen", 0) + 1
        cache = self.__dict__.get("_pack_cache")
        if cache:
            cache.clear()
        if self.__dict__.get("_digest_cache") is not None:
            self._digest_cache = None

    @property
    def store(self) -> DenseStore:
        """Live store lanes. Reading this marks the snapshot as
        escaped, which disables buffer donation on subsequent
        `put_batch`/`delete_batch` calls until the store is next
        replaced — a snapshot you hold stays readable. Resident planes
        are never handed out: the next flush or point read splits the
        store into planes of their own, so a held snapshot never stops
        a commit donating the planes."""
        self.drain_ingest()
        self._store_escaped = True
        return self._store

    @property
    def _held(self):
        """The store as it is held, planes or lanes (no device work)."""
        return self._planes if self._planes is not None \
            else self._store_lanes

    def _served_planes(self) -> Optional[SplitStore]:
        """The store as resident planes, for a served flush or point
        read, or None where the store is not resident
        (`_resident_for`). A store a cold path left as lanes moves
        back first: one O(store) split, after which the lanes are
        dropped (a caller still holding them as `store` keeps its
        snapshot)."""
        if self._planes is None and self._resident_for(self.n_slots):
            from ..ops.pallas_scatter import store_to_planes
            self._planes = store_to_planes(self._store_lanes)
            self._store_lanes = None
            self._store_escaped = False
        return self._planes

    def wait_store(self) -> None:
        """Block until device work already queued on the store's lanes
        (earlier commits and merges) has finished. Unlike `store`, it
        neither drains the ingest window nor marks the store escaped,
        so later commits still donate the lanes."""
        jax.block_until_ready(self._held)

    @property
    def store_generation(self) -> int:
        """Monotonic count of store replacements. Every mutation lands
        through the ``_store`` setter and bumps it — including
        `gc_purge`/`compact`, which do NOT advance the canonical clock,
        so pack/digest cache keys fold this in (docs/STORAGE.md)."""
        return self._store_gen

    @property
    def gc_floor(self) -> int:
        """The armed resurrection fence: the highest purge floor (a
        packed logical time) any `gc_purge` ran at, or 0. Merge paths
        drop inbound rows below it that target unoccupied slots."""
        return self._gc_floor_lt

    def refresh_canonical_time(self) -> None:
        self.drain_ingest()
        if self._planes is not None:
            from ..ops.pallas_scatter import planes_max_logical_time
            lt = planes_max_logical_time(self._planes)
        else:
            lt = dense_max_logical_time(self._store_lanes)
        self._canonical_time = Hlc.from_logical_time(int(lt),
                                                     self._node_id)

    def _canonical_lt(self) -> jax.Array:
        """The canonical logicalTime as a device scalar — the live
        pipeline clock inside a ``pipelined()`` window, the host
        ``Hlc`` otherwise."""
        if self._pipe is not None:
            return self._pipe.canonical
        return jnp.int64(self._canonical_time.logical_time)

    @contextmanager
    def pipelined(self, exact_guards: bool = False):
        """Zero-host-sync merge window: inside it, ``merge`` /
        ``merge_many`` thread the canonical clock as a DEVICE scalar
        (the final send bump runs on device, `ops.merge.send_step`)
        and accumulate guard flags instead of fetching them — no
        device→host round trip per merge, the dominant per-call cost
        of a small merge. On exit, ONE readback materializes the
        clock and raises `PipelinedGuardError` if any recv/send guard
        fired during the window (coarse by design — the docstring
        there explains the trade).

        Semantic differences from unpipelined merges, stated plainly:

        - **Merges land optimistically.** An unpipelined merge with a
          real guard violation refuses the changeset (store
          untouched); a pipelined window has already applied it by
          the time the flush reports the flag. The lattice join is
          monotone either way, but clock-policy-violating records are
          IN the store when the error raises.
        - **Flags may be spurious.** The Mosaic/sharded executors'
          guard flags are documented supersets (a record the exact
          sequential order shields can still flag); unpipelined
          merges clear those by exact host recomputation, which needs
          the changesets — gone by flush time. A
          `PipelinedGuardError` therefore means "re-run unpipelined
          to find out": a clean re-run (merge is idempotent — the
          state is already merged) proves the flag spurious.
        - Wall-read counts match unpipelined merges, but the reads
          feed device ops; exception payloads are coarse.
        - **An active watch subscriber re-introduces a per-merge
          readback.** Change events are host-side by design (the win
          mask and winner lanes must be fetched to emit), so a window
          with live subscribers runs at unpipelined latency — the
          events themselves stay correct.

        Store lanes and the canonical clock are bit-identical to the
        same merges issued unpipelined (differentially tested).
        Local writes (`put_batch` etc.) are refused inside the
        window — they need the host clock.

        ``exact_guards=True`` trades one extra device pass per merge
        (the r-major running-cummax `recv_guards`, seeded with the
        threaded canonical — flag-identical to the unpipelined path)
        for EXACT diagnostics: no spurious flags, and the flush raises
        the reference's own typed exceptions
        (`DuplicateNodeException`/`ClockDriftException`) with the
        unpipelined payloads, naming the offending merge. The window
        contract is unchanged in one respect: merges have already
        LANDED when the flush raises (optimistic application)."""
        if self._pipe is not None:
            raise RuntimeError("pipelined() windows do not nest")
        # A pipelined window threads the canonical as a device scalar
        # seeded HERE; staged ingest rows would otherwise commit with
        # stamps the window never sees — barrier first.
        self.drain_ingest()
        import sys as _sys
        self._pipe = _PipeState(self._canonical_time.logical_time,
                                exact=exact_guards)
        try:
            yield self
        finally:
            pipe, self._pipe = self._pipe, None
            (lt, any_bad, overflow, drift, val_ovf, first_idx,
             ex_have, ex_dup, ex_lt, ex_caf, ex_wall) = jax.device_get(
                (pipe.canonical, pipe.any_bad, pipe.overflow,
                 pipe.drift, pipe.val_overflow, pipe.first_flag_idx,
                 pipe.ex_have, pipe.ex_dup, pipe.ex_lt, pipe.ex_caf,
                 pipe.ex_wall))
            self._canonical_time = Hlc.from_logical_time(
                int(lt), self._node_id)
            # Never shadow an in-flight exception from the window
            # body — the guard report matters less than the error
            # that actually interrupted the caller. (A bare `return`
            # here would SWALLOW it: finally-block semantics.)
            in_flight = _sys.exc_info()[0] is not None
            def _coarse_report(include_recv: bool) -> None:
                kinds = [k for k, f in (
                    ("recv-guard (duplicate-node or drift)",
                     any_bad and include_recv),
                    ("recv-guard (exact: "
                     + ("duplicate-node" if bool(ex_dup) else "drift")
                     + ")", pipe.exact and ex_have),
                    ("send counter overflow", overflow),
                    ("send drift", drift),
                    ("value-ref overflow (records with values past "
                     "int32 were SKIPPED, not merged; re-sync from "
                     "the peer with a value_width=64 replica)",
                     val_ovf)) if bool(f)]
                raise PipelinedGuardError(
                    f"guards tripped in pipelined window: "
                    f"{', '.join(kinds)}; first flagged at merge "
                    f"#{int(first_idx)} of {pipe.merges} (0-based, "
                    "window order)"
                    + ("" if pipe.exact else
                       "; possibly spurious (superset flags) — re-run "
                       "from that batch unpipelined for the exact "
                       "diagnosis, or open the window with "
                       "exact_guards=True"))

            if not in_flight:
                if not pipe.exact:
                    if (bool(any_bad) or bool(overflow) or bool(drift)
                            or bool(val_ovf)):
                        _coarse_report(include_recv=True)
                else:
                    # Exact-mode priority mirrors the unpipelined
                    # in-merge ordering: a value-overflow rejects
                    # before guard handling (its "records were
                    # SKIPPED" report must never be eaten by a typed
                    # raise); the recv guard preempts the send bump
                    # (send flags on an offending merge are a
                    # consequence of optimistic application, not the
                    # diagnosis).
                    if bool(val_ovf):
                        _coarse_report(include_recv=False)
                    if bool(ex_have):
                        # The unpipelined exception types and payloads
                        # (the merges are already in the store —
                        # window contract); ex_wall is the offending
                        # merge's own wall read.
                        if bool(ex_dup):
                            raise DuplicateNodeException(
                                str(self._node_id))
                        raise ClockDriftException(int(ex_lt) >> 16,
                                                  int(ex_wall))
                    if bool(overflow) or bool(drift):
                        _coarse_report(include_recv=False)

    # --- ingest fast lane (models/ingest.py, docs/INGEST.md) ---

    @contextmanager
    def ingest(self, auto_flush_rows: int = 1 << 16):
        """Write-combining window: inside it, ``put_batch`` /
        ``delete_batch`` (and everything routed through them —
        `KeyedDenseCrdt.put`, ``clear``) stage into host-side columnar
        buffers instead of dispatching a scatter per call. Staged rows
        commit as ONE fused device program (`ops.dense.ingest_scatter`)
        stamped by ONE vectorized `Hlc.send_batch` — each staged call
        keeps its own strictly-later HLC, so per-record LWW order is
        exactly the unbatched outcome. Commits are non-blocking
        (double-buffered: the host stages the next backlog while the
        previous flush executes on device).

        Flush triggers: the backlog reaching ``auto_flush_rows``; any
        merge/pack/serialization/snapshot barrier (`drain_ingest`);
        an explicit ``wc.flush()``; window exit.

        Visibility: point reads (``get`` / ``contains_slot`` /
        ``is_deleted``) and ``count_modified_since`` answer from the
        staging overlay — read-your-writes without a flush. Every
        other surface drains first, so nothing outside the window can
        observe a store missing staged rows. Change events fire at
        COMMIT with the winning post-dedup value per slot.

        Semantic differences from unbatched writes, stated plainly:
        staged calls share one flush-time wall read (one `send_batch`
        counter run) instead of one wall read per call, so injected
        clocks tick differently — which is why the combiner is opt-in
        rather than always-on. Refused inside ``pipelined()`` windows
        (local writes need the host clock there too); opening a
        pipelined window inside an ingest window drains first.

        Yields the `WriteCombiner` (exposes ``pending_rows``,
        ``flush()``, ``flushes``/``rows_committed`` counters)."""
        self._refuse_in_pipeline("ingest")
        if self._ingest is not None:
            raise RuntimeError("ingest() windows do not nest")
        from .ingest import WriteCombiner
        import sys as _sys
        wc = WriteCombiner(self, auto_flush_rows=auto_flush_rows)
        self._ingest = wc
        try:
            yield wc
        finally:
            try:
                wc.flush("exit")
            except Exception:
                # Never shadow the exception that interrupted the
                # window body (same contract as pipelined()); with no
                # in-flight error the flush failure IS the error.
                if _sys.exc_info()[0] is None:
                    raise
            finally:
                self._ingest = None

    def drain_ingest(self) -> bool:
        """Commit any staged ingest-window writes NOW. No-op outside a
        window (returns False). Every merge / pack / serialization /
        checkpoint / bulk-read surface calls this first — the barrier
        that keeps staged rows invisible only to the point reads the
        overlay answers."""
        ing = self._ingest
        if ing is None:
            return False
        return ing.flush("barrier")

    # --- local ops: one send per batch (crdt.dart:39-54) ---

    def _write_sharding(self):
        """NamedSharding pinned onto write-scatter outputs, or None.
        The sharded model returns its key-axis sharding so local
        writes land laid out — no post-write re-shard copy."""
        return None

    def _refuse_in_pipeline(self, op: str) -> None:
        if self._pipe is not None:
            raise RuntimeError(
                f"{op} needs the host clock; it cannot run inside a "
                "pipelined() merge window — exit the window first")

    def _check_slots(self, slots: np.ndarray) -> None:
        # JAX scatter drops out-of-bounds indices silently; fail loudly
        # instead of losing writes.
        if slots.size and (slots.min() < 0 or slots.max() >= self.n_slots):
            raise IndexError(
                f"slot indices must be within [0, {self.n_slots}); got "
                f"range [{slots.min()}, {slots.max()}]")

    def _donate_writes(self) -> bool:
        """Donate old store buffers to write scatters only when (a) the
        backend honors donation (CPU ignores it with a warning) and
        (b) the current store snapshot has never been handed out via
        the public ``store`` property — a caller-held snapshot must
        stay readable, so an escaped store is never donated."""
        if self._store_escaped:
            return False
        try:
            return next(iter(self._held[0].devices())).platform != "cpu"
        except Exception:
            return False

    def put_batch(self, slots, values, tombs=None) -> None:
        """Write values at slot indices; the whole batch shares ONE
        freshly-sent HLC (putAll semantics, crdt.dart:46-54).
        ``tombs`` (bool per entry) tombstones those entries under the
        same batch stamp — the mixed putAll shape (delete = put None,
        crdt.dart:58) that `delete_batch` alone can't express without
        spending a second stamp."""
        self._refuse_in_pipeline("put_batch")
        slots = np.asarray(slots, np.int32)
        self._check_slots(slots)
        self._check_value_width(values)
        if self._ingest is not None:
            # Validation above ran eagerly (staging must fail at the
            # call site, like the unbatched path); the rows themselves
            # wait for the flush stamp + fused commit.
            self._ingest.stage(
                slots.astype(np.int64),
                np.ascontiguousarray(np.broadcast_to(
                    np.asarray(values, np.int64), slots.shape)),
                None if tombs is None else np.ascontiguousarray(
                    np.broadcast_to(np.asarray(tombs, bool),
                                    slots.shape)))
            return
        k = slots.shape[0]
        values = np.broadcast_to(np.asarray(values, np.int64), (k,))
        tombs_h = None if tombs is None else np.broadcast_to(
            np.asarray(tombs, bool), (k,))
        # Lanes padded to a power of two with n_slots sentinels the
        # scatter drops: batch sizes share O(log k) compiled programs.
        width = 1 << max(k - 1, 0).bit_length()
        slot_l = np.full(width, self.n_slots, np.int32)
        val_l = np.zeros(width, np.int64)
        tomb_l = np.zeros(width, bool)
        slot_l[:k] = slots
        val_l[:k] = values
        if tombs_h is not None:
            tomb_l[:k] = tombs_h
        self._canonical_time = Hlc.send(self._canonical_time,
                                        millis=self._wall_clock())
        t = jnp.int64(self._canonical_time.logical_time)
        me = jnp.int32(self._table.ordinal(self._node_id))
        # One fused jit (not 7 eager scatters); donate the old lanes on
        # backends that support it so an O(k) write never copies the
        # O(n_slots) store.
        self._store = put_scatter(
            self._store, jnp.asarray(slot_l), jnp.asarray(val_l),
            t, me, tombs=jnp.asarray(tomb_l),
            donate=self._donate_writes(), sharding=self._write_sharding())
        self._store_escaped = False
        self.stats.puts += 1
        self.stats.records_put += int(k)
        self._emit_put(slots, values, tombs_h)

    def delete_batch(self, slots) -> None:
        """Tombstone slots (delete = put None, crdt.dart:58)."""
        self._refuse_in_pipeline("delete_batch")
        slots = np.asarray(slots, np.int32)
        self._check_slots(slots)
        if self._ingest is not None:
            self._ingest.stage(slots.astype(np.int64),
                               np.zeros(slots.shape[0], np.int64),
                               np.ones(slots.shape[0], bool))
            return
        slots = jnp.asarray(slots)
        self._canonical_time = Hlc.send(self._canonical_time,
                                        millis=self._wall_clock())
        t = jnp.int64(self._canonical_time.logical_time)
        me = jnp.int32(self._table.ordinal(self._node_id))
        self._store = delete_scatter(self._store, slots, t, me,
                                     donate=self._donate_writes(),
                                     sharding=self._write_sharding())
        self._store_escaped = False
        self.stats.puts += 1
        self.stats.records_put += int(slots.shape[0])
        self._emit_delete(slots)

    # --- views (tombstones excluded, crdt.dart:16-29) ---

    @property
    def live_mask(self) -> jax.Array:
        self.drain_ingest()
        return self._store.occupied & ~self._store.tomb

    @property
    def values(self) -> jax.Array:
        """int64[n_slots]; only positions with ``live_mask`` are live.
        Hands out the live lane, so (like ``store``) it marks the
        snapshot escaped — later writes won't donate its buffer."""
        self.drain_ingest()
        self._store_escaped = True
        return self._store.val

    def _check_slot(self, slot: int) -> None:
        # JAX clamps out-of-range reads to the edge instead of raising,
        # which would answer confidently for the wrong slot.
        if not 0 <= slot < self.n_slots:
            raise IndexError(
                f"slot {slot} out of range [0, {self.n_slots})")

    def get(self, slot: int) -> Optional[int]:
        self._check_slot(slot)
        if self._ingest is not None:
            # Read-your-writes overlay: a staged row answers from host
            # memory — the later flush stamp beats anything the store
            # holds for the slot, so this IS the post-commit answer.
            staged, v = self._ingest.pending_value(slot)
            if staged:
                return v
        occ, _, _, val, _, _, tomb = self._slot_fields(slot)
        return val if occ and not tomb else None

    def _slot_fields(self, slot: int) -> Tuple[bool, int, int, int, int,
                                                int, bool]:
        """``(occupied, lt, node, val, mod_lt, mod_node, tomb)`` of one
        stored slot (no ingest overlay): one gathering dispatch, then
        one device→host transfer. Every point read goes through here,
        on resident planes where the store has them."""
        from ..ops.pallas_scatter import decode_slot, gather_slot
        self._served_planes()
        words = gather_slot(self._held, slot)
        # The read queues on the device behind its earlier work on
        # the store (commits, merges); that wait is counted apart from
        # the read's own fetch, after the dispatch so the device runs
        # the read straight after it.
        t = time.perf_counter()
        self.wait_store()
        self.store_wait_seconds += time.perf_counter() - t
        return decode_slot(jax.device_get(words))

    def get_slot_record(self, slot: int) -> Optional[Record]:
        """Single-slot `Record` fetch (getRecord semantics,
        crdt.dart:146) — ONE batched device→host transfer of the
        slot's fields, never a full-store readback (`record_map` is
        the bulk shape; a 1M-slot replica must answer a point read in
        O(1))."""
        self._check_slot(slot)
        # Records carry stamps, which staged rows only get at flush —
        # drain rather than synthesize an overlay answer.
        self.drain_ingest()
        occ, lt, node, val, mod_lt, mod_node, tomb = self._slot_fields(
            slot)
        if not occ:
            return None
        from ..hlc import MAX_COUNTER, SHIFT
        ids = self._table.ids()
        return Record(
            Hlc._raw(lt >> SHIFT, lt & MAX_COUNTER, ids[node]),
            None if tomb else val,
            Hlc._raw(mod_lt >> SHIFT, mod_lt & MAX_COUNTER,
                     ids[mod_node]))

    def contains_slot(self, slot: int) -> bool:
        """True if the slot holds a record, live OR tombstoned
        (containsKey semantics, crdt.dart:141)."""
        self._check_slot(slot)
        if self._ingest is not None \
                and self._ingest.pending_value(slot)[0]:
            return True
        return self._slot_fields(slot)[0]

    def is_deleted(self, slot: int) -> Optional[bool]:
        """None for never-written slots, else the tombstone flag
        (crdt.dart:61-64)."""
        self._check_slot(slot)
        if self._ingest is not None:
            staged, v = self._ingest.pending_value(slot)
            if staged:
                return v is None
        occ, *_, tomb = self._slot_fields(slot)
        return tomb if occ else None

    def clear(self, purge: bool = False) -> None:
        """Tombstone every LIVE slot with one batch HLC, or physically
        purge (crdt.dart:67-73: clear = putAll(None for live keys))."""
        if purge:
            return self.purge()
        slots = np.nonzero(np.asarray(self.live_mask))[0]
        if slots.size:            # empty putAll never touches the clock
            self.delete_batch(slots)

    def purge(self) -> None:
        """Physically drop all records (crdt.dart:168-169). The
        canonical clock and node table are untouched."""
        self.drain_ingest()
        self._store = self._empty_store(self.n_slots)

    # --- tombstone epoch GC + online compaction (docs/STORAGE.md) ---

    def gc_purge(self, stability: Hlc, *,
                 drift_slack_ms: Optional[int] = None) -> int:
        """Epoch tombstone GC: physically drop every tombstone whose
        delete stamp every peer's durable watermark has passed —
        ``stability`` MUST be a fleet stability watermark
        (`GossipNode.stability_hlc` / `ServeTier.stability_hlc`; the
        crdtlint ``purge-watermark-unfenced`` rule holds library call
        sites to that). One donated device dispatch masks the purged
        rows out of all lanes (`ops.dense.gc_purge`); an unadvanced
        watermark short-circuits BEFORE dispatch, so idle GC passes
        cost nothing (ledger-asserted in the tests).

        The purge floor is the watermark minus a clock-drift slack
        (``hlc.MAX_DRIFT`` unless overridden — single-node callers
        whose watermark IS their own head pass 0): with the slack, any
        row a peer legitimately holds undelivered sits ABOVE the
        floor, which is what makes the merge-side resurrection fence
        precise — inbound rows below the floor targeting a PURGED
        slot are provably-dominated replays and are dropped (slots
        never purged here are untouched: an old row arriving at one
        for the first time — a migration stream, an initial sync — is
        new information, not a replay). Returns the number of slots
        purged."""
        self._refuse_in_pipeline("gc_purge")
        self.drain_ingest()
        from ..hlc import MAX_DRIFT, SHIFT
        slack = MAX_DRIFT if drift_slack_ms is None else int(drift_slack_ms)
        if slack < 0:
            raise ValueError(f"drift_slack_ms must be >= 0, got {slack}")
        floor = int(stability.logical_time) - (slack << SHIFT)
        if floor <= 0 or floor <= self._last_gc_floor_lt:
            return 0  # watermark hasn't advanced: zero dispatches
        from ..obs.registry import default_registry
        from ..ops.dense import gc_purge as _gc_purge_op
        new_store, purged_count, purged_mask = _gc_purge_op(
            self._store, jnp.int64(floor),
            donate=self._donate_writes(),
            sharding=self._write_sharding())
        mask_h = None
        if self._sem is not None or _sanitizer.enabled():
            n_purged, mask_h = jax.device_get((purged_count, purged_mask))
            mask_h = np.asarray(mask_h)
        else:
            n_purged = jax.device_get(purged_count)
        n_purged = int(n_purged)
        self._store = self._postprocess_store(new_store)
        self._store_escaped = False
        self._last_gc_floor_lt = floor
        self._gc_floor_lt = max(self._gc_floor_lt, floor)
        # Accumulate the device fence mask the merge paths consult —
        # purged slots only, so the fence can never eat first-time
        # deliveries (migration, initial sync) to slots it never GC'd.
        if self._gc_fence_dev is None:
            self._gc_fence_dev = purged_mask
        else:
            self._gc_fence_dev = jnp.logical_or(
                self._gc_fence_dev, purged_mask)
        if n_purged and self._sem is not None:
            typed_purged = mask_h & (self._sem != 0)
            if typed_purged.any():
                # Purged slots revert to the LWW default — the typed
                # tag described the tombstoned record, which is gone.
                sem = self._sem.copy()
                sem[typed_purged] = 0
                self._sem = sem if sem.any() else None
                self._sem_dev = None
                self._sem_version += 1
        if _sanitizer.enabled():
            # Arm the post-purge resurrection check: every later merge
            # asserts no recorded slot re-occupies below the floor
            # (sanitizer.check_dense_no_resurrection). Compaction
            # remaps slots, so it retires the record.
            slots = np.nonzero(mask_h)[0]
            if self._gc_purged is not None:
                prev_slots, _ = self._gc_purged
                slots = np.union1d(prev_slots, slots)
            self._gc_purged = (slots, floor)
        default_registry().counter(
            "crdt_tpu_gc_purged_slots_total",
            "tombstoned slots physically reclaimed by epoch GC").inc(
                n_purged, node=str(self._node_id))
        default_registry().counter(
            "crdt_tpu_gc_passes_total",
            "gc_purge dispatches (watermark advanced)").inc(
                node=str(self._node_id))
        return n_purged

    def compact(self, ranges=None) -> np.ndarray:
        """Online store compaction: remap surviving rows to a dense
        prefix (per span — the default spans the whole store) and
        rebuild the digest-tree levels, all in ONE donated device
        dispatch (`ops.dense.compact_remap`). Returns the slot
        translation table ``int32[n_slots]`` — ``translation[old] =
        new`` for occupied rows, ``-1`` for empty slots — which the
        caller MUST apply to every external slot reference
        (`KeyedDenseCrdt.compact` rewrites its intern map; raw-slot
        callers compact only when they own the slot space,
        docs/STORAGE.md). ``ranges`` restricts compaction to half-open
        ``(lo, hi)`` spans; rows outside keep their slots, so routing
        arcs stay range-preserving. The digest cache is re-seeded from
        the in-program rebuild, so the next anti-entropy walk costs
        zero digest dispatches."""
        self._refuse_in_pipeline("compact")
        self.drain_ingest()
        spans = self._normalize_ranges(
            ((0, self.n_slots),) if ranges is None else ranges)
        k = max(1, len(spans))
        pad = 1
        while pad < k:
            pad *= 2
        los = np.zeros(pad, np.int64)
        his = np.zeros(pad, np.int64)
        for i, (lo, hi) in enumerate(spans):
            los[i] = lo
            his[i] = hi
        from ..ops.dense import compact_remap
        from ..ops.digest import build_digest_tree
        sem_dev = self._sem_device() if self._sem is not None else None
        out = compact_remap(self._store, jnp.asarray(los),
                            jnp.asarray(his), sem_dev,
                            leaf_width=self.DIGEST_LEAF_WIDTH,
                            donate=self._donate_writes(),
                            sharding=self._write_sharding())
        if sem_dev is not None:
            new_store, new_sem, translation, _live, levels = out
        else:
            new_store, translation, _live, levels = out
            new_sem = None
        translation = np.asarray(jax.device_get(translation))
        self._store = self._postprocess_store(new_store)
        self._store_escaped = False
        if new_sem is not None:
            sem_h = np.asarray(jax.device_get(new_sem)).astype(np.int8)
            self._sem = sem_h if sem_h.any() else None
            self._sem_dev = None
            self._sem_version += 1
        # Recorded purge slots predate the remap; retire the record
        # and the device fence mask rather than translate them
        # (purged slots are unoccupied, so their translations are -1
        # anyway, and post-compact slot identity belongs to the
        # single remap owner — docs/STORAGE.md).
        self._gc_purged = None
        self._gc_fence_dev = None
        # Seed AFTER the store swap (the setter cleared the cache) and
        # the sem version bump, under the exact key the next
        # `digest_tree` lookup builds.
        tree = build_digest_tree(self.n_slots, self.DIGEST_LEAF_WIDTH,
                                 levels)
        self._digest_cache = (self._digest_key(), tree)
        from ..obs.registry import default_registry
        default_registry().counter(
            "crdt_tpu_compact_passes_total",
            "compact_remap dispatches").inc(node=str(self._node_id))
        return translation

    def grow(self, n_slots: int) -> None:
        """Grow the slot capacity to ``n_slots`` (records keep their
        slots; new slots start empty). The dense analogue of the
        reference map's unbounded growth (map_crdt.dart:10) — capacity
        is a layout choice, not a data bound. Shrinking would drop
        records; it is refused.

        Peers at the old capacity keep syncing with this replica
        (their narrower changesets are padded on ingest); merging THIS
        replica's wider changesets into an ungrown peer raises there
        until the peer grows too. With ``executor="auto"`` the Mosaic
        kernel path engages/disengages with tile alignment
        (`crdt_tpu.ops.TILE`); a forced ``executor="pallas"`` refuses
        an unaligned growth outright."""
        if n_slots < self.n_slots:
            raise ValueError(
                f"cannot shrink {self.n_slots} -> {n_slots} slots "
                "(records would be dropped); build a new replica and "
                "merge instead")
        if self._executor in ("pallas", "pallas-interpret"):
            from ..ops.pallas_merge import TILE
            if n_slots % TILE:
                raise ValueError(
                    f"executor={self._executor!r} needs n_slots % "
                    f"{TILE} == 0; got {n_slots}")
        if n_slots == self.n_slots:
            return
        self.drain_ingest()
        if self._sem is not None:
            # New slots start as LWW (tag 0) — the untyped default.
            self._sem = np.concatenate(
                [self._sem, np.zeros(n_slots - self.n_slots, np.int8)])
            self._sem_dev = None
        if self._gc_fence_dev is not None:
            # New slots were never purged — the fence must not cover
            # them (first-time deliveries land there).
            self._gc_fence_dev = jnp.concatenate(
                [self._gc_fence_dev,
                 jnp.zeros(n_slots - self.n_slots, jnp.bool_)])
        pad = empty_dense_store(n_slots - self.n_slots)
        self._store = DenseStore(*(
            jnp.concatenate([lane, pad_lane])
            for lane, pad_lane in zip(self._store, pad)))

    def __len__(self) -> int:
        return int(jnp.sum(self.live_mask))

    # --- per-slot semantics (crdt_tpu.semantics, docs/TYPES.md) ---

    @property
    def _has_typed(self) -> bool:
        """Any slot carrying a non-LWW tag? (`_sem` collapses back to
        None when a migration returns every slot to LWW, so this is a
        plain None check — the hot paths branch on it.)"""
        return self._sem is not None

    def _sem_host(self) -> np.ndarray:
        """The per-slot tag column as host int8 (all zeros when the
        replica is untyped). Do not mutate — go through
        `set_semantics`, which versions the column."""
        if self._sem is None:
            return np.zeros(self.n_slots, np.int8)
        return self._sem

    def _sem_device(self) -> jax.Array:
        """Device mirror of the tag column, rebuilt lazily after each
        migration/grow (the typed kernels take it as a plain operand,
        so jit caches stay warm across migrations)."""
        if self._sem_dev is None:
            self._sem_dev = jnp.asarray(self._sem_host())
        return self._sem_dev

    def set_semantics(self, slots, semantics) -> None:
        """Assign a registered semantics (`crdt_tpu.semantics`) to
        slots — by spec, name, or tag. Typed slots join through the
        per-tag sub-semilattice (`semantics.kernels`) instead of the
        LWW winner-takes-all rule; clock lanes, watermarks and guards
        are unchanged (the semidirect-product composition).

        This is replica-local CONFIGURATION, not replicated state:
        every peer must run the same migration before syncing typed
        slots (the packed wire form carries tags and rejects
        mismatches; docs/TYPES.md has the rollout recipe). Migrating a
        slot does not rewrite its lane — migrate before first write."""
        self._refuse_in_pipeline("set_semantics")
        self.drain_ingest()
        from ..semantics import SemanticsSpec, by_tag, get_semantics
        if isinstance(semantics, SemanticsSpec):
            spec = semantics
        elif isinstance(semantics, str):
            spec = get_semantics(semantics)
        else:
            spec = by_tag(int(semantics))
        if spec.tag != 0:
            if self._value_width != 64:
                raise ValueError(
                    "typed semantics pack state into the full int64 "
                    "value lane; this replica was built with "
                    "value_width=32")
            if self._executor in ("pallas", "pallas-interpret"):
                raise ValueError(
                    f"typed semantics run on the XLA path; "
                    f"executor={self._executor!r} forces the Mosaic "
                    "kernel (use executor='auto' or 'xla')")
        slots = np.asarray(slots, np.int32).reshape(-1)
        self._check_slots(slots)
        sem = (self._sem if self._sem is not None
               else np.zeros(self.n_slots, np.int8))
        sem[slots] = np.int8(spec.tag)
        self._sem = sem if sem.any() else None
        self._sem_dev = None
        self._sem_version += 1
        # Cached packs may hold rows under the old tags (or withhold
        # rows that are now LWW) — the version key alone would let an
        # in-flight entry at the same watermark survive. Digests mix
        # the tag lane, so the cached tree goes with them.
        self._pack_cache.clear()
        self._digest_cache = None

    def semantics_of(self, slot: int):
        """The registered `SemanticsSpec` governing a slot."""
        self._check_slot(slot)
        from ..semantics import by_tag
        return by_tag(0 if self._sem is None else int(self._sem[slot]))

    def _lane_value(self, slot: int) -> int:
        """Raw int64 lane at a slot, ingest-overlay aware — what a
        typed read-modify-write builds on. Tombstones do NOT zero
        typed lanes (deletion is the LWW action layered on top, and
        un-deleting reveals the converged state), so this reads the
        lane itself, not the live view."""
        if self._ingest is not None:
            staged, v = self._ingest.pending_value(slot)
            if staged:
                return 0 if v is None else int(v)
        occ, _, _, val, *_ = self._slot_fields(slot)
        return val if occ else 0

    def _typed_spec(self, slot: int, *names):
        self._check_slot(slot)
        spec = self.semantics_of(slot)
        if spec.name not in names:
            raise TypeError(
                f"slot {slot} holds {spec.name!r} semantics; this op "
                f"needs {' / '.join(names)} (set_semantics first)")
        return spec

    def counter_add(self, slot: int, delta: int) -> int:
        """Add ``delta`` to a counter slot and return the new decoded
        value. ``gcounter`` slots refuse negative deltas; ``pncounter``
        slots credit the pos/neg half. Works inside ``ingest()``
        windows (the staged overlay makes consecutive adds
        accumulate). The dense counter contract: ONE writer per slot —
        the merge join is per-lane max, so concurrent writers on one
        slot lose increments; give each replica its own slot and sum
        (docs/TYPES.md, examples/counter_example.py)."""
        spec = self._typed_spec(slot, "gcounter", "pncounter")
        delta = int(delta)
        lane = self._lane_value(slot)
        if spec.name == "gcounter":
            if delta < 0:
                raise ValueError(
                    "gcounter is grow-only; use pncounter semantics "
                    "for decrements")
            lane = lane + delta
            if lane >= 1 << 63:
                raise OverflowError("gcounter lane overflow")
        else:
            from ..semantics.kernels import _PN_HALF
            pos = (lane >> 32) & _PN_HALF
            neg = lane & _PN_HALF
            if delta >= 0:
                pos += delta
            else:
                neg -= delta
            if pos > _PN_HALF or neg > _PN_HALF:
                raise OverflowError(
                    "pncounter half overflow (31 bits per direction)")
            lane = (pos << 32) | neg
        self.put_batch([slot], [lane])
        return int(spec.decode(lane))

    def counter_value(self, slot: int) -> int:
        """Decoded counter value at a slot (pos − neg for pncounter)."""
        spec = self._typed_spec(slot, "gcounter", "pncounter")
        return int(spec.decode(self._lane_value(slot)))

    def orset_add(self, slot: int, element: int) -> frozenset:
        """Add an element (``[0, ORSET_UNIVERSE)``) to an OR-set slot:
        bump its causal length even→odd. Adding a present element is a
        no-op (no new write, no clock tick). Returns the updated
        membership."""
        spec = self._typed_spec(slot, "orset")
        from ..semantics import ORSET_MAX_LEN, ORSET_UNIVERSE
        e = int(element)
        if not 0 <= e < ORSET_UNIVERSE:
            raise ValueError(
                f"orset element out of universe [0, {ORSET_UNIVERSE}): "
                f"{e}")
        lane = self._lane_value(slot)
        n = (lane >> (4 * e)) & 0xF
        if n % 2 == 1:
            return spec.decode(lane)
        if n >= ORSET_MAX_LEN:
            raise OverflowError(
                f"orset causal length saturated at {ORSET_MAX_LEN} "
                f"for element {e} (no further add/remove cycles)")
        lane = (lane & ~(0xF << (4 * e))) | ((n + 1) << (4 * e))
        self.put_batch([slot], [lane])
        return spec.decode(lane)

    def orset_remove(self, slot: int, element: int) -> frozenset:
        """Remove an element: bump its causal length odd→even.
        Removing an absent element is a no-op. Returns the updated
        membership."""
        spec = self._typed_spec(slot, "orset")
        from ..semantics import ORSET_MAX_LEN, ORSET_UNIVERSE
        e = int(element)
        if not 0 <= e < ORSET_UNIVERSE:
            raise ValueError(
                f"orset element out of universe [0, {ORSET_UNIVERSE}): "
                f"{e}")
        lane = self._lane_value(slot)
        n = (lane >> (4 * e)) & 0xF
        if n % 2 == 0:
            return spec.decode(lane)
        if n >= ORSET_MAX_LEN:
            raise OverflowError(
                f"orset causal length saturated at {ORSET_MAX_LEN} "
                f"for element {e} (no further add/remove cycles)")
        lane = (lane & ~(0xF << (4 * e))) | ((n + 1) << (4 * e))
        self.put_batch([slot], [lane])
        return spec.decode(lane)

    def orset_members(self, slot: int) -> frozenset:
        """Current members of an OR-set slot (odd causal lengths)."""
        spec = self._typed_spec(slot, "orset")
        return spec.decode(self._lane_value(slot))

    def mvreg_put(self, slot: int, value: int) -> None:
        """Write a multi-value register: this write's fresh HLC is
        strictly newer than anything the replica has seen, so it
        replaces local values outright; CONCURRENT peer writes (equal
        lt under different nodes) union on merge up to the top
        ``MVREG_K``."""
        spec = self._typed_spec(slot, "mvreg")
        self.put_batch([slot], [spec.encode(value)])

    def mvreg_get(self, slot: int) -> Tuple[int, ...]:
        """Concurrent values at an mvreg slot, largest first — one
        element after any local write, possibly several after merging
        concurrent peers."""
        spec = self._typed_spec(slot, "mvreg")
        return spec.decode(self._lane_value(slot))

    # --- watch/reactivity (C13, crdt.dart:162-164) ---

    def watch(self, slot: Optional[int] = None) -> ChangeStream:
        """Per-slot or whole-store change stream. Events are
        ``(slot, value)`` with value ``None`` for deletes, emitted
        host-side after device writes land (reactivity never lives in
        the kernel — SURVEY.md §7 hard part 6)."""
        return self._hub.stream(slot)

    def _watch_decode(self, slot, value):
        """Decode one committed lane value for a watch event: typed
        slots (counter/orset/mvreg) must emit what their reads return
        — `spec.decode(lane)` — never the packed raw lane a subscriber
        cannot interpret. Untyped replicas pay a single None check."""
        if value is None or self._sem is None:
            return value
        tag = int(self._sem[slot])
        if tag == 0:
            return value
        from ..semantics import by_tag
        return by_tag(tag).decode(int(value))

    def _emit_put(self, slots, values, tombs=None) -> None:
        if not self._hub.active:
            return  # no subscribers: bulk path stays device-only
        # Host copies ONCE per batch — the arrays arrive as device
        # buffers here, and a per-lookup np.asarray would re-transfer
        # the whole lane to read one element.
        slot_arr = np.asarray(slots)
        val_arr = np.asarray(values)

        def pairs():
            sl = [int(x) for x in slot_arr]
            vals = [None if (tombs is not None and bool(tombs[i]))
                    else self._watch_decode(sl[i], int(val_arr[i]))
                    for i in range(len(slot_arr))]
            return sl, vals

        def get(k):
            if not isinstance(k, (int, np.integer)):
                return False, None
            hit = np.nonzero(slot_arr == k)[0]
            if hit.size == 0:
                return False, None
            i = int(hit[-1])
            deleted = tombs is not None and bool(tombs[i])
            return True, (None if deleted
                          else self._watch_decode(int(k),
                                                  int(val_arr[i])))

        # A raw slot array may repeat a slot; keyed streams must then
        # see every occurrence (add_batch's per-pair contract), so the
        # O(1) keyed shortcut only applies to duplicate-free batches.
        unique = len(np.unique(slot_arr)) == len(slot_arr)
        self._hub.add_batch(pairs, get if unique else None)

    def _emit_delete(self, slots) -> None:
        if not self._hub.active:
            return
        slot_arr = np.asarray(slots)
        unique = len(np.unique(slot_arr)) == len(slot_arr)
        self._hub.add_batch(
            lambda: ([int(s) for s in slot_arr],
                     [None] * len(slot_arr)),
            (lambda k: (isinstance(k, (int, np.integer))
                        and bool(np.any(slot_arr == k)), None))
            if unique else None)

    def _emit_merge_wins(self, win) -> None:
        """Winner change events from the fan-in's win mask, read
        against the store the merge just assigned — batched,
        post-dispatch (the device work is already queued); a subscriber
        costs one win-mask readback, never a per-record device loop."""
        if not self._hub.active:
            return
        store = self._store
        win, tomb, val = jax.device_get((win, store.tomb, store.val))
        widx = np.nonzero(win)[0]

        def pairs():
            return ([int(s) for s in widx],
                    [None if tomb[s]
                     else self._watch_decode(int(s), int(val[s]))
                     for s in widx])

        def get(k):
            if not (isinstance(k, (int, np.integer))
                    and 0 <= k < win.shape[0] and win[k]):
                return False, None
            return True, (None if tomb[k]
                          else self._watch_decode(int(k), int(val[k])))

        # crdtlint: disable=add-batch-unique-keys -- widx comes from np.nonzero(win): a slot mask cannot repeat a slot, so the batch is unique by construction
        self._hub.add_batch(pairs, get)

    # --- wire interop (C10/C11): every replica speaks the JSON wire
    # format (crdt_json.dart:8-37; example/crdt_example.dart:12-16), so
    # a dense replica can sync with MapCrdt/TpuMapCrdt or external
    # JSON peers, not just other dense stores. ---

    def _check_value_width(self, values) -> None:
        if self._value_width == 32:
            v = np.asarray(values, np.int64)
            if v.size and (v.min() < -(2 ** 31) or v.max() >= 2 ** 31):
                raise ValueError(
                    "value_width=32 replica got a value outside int32 "
                    "range; use value_width=64 or store a payload-"
                    "table index instead")

    def _check_int_values(self, record_map: Dict[int, Record]) -> None:
        """The payload lane is int64; any other type would be silently
        truncated and (sharing the peer's hlc) diverge forever — fail
        loudly, identically on every record ingest path."""
        for slot, rec in record_map.items():
            if rec.value is not None and (
                    isinstance(rec.value, bool)
                    or not isinstance(rec.value, (int, np.integer))):
                # bool is an int subclass but would be stored as 0/1
                # and re-exported as such under the peer's hlc — the
                # silent-divergence shape this check exists to stop.
                raise TypeError(
                    f"DenseCrdt values must be ints; slot {slot} got "
                    f"{type(rec.value).__name__}")

    def put_slot_records(self, record_map: Dict[int, Record]) -> None:
        """Raw record writes preserving each record's own ``hlc`` and
        ``modified`` stamps — the putRecords storage primitive
        (crdt.dart:151-155): records land verbatim, with NO LWW compare
        and NO canonical-clock involvement (put_record's contract).
        Values must be ints (or None tombstones). Bulk-import shape:
        restoring a record dump, seeding a replica, or backing the
        `Crdt` storage slots through `KeyedDenseCrdt`."""
        if not record_map:
            return
        # Verbatim stamps must not interleave with a pending flush's
        # send_batch stamps — barrier before the raw scatter.
        self.drain_ingest()
        k = len(record_map)
        slots = np.fromiter(record_map.keys(), np.int64, count=k)
        self._check_slots(slots)
        recs = list(record_map.values())
        self._check_int_values(record_map)
        self._check_value_width(
            [0 if r.value is None else int(r.value) for r in recs])
        self._intern_ids({r.hlc.node_id for r in recs}
                         | {r.modified.node_id for r in recs})
        ords = {nid: i for i, nid in enumerate(self._table.ids())}
        # Pad k to a power of two (invalid rows scatter to the
        # n_slots sentinel, mode="drop") so the jitted scatter compiles
        # O(log k) distinct shapes — same trick as merge_records.
        padded = 1 << max(k - 1, 1).bit_length()
        slot_arr = np.full((padded,), self.n_slots, np.int64)
        lt = np.zeros((padded,), np.int64)
        node = np.zeros((padded,), np.int32)
        val = np.zeros((padded,), np.int64)
        mod_lt = np.zeros((padded,), np.int64)
        mod_node = np.zeros((padded,), np.int32)
        tomb = np.zeros((padded,), bool)
        slot_arr[:k] = slots
        lt[:k] = [r.hlc.logical_time for r in recs]
        node[:k] = [ords[r.hlc.node_id] for r in recs]
        val[:k] = [0 if r.value is None else int(r.value) for r in recs]
        mod_lt[:k] = [r.modified.logical_time for r in recs]
        mod_node[:k] = [ords[r.modified.node_id] for r in recs]
        tomb[:k] = [r.is_deleted for r in recs]
        from ..ops.dense import record_scatter
        self._store = self._postprocess_store(record_scatter(
            self._store, jnp.asarray(slot_arr), jnp.asarray(lt),
            jnp.asarray(node), jnp.asarray(val), jnp.asarray(mod_lt),
            jnp.asarray(mod_node), jnp.asarray(tomb),
            donate=self._donate_writes(),
            sharding=self._write_sharding()))
        self._store_escaped = False
        self.stats.puts += 1
        self.stats.records_put += k
        if self._hub.active:
            for slot, rec in record_map.items():
                self._hub.add(int(slot),
                              None if rec.is_deleted
                              else self._watch_decode(int(slot),
                                                      int(rec.value)))

    def _delta_mask(self, modified_since: Optional[Hlc]) -> np.ndarray:
        if modified_since is None:
            mask = self._store.occupied
        else:
            mask = dense_delta_mask(
                self._store, jnp.int64(modified_since.logical_time))
        return mask

    def count_modified_since(self, modified_since: Optional[Hlc] = None
                             ) -> int:
        """Delta-backlog size for lag monitoring: occupied slots with
        ``mod_lt >= modified_since`` (tombstones included). One masked
        sum on device, one scalar fetch — never materializes records.

        Inside an ingest window, staged rows count too (their flush
        stamp is at-or-after the canonical head, so they are modified
        under any watermark bound) — lag monitors see the backlog
        without forcing a flush."""
        mask = self._delta_mask(modified_since)
        ing = self._ingest
        if ing is not None and ing.pending_rows:
            mask = mask.at[jnp.asarray(
                ing.pending_slot_array())].set(True)
        return int(jax.device_get(jnp.sum(mask)))

    def record_map(self, modified_since: Optional[Hlc] = None
                   ) -> Dict[int, Record]:
        """Slot→Record export (recordMap semantics, crdt.dart:140-169,
        inclusive ``modified_since`` bound) — the bridge between the
        columnar lanes and the record-dict/JSON world. One device→host
        transfer; decode is vectorized (numpy unpack + object-array
        node gather), with per-record work reduced to the raw
        ``Hlc``/``Record`` allocations."""
        self.drain_ingest()
        mask = self._delta_mask(modified_since)
        # One batched fetch (async prefetch per leaf) instead of seven
        # sequential device->host round trips.
        mask, lt, node, val, mod_lt, mod_node, tomb = jax.device_get(
            (mask, self._store.lt, self._store.node, self._store.val,
             self._store.mod_lt, self._store.mod_node, self._store.tomb))
        idx = np.nonzero(mask)[0]
        if idx.size == 0:
            return {}
        ids = np.array(self._table.ids(), object)
        from ..hlc import MAX_COUNTER, SHIFT
        cols = (idx.tolist(),
                (lt[idx] >> SHIFT).tolist(),
                (lt[idx] & MAX_COUNTER).tolist(),
                ids[node[idx]],
                val[idx].tolist(), tomb[idx].tolist(),
                (mod_lt[idx] >> SHIFT).tolist(),
                (mod_lt[idx] & MAX_COUNTER).tolist(),
                ids[mod_node[idx]])
        raw = Hlc._raw
        return {
            slot: Record(raw(ms, c, n), None if tb else v,
                         raw(mms, mc, mn))
            for slot, ms, c, n, v, tb, mms, mc, mn in zip(*cols)
        }

    def to_json(self, modified_since: Optional[Hlc] = None,
                key_encoder: Optional[KeyEncoder] = None,
                value_encoder: Optional[ValueEncoder] = None) -> str:
        """Wire JSON export (crdt.dart:124-135): slots stringify as int
        keys, matching the reference's int-key golden format.

        With default coders this streams straight from the lanes —
        numpy unpack, C-codec batch HLC formatting, direct string
        assembly (every piece is JSON-plain: int keys, int/null
        values) — byte-identical to the generic encoder but without
        materializing a Record dict (a 1M-slot export runs in
        seconds)."""
        self.drain_ingest()
        if key_encoder is None and value_encoder is None:
            fast = self._to_json_fast(modified_since)
            if fast is not None:
                return fast
        return crdt_json.encode(self.record_map(modified_since),
                                key_encoder=key_encoder,
                                value_encoder=value_encoder)

    def _to_json_fast(self, modified_since: Optional[Hlc]) -> Optional[str]:
        """Lane-direct wire export, or None to defer to the generic
        path (no native codec; an out-of-range year; a node id that is
        not UTF-8 encodable). Escape-needing node ids are handled by
        the C assembler's JSON escaping."""
        from .. import native
        codec = native.load()
        if codec is None:
            return None
        id_strs = [str(n) for n in self._table.ids()]
        mask = self._delta_mask(modified_since)
        # `modified` is local-only and never serialized
        # (record.dart:28-31) — the wire fetch skips those lanes.
        mask, lt, node, val, tomb = jax.device_get(
            (mask, self._store.lt, self._store.node, self._store.val,
             self._store.tomb))
        idx = np.nonzero(mask)[0]
        if idx.size == 0:
            return "{}"
        from ..hlc import MAX_COUNTER, SHIFT
        hlcs = codec.format_hlc_batch(
            (lt[idx] >> SHIFT).tolist(), (lt[idx] & MAX_COUNTER).tolist(),
            np.array(id_strs, object)[node[idx]].tolist())
        if None in hlcs:
            # deferred item: out-of-window year (generic path raises)
            # or a non-UTF-8 node id (generic path serializes it)
            return None
        # C one-pass assembly (int slot keys; escape-safe for any node
        # id). Values: int, or None for tombstones — all scalars, so
        # the dumps fallback never fires, but pass the real one anyway.
        values = [None if tb else v
                  for v, tb in zip(val[idx].tolist(), tomb[idx].tolist())]
        return codec.format_wire(idx.tolist(), hlcs, values,
                                 crdt_json.compact_dumps)

    def merge_records(self, record_map: Dict[int, Record]) -> None:
        """Fan-in a record dict (from a MapCrdt/TpuMapCrdt peer or a
        JSON decode). Values must be ints (or None tombstones) — the
        dense model's payload lane is int64.

        Clock absorption and recv guards run host-side, in the
        payload's own iteration order — the reference's visit order
        (crdt.dart:80-85) — through the shared vectorized fold
        (`utils.host_guards.recv_fold_columns`, the same one the other
        host backends use), so guard trips, their payloads, and the
        partially-advanced canonical on failure match ``MapCrdt.merge``
        exactly. A slot-ordered device-side check could disagree on
        which records the fast path shields (hlc.dart:85). After
        absorption the canonical clock is ≥ every remote lt, so the
        join itself needs no further guard work and is
        order-independent.

        Cost is O(k) in the delta size — host arrays, transfer, and
        the `sparse_fanin_step` gather/scatter are all k-wide (a
        10-record JSON sync into a 1M-slot replica must not
        materialize 1M-wide lanes). Equivalence with the full-width
        changeset join is property-tested
        (tests/test_dense_crdt.py::TestSparseWireDelta)."""
        self._refuse_in_pipeline("merge_records")  # host recv fold
        self.drain_ingest()
        if not record_map:
            self.merge_many([])
            return
        k = len(record_map)
        slots = np.fromiter(record_map.keys(), np.int64, count=k)
        recs = list(record_map.values())
        from .. import native
        codec = native.load()
        if codec is not None:
            lt_buf, nodes, values = codec.records_to_columns(recs, False)
            lt = np.frombuffer(lt_buf, np.int64)
        else:
            lt = np.fromiter((r.hlc.logical_time for r in recs),
                             np.int64, count=k)
            nodes = [r.hlc.node_id for r in recs]
            values = [r.value for r in recs]
        self._merge_columns(slots, lt, nodes, values)

    def merge_json(self, json_str: str,
                   key_decoder: Optional[KeyDecoder] = None,
                   value_decoder: Optional[ValueDecoder] = None) -> None:
        """Columnar wire JSON ingest (crdt.dart:100-109): C batch HLC
        parse → packed int64 lanes → shared recv fold →
        `sparse_fanin_step`, no per-record Record/Hlc objects (the
        same decode shape `TpuMapCrdt`/`SqliteCrdt` ingest through).
        Keys decode to int slots by default."""
        self._refuse_in_pipeline("merge_json")  # host recv fold
        self.drain_ingest()
        # Tick parity with the generic Crdt.merge_json: the decode-time
        # `modified` stamp consumes one wall read there
        # (Crdt._decode_wall_millis contract) — a merge immediately
        # re-stamps winners, so only the READ must happen here.
        self._wall_clock()
        if (key_decoder is None or key_decoder is int) \
                and value_decoder is None:
            from .. import native
            codec = native.load()
            scanned = (codec.parse_wire_dense(json_str)
                       if codec is not None else None)
            if scanned is not None:
                # Zero-Python-object lane: the C scan produced raw
                # columnar buffers (no key strings, no value ints) —
                # validate ranges, map node ordinals, and join.
                sbuf, ltbuf, nibuf, uniq, vbuf, tbuf, vmin, vmax = scanned
                k = len(tbuf)
                if not k:
                    self.merge_many([])
                    return
                slots = np.frombuffer(sbuf, np.int32)
                lt = np.frombuffer(ltbuf, np.int64)
                ni = np.frombuffer(nibuf, np.int32)
                val = np.frombuffer(vbuf, np.int64)
                tomb = np.frombuffer(tbuf, np.uint8).astype(bool)
                keep = self._last_wins_keep(slots)
                if keep is not None:
                    # Duplicate literal wire keys: collapse last-wins
                    # (decode-dict parity) before anything counts or
                    # validates the dropped occurrences.
                    slots, lt, ni, val, tomb = (
                        slots[keep], lt[keep], ni[keep], val[keep],
                        tomb[keep])
                    k = len(slots)
                self.stats.merges += 1
                self.stats.add_seen_lazy(k)
                self._check_slots(slots)
                self._check_value_width(
                    np.array([vmin, vmax], np.int64)
                    if keep is None else val)
                self._intern_ids(uniq)
                node = self._table.encode(uniq)[ni]
                self._merge_validated(slots, lt, node, val, tomb)
                return
        keys, lt, nodes, values = crdt_json.decode_columns(
            json_str, key_decoder=key_decoder or int,
            value_decoder=value_decoder)
        if not keys:
            self.merge_many([])
            return
        self._merge_columns(np.asarray(keys, np.int64), lt, nodes,
                            values)

    @staticmethod
    def _last_wins_keep(slots: np.ndarray) -> Optional[np.ndarray]:
        """Indices keeping the LAST occurrence per duplicate slot (in
        payload order), or None when already unique. Distinct wire
        keys may decode to ONE slot ("5" and "05" under the int key
        decoder); the legacy decode-dict collapsed those last-wins
        BEFORE the merge ever saw them, and the scatter/wide joins
        require unique slots — XLA scatter with duplicate indices has
        backend-dependent winner order."""
        k = len(slots)
        # First occurrence in the reversed view = last in the payload.
        _, idx = np.unique(slots[::-1], return_index=True)
        if len(idx) == k:
            return None
        return np.sort(k - 1 - idx)

    def _merge_columns(self, slots: np.ndarray, lt: np.ndarray,
                       node_ids: List[Any], values: List[Any]) -> None:
        """The shared O(k) columnar merge core (`merge_records` /
        `merge_json`): ``lt`` is int64[k] packed logical times aligned
        with ``slots``/``node_ids``/``values``. Every validation runs
        BEFORE the first clock mutation (and before the absorption
        wall read — the legacy visit order under a counting clock), so
        a rejected payload leaves the replica untouched. Duplicate
        slots collapse last-wins first — dropped occurrences are never
        seen, validated, or counted, exactly like the decode dict."""
        keep = self._last_wins_keep(slots)
        if keep is not None:
            slots, lt = slots[keep], lt[keep]
            node_ids = [node_ids[i] for i in keep]
            values = [values[i] for i in keep]
        k = len(slots)
        self.stats.merges += 1
        # add_seen_lazy (host int here): `records_seen +=` would drain
        # any pending lazy device scalar with a blocking readback.
        self.stats.add_seen_lazy(k)
        self._check_slots(slots)
        # The payload lane is int64; any other type (incl. bool, an
        # int subclass that would store as 0/1) would silently diverge
        # under the peer's hlc — one O(k) offender scan, on this
        # non-C fallback path only (the C wire scan rejects upstream
        # by deferring, and record dicts are already Python-bound).
        from .. import native
        codec = native.load()
        if codec is not None:
            tomb = np.frombuffer(codec.none_mask(values), bool)
        else:
            tomb = np.fromiter((v is None for v in values), bool, count=k)
        bad = next((i for i, v in enumerate(values)
                    if v is not None
                    and (isinstance(v, bool)
                         or not isinstance(v, (int, np.integer)))), None)
        if bad is not None:
            raise TypeError(
                f"DenseCrdt values must be ints; slot {slots[bad]} got "
                f"{type(values[bad]).__name__}")
        val = np.fromiter((0 if v is None else v for v in values),
                          np.int64, count=k)
        self._check_value_width(val)
        self._intern_ids(set(node_ids))
        node = self._table.encode(node_ids)
        self._merge_validated(slots, lt, node, val, tomb)

    def _merge_validated(self, slots: np.ndarray, lt: np.ndarray,
                         node: np.ndarray, val: np.ndarray,
                         tomb: np.ndarray, sem_ok: bool = False,
                         repack_since_lt: Optional[int] = None
                         ) -> Optional[jax.Array]:
        """Columnar merge tail on fully validated int lanes: recv fold,
        store join, watch emission, final send bump. ``node`` already
        holds LOCAL ordinals; stats counters are the caller's job up to
        ``merges``/``records_seen`` (this adds adopted).

        ``sem_ok`` asserts the caller verified the payload's semantics
        tags against the local column (`merge_packed` with a ``sem``
        lane). Without it, rows landing on typed slots are WITHHELD —
        an LWW-framed wire (record dicts, JSON, pre-semantics packed
        frames) cannot prove it joins under the right lattice, and
        joining a counter lane by LWW would corrupt it. Withheld rows
        count in ``crdt_tpu_sync_semantics_downgrade_total``.

        ``repack_since_lt`` asks the join to ALSO emit the next pack's
        delta mask (``mod_lt >= since``) from the same fused program
        (`merge_and_repack`); returns that device mask when the sparse
        fused path ran, None otherwise (wide/typed/withheld-empty join,
        where the caller falls back to a separate `pack_since`)."""
        if not sem_ok and self._sem is not None:
            typed = self._sem[slots] != 0
            if typed.any():
                from ..obs.registry import default_registry
                default_registry().counter(
                    "crdt_tpu_sync_semantics_downgrade_total",
                    "typed rows withheld from LWW-only wire forms by "
                    "direction").inc(int(typed.sum()),
                                     direction="inbound",
                                     node=str(self._node_id))
                keep = ~typed
                slots, lt, node, val, tomb = (
                    slots[keep], lt[keep], node[keep], val[keep],
                    tomb[keep])
                if not len(slots):
                    # Same two clock ticks as an empty merge
                    # (absorption wall read + final send bump), so
                    # injected clocks stay in step with peers that
                    # shipped nothing.
                    self._wall_clock()
                    self._canonical_time = Hlc.send(
                        self._canonical_time,
                        millis=self._wall_clock())
                    return None
        floor = self._gc_floor_lt
        if floor and self._gc_fence_dev is not None and len(slots):
            # Resurrection fence (docs/STORAGE.md): a row below the GC
            # floor targeting a slot this replica PURGED is a replay
            # of purged state — the stability watermark proves every
            # peer delivered everything below the floor (drift slack
            # included), so nothing below it is legitimately still in
            # flight for a purged slot. Rows at or above the floor,
            # sub-floor rows for never-purged slots (first-time
            # deliveries: migration streams, initial syncs), and rows
            # the join would dominate anyway all pass through.
            fenced = np.asarray(jax.device_get(
                self._gc_fence_dev[np.asarray(slots)]))
            stale = (lt <= floor) & fenced
            if stale.any():
                from ..obs.registry import default_registry
                default_registry().counter(
                    "crdt_tpu_gc_fenced_rows_total",
                    "inbound rows dropped by the post-GC resurrection "
                    "fence").inc(int(stale.sum()),
                                 node=str(self._node_id))
                keep = ~stale
                slots, lt, node, val, tomb = (
                    slots[keep], lt[keep], node[keep], val[keep],
                    tomb[keep])
                if not len(slots):
                    # Same two ticks as the withheld-empty path above.
                    self._wall_clock()
                    self._canonical_time = Hlc.send(
                        self._canonical_time,
                        millis=self._wall_clock())
                    return None
        k = len(slots)
        my_ord = self._table.ordinal(self._node_id)
        wall = self._wall_clock()

        # Recv guards + clock absorption against the RUNNING canonical
        # (exclusive cummax — hlc.dart:85's fast path shields records
        # the clock already dominates), in payload visit order, shared
        # with the other host backends (utils/host_guards.py).
        from ..utils.host_guards import recv_fold_columns
        fold = recv_fold_columns(lt, node == my_ord,
                                 self._canonical_time.logical_time, wall)
        if fold.bad_index is not None:
            # Canonical partially advanced to just before the offender
            # (sequential-merge parity, crdt.dart:77-94 throw path);
            # store untouched.
            self._canonical_time = Hlc.from_logical_time(
                fold.canonical_at_fail, self._node_id)
            if fold.bad_is_dup:
                raise DuplicateNodeException(str(self._node_id))
            raise ClockDriftException(int(lt[fold.bad_index]) >> 16, wall)
        new_canonical = fold.new_canonical

        with merge_annotation("crdt_tpu.dense_merge",
                              hlc=lambda: self._canonical_time):
            new_store, win, slot_aligned, repack_mask = \
                self._dispatch_columns(slots, lt, node, val, tomb,
                                       new_canonical, my_ord,
                                       repack_since_lt=repack_since_lt)
        self._store = self._postprocess_store(new_store)
        # The join produced fresh buffers (the old lanes were consumed
        # — donated when eligible); the next columnar merge may donate
        # them again, keeping repeated gossip rounds at the in-place
        # dispatch floor.
        self._store_escaped = False
        if _sanitizer.enabled():
            # Callers collapse duplicate slots before reaching here
            # (same contract the merge itself needs), so the
            # payload-order domination check is well-defined.
            _sanitizer.check_dense_sparse_join(self._store, slots, lt,
                                               node)
            if self._gc_purged is not None:
                _sanitizer.check_dense_no_resurrection(
                    self._store, *self._gc_purged)

        if self._hub.active:
            win_full = np.asarray(jax.device_get(win))
            # The wide join reports win per SLOT; re-align to payload
            # order so events keep the reference's visit order.
            win_h = win_full[slots] if slot_aligned else win_full[:k]
            self.stats.records_adopted += int(win_h.sum())
            widx = np.nonzero(win_h)[0]

            def value_at(i):
                return None if tomb[i] else int(val[i])

            # Both callers (`_merge_columns` and the C wire-scan path)
            # collapse duplicate slots last-wins before reaching here,
            # so a queried slot matches AT MOST one payload entry —
            # the get callback can never answer with a losing
            # occurrence's value (ChangeHub.add_batch's contract).
            # crdtlint: disable=add-batch-unique-keys -- duplicate slots are collapsed last-wins by both callers before reaching here (see above)
            self._hub.add_batch(
                lambda: ([int(slots[i]) for i in widx],
                         [value_at(i) for i in widx]),
                lambda q: ((True,
                            value_at(int(np.nonzero(slots == q)[0][-1])))
                           if isinstance(q, (int, np.integer))
                           and bool(np.any(slots[widx] == q))
                           else (False, None)))
        else:
            # No subscriber: keep the win mask on device — the warm
            # sparse path then has ZERO device->host fetches (each one
            # is a full host round trip); the adopted counter drains
            # lazily when stats are read.
            self.stats.add_adopted_lazy(jnp.sum(win))
        self._canonical_time = Hlc.send(
            Hlc.from_logical_time(new_canonical, self._node_id),
            millis=self._wall_clock())
        return repack_mask

    # Above this fraction of the slot space a columnar delta executes
    # as the elementwise N-wide join instead of the k-index scatter:
    # TPU scatters serialize per index (~0.3 s for 1M indices on v5e),
    # while the slot-aligned compare/select sweep is one fused
    # elementwise pass; the host-side fancy-write that builds the
    # N-wide lanes costs ~30 ms at 1M. Below the threshold the O(k)
    # scatter wins (a 10-record sync must not touch N-wide lanes).
    WIDE_JOIN_FRACTION = 4

    def _dispatch_columns(self, slots, lt, node, val, tomb,
                          new_canonical: int, my_ord: int,
                          repack_since_lt: Optional[int] = None):
        """Run a validated columnar delta through the store join.
        Returns ``(new_store, win, slot_aligned, repack_mask)`` —
        ``win`` is per SLOT (N-wide) when ``slot_aligned``, else per
        payload entry. ``repack_mask`` is the fused next-pack delta
        mask when ``repack_since_lt`` was requested AND the sparse
        fused kernel ran; None on every other route."""
        if self._sem is not None:
            return self._dispatch_columns_typed(
                slots, lt, node, val, tomb, new_canonical,
                my_ord) + (None,)
        k = len(slots)
        n = self.n_slots
        if k * self.WIDE_JOIN_FRACTION >= n:
            lt_n = np.zeros((n,), np.int64)
            node_n = np.zeros((n,), np.int16
                              if len(self._table) <= 0x7FFF else np.int32)
            tomb_n = np.zeros((n,), bool)
            valid_n = np.zeros((n,), bool)
            lt_n[slots] = lt
            node_n[slots] = node
            tomb_n[slots] = tomb
            valid_n[slots] = True
            # Narrow the value lane to int32 when every value fits —
            # the transfer is the wide join's main cost and the jit
            # widens on device (value_width=32 replicas always fit).
            if self._value_width == 32 or (
                    k and -(2 ** 31) <= int(val.min())
                    and int(val.max()) < 2 ** 31):
                val_n = np.zeros((n,), np.int32)
            else:
                val_n = np.zeros((n,), np.int64)
            val_n[slots] = val
            from ..ops.dense import wire_join_step
            new_store, win = wire_join_step(
                self._store, jnp.asarray(lt_n), jnp.asarray(node_n),
                jnp.asarray(val_n), jnp.asarray(tomb_n),
                jnp.asarray(valid_n), jnp.int64(new_canonical),
                jnp.int32(my_ord), donate=self._donate_writes(),
                sharding=self._write_sharding())
            return new_store, win, True, None
        # Pad k to a power of two (invalid rows scatter to the n_slots
        # sentinel, mode="drop") so the jitted step compiles O(log k)
        # distinct shapes, not one per delta size.
        padded = 1 << max(k - 1, 1).bit_length()
        lt_p = np.zeros((padded,), np.int64)
        node_p = np.zeros((padded,), np.int32)
        val_p = np.zeros((padded,), np.int64)
        tomb_p = np.zeros((padded,), bool)
        valid = np.zeros((padded,), bool)
        slot_arr = np.full((padded,), self.n_slots,
                           np.int32 if self.n_slots < 2 ** 31 - 1
                           else np.int64)
        slot_arr[:k] = slots
        valid[:k] = True
        lt_p[:k] = lt
        node_p[:k] = node
        val_p[:k] = val
        tomb_p[:k] = tomb
        if repack_since_lt is not None:
            # Fused relay: the join AND the next pack's delta mask come
            # out of ONE jitted program — no second dispatch between a
            # gossip merge and the reply pack (docs/FASTPATH.md).
            new_store, win, mask = merge_repack_step(
                self._store, jnp.asarray(slot_arr), jnp.asarray(lt_p),
                jnp.asarray(node_p), jnp.asarray(val_p),
                jnp.asarray(tomb_p), jnp.asarray(valid),
                jnp.int64(new_canonical), jnp.int32(my_ord),
                jnp.int64(repack_since_lt),
                donate=self._donate_writes(),
                sharding=self._write_sharding())
            return new_store, win, False, mask
        new_store, win = sparse_fanin_step(
            self._store, jnp.asarray(slot_arr), jnp.asarray(lt_p),
            jnp.asarray(node_p), jnp.asarray(val_p),
            jnp.asarray(tomb_p), jnp.asarray(valid),
            jnp.int64(new_canonical), jnp.int32(my_ord),
            donate=self._donate_writes(), sharding=self._write_sharding())
        return new_store, win, False, None

    def _dispatch_columns_typed(self, slots, lt, node, val, tomb,
                                new_canonical: int, my_ord: int):
        """The typed counterpart of `_dispatch_columns`: same
        wide-vs-sparse cutover, but routed through the semantics
        kernels with the per-slot (wide) or per-row (sparse) tag lane.
        The value lane stays int64 — typed encodings use all 64 bits,
        so the wide path's int32 narrowing never applies."""
        from ..semantics.kernels import (typed_sparse_join_step,
                                         typed_wire_join_step)
        k = len(slots)
        n = self.n_slots
        if k * self.WIDE_JOIN_FRACTION >= n:
            lt_n = np.zeros((n,), np.int64)
            node_n = np.zeros((n,), np.int32)
            val_n = np.zeros((n,), np.int64)
            tomb_n = np.zeros((n,), bool)
            valid_n = np.zeros((n,), bool)
            lt_n[slots] = lt
            node_n[slots] = node
            val_n[slots] = val
            tomb_n[slots] = tomb
            valid_n[slots] = True
            new_store, win = typed_wire_join_step(
                self._store, self._sem_device(), jnp.asarray(lt_n),
                jnp.asarray(node_n), jnp.asarray(val_n),
                jnp.asarray(tomb_n), jnp.asarray(valid_n),
                jnp.int64(new_canonical), jnp.int32(my_ord),
                donate=self._donate_writes(),
                sharding=self._write_sharding())
            return new_store, win, True
        padded = 1 << max(k - 1, 1).bit_length()
        sem_rows = np.zeros((padded,), np.int8)
        lt_p = np.zeros((padded,), np.int64)
        node_p = np.zeros((padded,), np.int32)
        val_p = np.zeros((padded,), np.int64)
        tomb_p = np.zeros((padded,), bool)
        valid = np.zeros((padded,), bool)
        slot_arr = np.full((padded,), self.n_slots, np.int32)
        slot_arr[:k] = slots
        sem_rows[:k] = self._sem[slots]
        valid[:k] = True
        lt_p[:k] = lt
        node_p[:k] = node
        val_p[:k] = val
        tomb_p[:k] = tomb
        new_store, win = typed_sparse_join_step(
            self._store, jnp.asarray(sem_rows), jnp.asarray(slot_arr),
            jnp.asarray(lt_p), jnp.asarray(node_p), jnp.asarray(val_p),
            jnp.asarray(tomb_p), jnp.asarray(valid),
            jnp.int64(new_canonical), jnp.int32(my_ord),
            donate=self._donate_writes(),
            sharding=self._write_sharding())
        return new_store, win, False

    # --- checkpoint/resume (SURVEY.md §5) ---

    def save(self, path: str) -> None:
        """Columnar snapshot INCLUDING the node-id table the ordinal
        lanes index into (`crdt_tpu.checkpoint.save_dense`) AND the
        Merkle digest tree under its cache key — a restarted replica
        answers its first anti-entropy walk from the persisted tree
        with zero digest dispatches (docs/ANTIENTROPY.md). The tree
        comes from the digest cache when the store is quiet, so a
        save after a walk adds no device work."""
        self.drain_ingest()
        from ..checkpoint import save_dense
        tree = self.digest_tree()
        save_dense(self._store, path,
                   node_ids=self._table.ids(),
                   digest=(tree, self._canonical_time.logical_time,
                           self._sem_version))

    @classmethod
    def load(cls, node_id: Any, path: str,
             wall_clock: Optional[Callable[[], int]] = None,
             **kwargs) -> "DenseCrdt":
        """Resume from a snapshot; the canonical clock rebuilds from the
        lanes (refreshCanonicalTime semantics, crdt.dart:31-33) and
        writer attribution survives via the persisted node table. A
        persisted digest tree re-seeds the digest cache when its key
        still matches the rebuilt state — guarded on clock, semantics
        version, and geometry, so a stale or foreign tree silently
        falls back to rebuild-on-first-walk."""
        from ..checkpoint import load_dense_digest, \
            load_dense_with_node_ids
        store, ids = load_dense_with_node_ids(path)
        if ids is None:
            # A lane-only snapshot's ordinals are uninterpretable here;
            # constructing a replica anyway would silently re-attribute
            # (or crash on) every foreign record.
            raise ValueError(
                f"{path} has no node-id table (store-level snapshot); "
                "use DenseCrdt.save for resumable snapshots, or pass "
                "store=load_dense(path) with the original node_ids")
        crdt = cls(node_id, store.n_slots, wall_clock=wall_clock,
                   store=store, node_ids=ids, **kwargs)
        restored = load_dense_digest(path)
        if restored is not None:
            tree, logical_time, sem_version = restored
            # Seed AFTER construction: the _store setter in __init__
            # cleared the cache, and the guards below are what make
            # the seed sound (same clock head, same semantics column
            # version, same tree geometry as this replica would build).
            if (logical_time == crdt._canonical_time.logical_time
                    and sem_version == crdt._sem_version
                    and tree.n_slots == crdt.n_slots
                    and tree.leaf_width == crdt.DIGEST_LEAF_WIDTH):
                # Key under the LIVE generation: the snapshot's counter
                # is meaningless here, and the guards above prove the
                # tree matches the state this generation names.
                crdt._digest_cache = (crdt._digest_key(), tree)
        return crdt

    # --- replication (C9/C10) ---

    def export_delta(self, since: Optional[Hlc] = None
                     ) -> Tuple[DenseChangeset, List[Any]]:
        """Outbound changeset: full state, or records with
        ``modified >= since`` (inclusive, map_crdt.dart:44-45), plus the
        node-id list its ordinals index into."""
        self.drain_ingest()
        since_lt = None if since is None else jnp.int64(since.logical_time)
        # store_to_changeset reshapes lanes; whether jax aliases the
        # underlying buffers is backend-dependent, so treat the export
        # as an escape — later writes must not donate those buffers.
        self._store_escaped = True
        cs = store_to_changeset(self._store, since_lt)
        return cs, self._table.ids()

    def _fit_slots(self, cs: DenseChangeset) -> DenseChangeset:
        """Normalize a peer changeset's slot width to this replica's
        capacity: a NARROWER peer (pre-`grow` rollout) pads with
        invalid lanes; a WIDER one would silently drop records past
        capacity, so it raises with the remedy instead of dying in an
        XLA shape error."""
        width = cs.lt.shape[1]
        if width == self.n_slots:
            return cs
        if width > self.n_slots:
            raise ValueError(
                f"peer changeset covers {width} slots but this replica "
                f"holds {self.n_slots}; call grow({width}) first")
        pad = self.n_slots - width
        return DenseChangeset(
            lt=jnp.pad(cs.lt, ((0, 0), (0, pad))),
            node=jnp.pad(cs.node, ((0, 0), (0, pad))),
            val=jnp.pad(cs.val, ((0, 0), (0, pad))),
            tomb=jnp.pad(cs.tomb, ((0, 0), (0, pad))),
            valid=jnp.pad(cs.valid, ((0, 0), (0, pad))),
        )

    def _intern_ids(self, node_ids: Sequence[Any]) -> None:
        """Intern ids into the table, re-encoding stored lanes when new
        ids shift existing ordinals."""
        remap_store = self._table.intern(list(node_ids))
        if remap_store is not None:
            rd = jnp.asarray(remap_store)
            self._store = self._store._replace(
                node=rd[self._store.node],
                mod_node=rd[self._store.mod_node])

    def _encode_peer(self, cs: DenseChangeset, node_ids: Sequence[Any]
                     ) -> DenseChangeset:
        """Rewrite a changeset's ordinals into this replica's table.
        Every id in ``node_ids`` must already be interned — encoding
        against a table that can still shift corrupts earlier-encoded
        changesets (the round-1 stale-ordinal bug)."""
        remap = [self._table.ordinal(n) for n in node_ids]
        if remap == list(range(len(self._table))):
            # Peer table == local table (the steady gossip state):
            # the gather would rewrite an identical [R, N] node lane.
            return cs
        peer_to_local = jnp.asarray(remap, jnp.int32)
        return cs._replace(node=peer_to_local[cs.node])

    # Above this many replica rows the fold is executed as a lax.scan
    # over fixed-size chunks instead of a Python-unrolled [R, N] batch:
    # compile time stays flat in the peer count and one compiled step
    # serves every stream length. Results are bit-identical (the stream
    # is stamped with the union-final canonical).
    STREAM_THRESHOLD_ROWS = 16
    STREAM_CHUNK_ROWS = 8

    def _use_pallas(self) -> bool:
        """Route merges through the Mosaic kernel? ``executor=`` forces
        it on ("pallas" / "pallas-interpret") or off ("xla"); "auto"
        takes the kernel whenever the store is tile-aligned, the node
        table fits the kernel's int16 wire lane, and the backend is an
        accelerator."""
        if self._sem is not None:
            # Typed stores join through the semantics kernels (XLA
            # elementwise); the Mosaic kernel is LWW-only.
            # `set_semantics` refuses forced-pallas executors, so this
            # auto-fallback never contradicts an explicit request.
            return False
        from ..ops.pallas_merge import MAX_NODE_ORDINAL, mosaic_auto
        if len(self._table) > MAX_NODE_ORDINAL:
            # The kernel's changeset node lane is int16 (ordinals are
            # distinct-replica counts); a table past 32k ordinals
            # routes to the XLA fold rather than wrapping silently.
            if self._executor in ("pallas", "pallas-interpret"):
                raise ValueError(
                    f"executor={self._executor!r} supports at most "
                    f"{MAX_NODE_ORDINAL} node ordinals; table holds "
                    f"{len(self._table)}")
            return False
        if self._executor == "xla":
            return False
        if self._executor in ("pallas", "pallas-interpret"):
            return True
        return mosaic_auto(self.n_slots, jax.devices()[0].platform)

    def _dispatch_fanin(self, cs: DenseChangeset, wall: int):
        """Run the fan-in join; subclasses route to other executors.
        Returns ``(new_store, res)`` with a FaninResult-compatible res."""
        canonical = self._canonical_lt()
        local = jnp.int32(self._table.ordinal(self._node_id))
        if self._sem is not None:
            return self._typed_fanin(cs, canonical, local, wall)
        if self._use_pallas():
            return self._dispatch_pallas(cs, canonical, local, wall)
        r = cs.lt.shape[0]
        if r <= self.STREAM_THRESHOLD_ROWS:
            return fanin_step(self._store, cs, canonical, local,
                              jnp.int64(wall))
        rc = self.STREAM_CHUNK_ROWS
        cs = pad_replica_rows(cs, rc)
        chunks = DenseChangeset(*(
            lane.reshape(-1, rc, lane.shape[1]) for lane in cs))
        stamp = jnp.maximum(canonical,
                            jnp.max(jnp.where(cs.valid, cs.lt, _NEG)))
        return fanin_stream(self._store, chunks, canonical, local,
                            jnp.int64(wall), stamp)

    def _typed_fanin(self, cs: DenseChangeset, canonical, local,
                     wall: int):
        """Changeset fan-in on a typed store: the semantics kernels'
        Python-unrolled elementwise fold. Shared by the base AND
        sharded models — typed joins are purely elementwise, so the
        sharded store runs the same jit with its key-axis sharding
        pinned, no collective dispatch (replica rows fold locally
        against key-sharded lanes). Guard flags here are exact (same
        `recv_guards` as the XLA fold), so `_exact_guards` passes the
        result through unchanged."""
        from ..semantics.kernels import typed_fanin_step
        return typed_fanin_step(self._store, self._sem_device(), cs,
                                canonical, local, jnp.int64(wall),
                                sharding=self._write_sharding())

    def _dispatch_pallas(self, cs: DenseChangeset, canonical, local,
                         wall: int):
        """The Mosaic executor — ONE fused dispatch
        (`model_fanin_batch`): lane split/narrowing, value-width
        masking, seen count, the batch kernel, and the store re-join
        all inside a single jit, because each separate dispatch is a
        host round trip (optimistic guard flags — `_exact_guards`
        recomputes on a trip because the result carries no
        first-offender fields)."""
        from ..ops.pallas_merge import model_fanin_batch
        r = cs.lt.shape[0]
        chunk = self._kernel_chunk_rows(r)
        if chunk < r:
            cs = pad_replica_rows(cs, chunk)
        new_store, pres, seen, voverflow = model_fanin_batch(
            self._held, cs, canonical, local, jnp.int64(wall),
            chunk_rows=chunk,
            interpret=self._executor == "pallas-interpret",
            value_width=self._value_width)
        self.stats.add_seen_lazy(seen)
        if self._value_width == 32:
            self._pending_val_overflow = voverflow
        return new_store, self._pallas_result(pres)

    def _kernel_chunk_rows(self, r: int) -> int:
        """Chunk sizing for the batch kernel: small changesets (the
        common gossip delta) take ``chunk_rows=r`` and skip the row
        padding entirely — the eager pad concatenate writes chunk_rows
        full-width lanes (for 8×1M it cost more than the whole merge).
        Cost: each distinct r ≤ 8 compiles its own kernel once
        (bounded at 8 shapes; steady gossip reuses one), which the
        padding saving repays within a handful of merges."""
        return r if r <= self.STREAM_CHUNK_ROWS else self.STREAM_CHUNK_ROWS

    @staticmethod
    def _pallas_result(pres) -> FaninResult:
        """Adapt a `PallasFaninResult` (optimistic superset flags, no
        first-offender fields) to the model-layer FaninResult shape —
        `_exact_guards` recomputes on host when a flag trips."""
        return FaninResult(
            new_canonical=pres.new_canonical,
            win_count=jnp.sum(pres.win).astype(jnp.int32),
            win=pres.win,
            any_bad=pres.any_dup | pres.any_drift,
            first_bad=None, first_is_dup=None, canonical_at_fail=None)

    def _exact_guards(self, cs: DenseChangeset, res, wall: int):
        """Exact r-major sequential guard diagnostics (the visit order
        of crdt.dart:80-94). The XLA fan-in's flags are already exact
        and carry first-offender fields — returned as-is. Executors
        with coarse/superset flags (the sharded collectives, the
        optimistic Pallas guards) produce results WITHOUT
        ``first_bad``; recompute exactly on the unsharded changeset —
        failure path only — so raised exceptions carry the sequential
        path's first-offender payload, and false positives are cleared
        (None → merge proceeds)."""
        if getattr(res, "first_bad", None) is not None:
            return res
        any_bad, first_bad, first_is_dup, canonical_at_fail = recv_guards(
            cs.lt, cs.node, cs.valid,
            jnp.int64(self._canonical_time.logical_time),
            jnp.int32(self._table.ordinal(self._node_id)),
            jnp.int64(wall))
        if not bool(any_bad):
            return None
        return FaninResult(
            new_canonical=res.new_canonical, win_count=res.win_count,
            win=res.win, any_bad=any_bad, first_bad=first_bad,
            first_is_dup=first_is_dup, canonical_at_fail=canonical_at_fail)

    def _postprocess_store(self, store: DenseStore) -> DenseStore:
        """Hook for subclasses to re-annotate a freshly written store
        (the sharded model re-applies its NamedSharding here)."""
        return store

    def _resident_for(self, n_slots: int) -> bool:
        """Hold a store of ``n_slots`` as resident split planes (and
        commit it through the touched-tile Mosaic kernel)?
        Stamp-blind overwrites don't care about the store's semantics
        tags or table width, so the gates are only tile alignment and
        a backend Mosaic can lower on (interpret mode stands in
        off-TPU when forced)."""
        from ..ops.pallas_merge import TILE, mosaic_auto
        if n_slots % TILE or self._executor == "xla":
            return False
        if self._executor in ("pallas", "pallas-interpret"):
            return True
        return mosaic_auto(n_slots, jax.devices()[0].platform)

    def _empty_store(self, n_slots: int):
        """The never-written store of ``n_slots``, in the form it will
        be held: planes built directly where they are resident."""
        if self._resident_for(n_slots):
            from ..ops.pallas_scatter import empty_planes
            return empty_planes(n_slots)
        return empty_dense_store(n_slots)

    def _commit_scatter(self, slots: np.ndarray, lt: np.ndarray,
                        vals: np.ndarray, tombs: np.ndarray):
        """ONE device dispatch committing a deduped ingest batch
        (`WriteCombiner.flush`'s scatter tail). On resident planes the
        touched-tile Mosaic kernel writes its tiles in place (planes
        donated on every commit) and returns the planes; else the lax
        scatter with power-of-two padded lanes; the sharded model
        overrides this with one `shard_map` program
        (docs/FASTPATH.md)."""
        me = self._table.ordinal(self.node_id)
        planes = self._served_planes()
        if planes is not None:
            from ..ops.pallas_scatter import ingest_scatter_planes
            # crdtlint: disable=scatter-combiner-bypass -- only reached from the combiner's own flush, which IS the barrier
            return ingest_scatter_planes(
                planes, slots, lt, vals, tombs, me,
                donate=self._donate_writes(),
                interpret=self._executor == "pallas-interpret")
        # Fresh padded commit lanes every flush (power-of-two + slot ==
        # n_slots sentinel rows, mode="drop"): the dispatch owns them
        # outright, so the combiner's stage-side buffers are
        # immediately reusable — the double-buffer that lets the host
        # stage flush N+1 while N executes.
        d = len(slots)
        padded = 1 << max(d - 1, 1).bit_length()
        slot_l = np.full(padded, self.n_slots, np.int32)
        lt_l = np.zeros(padded, np.int64)
        val_l = np.zeros(padded, np.int64)
        tomb_l = np.zeros(padded, bool)
        slot_l[:d] = slots
        lt_l[:d] = lt
        val_l[:d] = vals
        tomb_l[:d] = tombs
        from ..ops.dense import ingest_scatter
        sharding = self._write_sharding()
        # crdtlint: disable=scatter-combiner-bypass -- only reached from the combiner's own flush, which IS the barrier
        new_store = ingest_scatter(
            self._store, jnp.asarray(slot_l), jnp.asarray(lt_l),
            jnp.asarray(val_l), jnp.asarray(tomb_l), jnp.int32(me),
            donate=self._donate_writes(), sharding=sharding)
        # The in-jit constraint already pinned the layout; skip the
        # subclass re-shard round-trip in that case.
        return new_store if sharding is not None \
            else self._postprocess_store(new_store)

    def _raise_guard(self, cs: DenseChangeset, res, wall: int) -> None:
        # Store untouched; canonical rolled to the pre-failure value
        # (sequential-merge parity, crdt.dart:77-94 throw path).
        self._canonical_time = Hlc.from_logical_time(
            int(res.canonical_at_fail), self._node_id)
        if bool(res.first_is_dup):
            raise DuplicateNodeException(str(self._node_id))
        bad_lt = int(cs.lt.reshape(-1)[int(res.first_bad)])
        raise ClockDriftException(bad_lt >> 16, wall)

    def merge(self, cs, node_ids: Optional[Sequence[Any]] = None) -> None:
        """Fan-in a peer changeset. ``cs.node`` ordinals index
        ``node_ids``; they are remapped into this replica's table.

        Also accepts a record dict (slot → Record) for duck-type
        compatibility with the `Crdt.merge` surface — `crdt_tpu.sync`
        rounds then work across dense and record-dict backends alike."""
        if isinstance(cs, dict):
            return self.merge_records(cs)
        if node_ids is None:
            raise ValueError(
                "merge(changeset) requires node_ids — the changeset's "
                "ordinals are meaningless without the table they index")
        self.merge_many([(cs, node_ids)])

    def merge_many(self, changesets: Sequence[
            Tuple[DenseChangeset, Sequence[Any]]]) -> None:
        """N-replica fan-in: concatenate peer changesets along the
        replica axis (earlier entries win identical-HLC ties, the
        sequential-merge order) and run ONE fused lattice join."""
        self.drain_ingest()
        self.stats.merges += 1
        if not changesets:
            # Merging nothing still consumes the absorption-phase wall
            # read AND the final send bump (crdt.dart:77-94 reads the
            # clock before the record loop regardless, then sends) —
            # the same two ticks every record-dict backend spends, so
            # cross-backend differentials under an injected clock
            # can't drift on empty anti-entropy rounds.
            self._wall_clock()
            if self._pipe is not None:
                # empty merges still occupy a window slot so the
                # flush's first-flag index stays aligned with the
                # caller's merge order
                self._pipe.merges += 1
                self._pipe_send_bump(self._wall_clock())
                return
            self._canonical_time = Hlc.send(self._canonical_time,
                                            millis=self._wall_clock())
            return
        # Intern the UNION of every peer's ids first — one table
        # mutation, one store re-encode — then encode each changeset
        # against the now-final table. Interleaving interning with
        # encoding left earlier-encoded changesets holding stale
        # ordinals whenever a later peer's ids re-sorted the table.
        union: set = set()
        for _, ids in changesets:
            union.update(ids)
        self._intern_ids(union)
        parts = [self._encode_peer(self._fit_slots(cs), ids)
                 for cs, ids in changesets]
        # Single-peer merges (the common gossip round) skip the concat
        # entirely — jnp.concatenate of one part still copies [R, N]
        # lanes.
        cs = parts[0] if len(parts) == 1 else DenseChangeset(
            *(jnp.concatenate([getattr(p, f) for p in parts])
              for f in DenseChangeset._fields))
        if self._gc_floor_lt and self._gc_fence_dev is not None:
            # Device-side resurrection fence for wide changesets —
            # same predicate as the columnar path in _merge_validated
            # (sub-floor row onto a PURGED slot = replay of purged
            # state); stays a mask fold, no host sync.
            cs = cs._replace(valid=cs.valid & ~(
                (cs.lt <= jnp.int64(self._gc_floor_lt))
                & self._gc_fence_dev[None, :]))
        pipe = self._pipe
        if pipe is not None and not pipe.exact and self._use_pallas():
            # Coarse pipelined Mosaic merges run as ONE dispatch
            # (merge + flag accumulation + send bump fused): the
            # separate bookkeeping ops each cost a host round trip and
            # were the dominant share of the north-star e2e pass.
            # Exact-guard windows keep the stepwise path (their guard
            # pass needs the wide lanes).
            from ..ops.pallas_merge import pipelined_model_step
            r = cs.lt.shape[0]
            chunk = self._kernel_chunk_rows(r)
            if chunk < r:
                cs = pad_replica_rows(cs, chunk)
            # Both wall reads up front (absorption + send bump): same
            # count and sequence as the unfused path, so injected
            # clocks tick identically.
            wall_merge = self._wall_clock()
            wall_send = self._wall_clock()
            with merge_annotation("crdt_tpu.dense_merge",
                                  hlc=lambda: self._canonical_time):
                (new_store, new_canon, any_bad, overflow, drift,
                 val_ovf, first_idx, win_count, win, seen) = \
                    pipelined_model_step(
                        self._held, cs, pipe.canonical, pipe.any_bad,
                        pipe.overflow, pipe.drift, pipe.val_overflow,
                        pipe.first_flag_idx,
                        jnp.int32(self._table.ordinal(self._node_id)),
                        jnp.int64(wall_merge), jnp.int64(wall_send),
                        jnp.int32(pipe.merges),
                        chunk_rows=chunk,
                        interpret=self._executor == "pallas-interpret",
                        value_width=self._value_width)
            pipe.canonical = new_canon
            pipe.any_bad = any_bad
            pipe.overflow = overflow
            pipe.drift = drift
            pipe.val_overflow = val_ovf
            pipe.first_flag_idx = first_idx
            pipe.merges += 1
            self._store = self._postprocess_store(new_store)
            self.stats.add_seen_lazy(seen)
            self.stats.add_adopted_lazy(win_count)
            self._emit_merge_wins(win)
            return
        if not self._use_pallas():
            # The Mosaic route folds BOTH of these into its single
            # fused dispatch (`model_fanin_batch`); the other
            # executors run them as standalone device ops here.
            if self._value_width == 32:
                # Uniform value-ref enforcement: records whose values
                # don't round-trip through int32 are masked INVALID
                # before dispatch — they never merge, so no truncated
                # or unnarrowed payload can land under the peer's
                # winning HLC — and the flag reports at the next
                # batched fetch / pipeline flush.
                fits = (cs.val.astype(jnp.int32).astype(jnp.int64)
                        == cs.val)
                self._pending_val_overflow = jnp.any(cs.valid & ~fits)
                cs = cs._replace(valid=cs.valid & fits)
            # Lazy device scalar: no device->host sync on the hot path.
            self.stats.add_seen_lazy(jnp.sum(cs.valid))

        wall = self._wall_clock()
        with merge_annotation("crdt_tpu.dense_merge",
                              hlc=lambda: self._canonical_time):
            new_store, res = self._dispatch_fanin(cs, wall)

        voverflow, self._pending_val_overflow = \
            self._pending_val_overflow, None
        self._finish_merge(new_store, res, voverflow, wall, lambda: cs)

    def _finish_merge(self, new_store, res, voverflow, wall: int,
                      cs_for_exact: Callable[[], DenseChangeset],
                      guard_lanes: Optional[Callable] = None) -> None:
        """Shared post-dispatch tail for changeset merges
        (`merge_many` / `merge_split`): the pipelined accumulation OR
        the one batched fetch + value-overflow reject + exact-guard
        recompute + store swap + stats + watch + final send bump.
        ``cs_for_exact`` lazily produces the WIDE changeset for the
        failure-path guard recompute — outside exact-mode windows,
        pre-split callers only pay the reconstruction when a flag
        actually trips. In an ``exact_guards`` window the guard lanes
        are needed EVERY merge; ``guard_lanes`` (a thunk returning
        ``(lt, node, valid)``) lets such callers supply just the three
        lanes the guards read instead of the full wide changeset."""
        if self._pipe is not None:
            # Pipelined tail: nothing leaves the device. Guard flags
            # OR-accumulate; the canonical threads through the device
            # send bump; the adopted counter drains lazily.
            pipe = self._pipe
            if pipe.exact:
                # One exact pass (cost: the running-cummax sweep the
                # fast kernels skip, plus — for pre-split callers —
                # the guard-lane reconstruction), seeded with the
                # threaded pre-merge canonical. The executor's
                # superset flags are superseded entirely.
                if guard_lanes is not None:
                    g_lt, g_node, g_valid = guard_lanes()
                else:
                    cs = cs_for_exact()
                    g_lt, g_node, g_valid = cs.lt, cs.node, cs.valid
                any_b, bad_lt, first_is_dup, caf = _pipe_exact_guards(
                    g_lt, g_node, g_valid, pipe.canonical,
                    jnp.int32(self._table.ordinal(self._node_id)),
                    jnp.int64(wall))
                newly = (~pipe.ex_have) & any_b
                pipe.ex_dup = jnp.where(newly, first_is_dup,
                                        pipe.ex_dup)
                pipe.ex_lt = jnp.where(newly, bad_lt, pipe.ex_lt)
                pipe.ex_caf = jnp.where(newly, caf, pipe.ex_caf)
                pipe.ex_wall = jnp.where(newly, jnp.int64(wall),
                                         pipe.ex_wall)
                pipe.ex_have = pipe.ex_have | any_b
                recv_flag = any_b
            else:
                recv_flag = res.any_bad
            new_flags = recv_flag
            if voverflow is not None:
                pipe.val_overflow = pipe.val_overflow | voverflow
                new_flags = new_flags | voverflow
            pipe.note(new_flags)
            pipe.any_bad = pipe.any_bad | recv_flag
            pipe.merges += 1
            self._store = new_store
            self.stats.add_adopted_lazy(res.win_count)
            self._emit_merge_wins(res.win)
            pipe.canonical = res.new_canonical
            self._pipe_send_bump(self._wall_clock())
            return

        # The small result scalars come back in ONE batched fetch: each
        # separate readback is a full host round trip. The [N] win
        # mask stays on device unless a watch subscriber needs it.
        from ..obs.trace import span
        with span("merge.guard_fetch", kind="merge"):
            if voverflow is None:
                any_bad, win_count, new_canonical = jax.device_get(
                    (res.any_bad, res.win_count, res.new_canonical))
                val_ovf = False
            else:
                any_bad, win_count, new_canonical, val_ovf = \
                    jax.device_get((res.any_bad, res.win_count,
                                    res.new_canonical, voverflow))
        if bool(val_ovf):
            # Raised BEFORE the store swap: the merge is rejected
            # whole (replica untouched; the offending records were
            # additionally masked out of the join), matching the
            # host-side write validation.
            raise ValueError(
                "value_width=32 replica merged a changeset holding "
                "values outside int32 range; use a value_width=64 "
                "replica (or payload-table indices) for such data")

        if bool(any_bad):
            cs = cs_for_exact()
            exact = self._exact_guards(cs, res, wall)
            if exact is not None:
                self._raise_guard(cs, exact, wall)
            # else: a coarser executor's guard flagged a record the
            # exact sequential order shields — proceed (store lanes
            # are bit-identical either way).

        self._store = new_store
        if _sanitizer.enabled():
            # Wide post-state check against the merged changeset. The
            # pipelined branch above is exempt BY CONTRACT: it promises
            # zero host syncs per merge, which a host-side assertion
            # would break — sanitize soaks run unpipelined.
            _sanitizer.check_dense_join(self._store, cs_for_exact())
            if self._gc_purged is not None:
                _sanitizer.check_dense_no_resurrection(
                    self._store, *self._gc_purged)
        self.stats.records_adopted += int(win_count)
        self._emit_merge_wins(res.win)
        self._canonical_time = Hlc.send(
            Hlc.from_logical_time(int(new_canonical), self._node_id),
            millis=self._wall_clock())

    # --- pre-split interchange (the kernel wire form, round 5) ---

    def export_split_delta(self, since: Optional[Hlc] = None,
                           tiled: bool = True):
        """Outbound changeset in the KERNEL WIRE FORM — split 32-bit
        lanes (`ops.pallas_merge.SplitChangeset`, or the narrow
        value-ref lanes on a ``value_width=32`` replica), pre-tiled to
        the kernel's resident layout when the capacity allows. What
        `merge_split` consumes with ZERO per-merge conversion: gossip
        peers exchanging this form skip both the int64 split and the
        tile relayout copy on every merge (each measured comparable to
        the join itself — docs/PERF.md round 5). Returns
        ``(split_changeset, node_ids)``."""
        from ..ops.pallas_merge import (TILE, split_changeset,
                                        split_changeset_narrow,
                                        tile_changeset)
        cs, ids = self.export_delta(since)
        if self._value_width == 32:
            # Values were range-checked on every ingest path; the
            # overflow flag is structurally False here.
            scs, _ = split_changeset_narrow(cs)
        else:
            scs = split_changeset(cs)
        if tiled and self.n_slots % TILE == 0:
            scs = tile_changeset(scs)
        return scs, ids

    def merge_split(self, scs, node_ids: Sequence[Any]) -> None:
        """Fan-in a PRE-SPLIT (optionally pre-tiled) changeset — the
        zero-conversion counterpart of ``merge(cs, node_ids)`` for
        peers exchanging `export_split_delta`'s wire form. Semantics
        (guards, value-width enforcement, pipelined windows, watch,
        stats, clock) are identical to the wide path; on executors
        without the Mosaic kernel the lanes are joined back to wide
        form and merged through ``merge`` (correct, just without the
        conversion saving). The changeset must cover exactly
        ``n_slots`` (capacity adaptation needs the wide path)."""
        from ..ops.pallas_merge import (_cs_shape, model_fanin_split,
                                        pad_split_rows,
                                        split_guard_lanes, split_to_wide)
        self.drain_ingest()
        r, n = _cs_shape(scs)
        if n != self.n_slots:
            raise ValueError(
                f"pre-split changeset covers {n} slots but this "
                f"replica holds {self.n_slots}; use merge() (the wide "
                "path pads/refuses capacity mismatches)")
        if not self._use_pallas():
            return self.merge(split_to_wide(scs), node_ids)
        from ..ops.pallas_merge import MAX_NODE_ORDINAL
        if len(self._table) + len(node_ids) > MAX_NODE_ORDINAL:
            # int16 node lane ceiling (pre-intern upper bound; the
            # wide path routes >32k-ordinal tables to the XLA fold).
            return self.merge(split_to_wide(scs), node_ids)
        self.stats.merges += 1
        self._intern_ids(node_ids)
        # Ordinal remap happens IN-JIT (model_fanin_split's node_map
        # gather) — eager remap ops cost a dispatch round trip each.
        node_map = np.fromiter(
            (self._table.ordinal(nid) for nid in node_ids),
            np.int16, count=len(node_ids))
        # Shared small-delta chunk sizing (`_kernel_chunk_rows`): skip
        # the (expensive, eager) row padding whenever r fits one chunk.
        chunk = self._kernel_chunk_rows(r)
        if chunk < r:
            scs = pad_split_rows(scs, chunk)
        pipe = self._pipe
        if pipe is not None and not pipe.exact:
            # Coarse window: one fused dispatch, like the wide path —
            # else the zero-conversion interchange would be the SLOWER
            # pipelined route (the bookkeeping dispatches cost more
            # than the merge at gossip shapes).
            from ..ops.pallas_merge import pipelined_model_step_split
            wall_merge = self._wall_clock()
            wall_send = self._wall_clock()
            with merge_annotation("crdt_tpu.dense_merge",
                                  hlc=lambda: self._canonical_time):
                (new_store, new_canon, any_bad, overflow, drift,
                 val_ovf, first_idx, win_count, win, seen) = \
                    pipelined_model_step_split(
                        self._held, scs, jnp.asarray(node_map),
                        pipe.canonical, pipe.any_bad, pipe.overflow,
                        pipe.drift, pipe.val_overflow,
                        pipe.first_flag_idx,
                        jnp.int32(self._table.ordinal(self._node_id)),
                        jnp.int64(wall_merge), jnp.int64(wall_send),
                        jnp.int32(pipe.merges), chunk_rows=chunk,
                        interpret=self._executor == "pallas-interpret",
                        value_width=self._value_width)
            pipe.canonical = new_canon
            pipe.any_bad = any_bad
            pipe.overflow = overflow
            pipe.drift = drift
            pipe.val_overflow = val_ovf
            pipe.first_flag_idx = first_idx
            pipe.merges += 1
            self._store = self._postprocess_store(new_store)
            self.stats.add_seen_lazy(seen)
            self.stats.add_adopted_lazy(win_count)
            self._emit_merge_wins(win)
            return
        wall = self._wall_clock()
        with merge_annotation("crdt_tpu.dense_merge",
                              hlc=lambda: self._canonical_time):
            new_store, pres, seen, voverflow = model_fanin_split(
                self._held, scs, jnp.asarray(node_map),
                self._canonical_lt(),
                jnp.int32(self._table.ordinal(self._node_id)),
                jnp.int64(wall), chunk_rows=chunk,
                interpret=self._executor == "pallas-interpret",
                value_width=self._value_width)
        self.stats.add_seen_lazy(seen)
        res = self._pallas_result(pres)

        def wide_for_exact():
            # Failure path only: reconstruct wide lanes AND apply the
            # ordinal remap (the hot path remapped in-jit, so ``scs``
            # still carries peer ordinals).
            wide = split_to_wide(scs)
            table = jnp.asarray(node_map, jnp.int32)
            idx = jnp.clip(wide.node, 0, len(node_map) - 1)
            return wide._replace(
                node=jnp.where(wide.valid, table[idx], 0))

        self._finish_merge(
            new_store, res,
            voverflow if self._value_width == 32 else None, wall,
            wide_for_exact,
            guard_lanes=lambda: split_guard_lanes(
                scs.hi, scs.lo, scs.node, jnp.asarray(node_map)))

    # pack_since cache depth: a replica gossips a handful of peers with
    # (usually) one shared watermark frontier per store state; slots
    # beyond that are churn, not reuse. Depth is enforced by LRU
    # eviction (`_pack_cache_store`), so a peer churn storm — 100
    # distinct watermarks against one store state — cannot grow the
    # cache past this bound; evictions are counted in
    # ``crdt_tpu_pack_cache_evictions_total``.
    PACK_CACHE_SLOTS = 4

    def _resolve_sem_mode(self, sem_mode: str) -> str:
        if sem_mode not in ("auto", "include", "withhold"):
            raise ValueError(f"unknown sem_mode {sem_mode!r}")
        # "plain": untyped store — no lane to attach, nothing to
        # withhold (the seed wire form, whatever the caller asked).
        return "plain" if self._sem is None else (
            "withhold" if sem_mode == "auto" else sem_mode)

    def _pack_host_columns(self, mask: np.ndarray, lt: np.ndarray,
                           node: np.ndarray, val: np.ndarray,
                           tomb: np.ndarray, resolved: str,
                           slots: Optional[np.ndarray] = None
                           ) -> PackedDelta:
        """Select the masked rows and land them in ONE arena
        (`ops.packing.pack_into_arena`) — the zero-copy pack tail
        shared by `pack_since`, `merge_and_repack` and the collective
        join. The arena's views are the exact buffers `pack_rows`
        frames for the wire. ``slots`` given: the columns are rows
        already selected, in slot order, and ``slots`` their slot
        numbers; the pack is the one the whole lanes would give."""
        idx = np.nonzero(mask)[0]
        slot_of = idx if slots is None else slots[idx]
        sem_src = None
        if resolved == "withhold":
            typed = self._sem[slot_of] != 0
            withheld = int(typed.sum())
            if withheld:
                from ..obs.registry import default_registry
                default_registry().counter(
                    "crdt_tpu_sync_semantics_downgrade_total",
                    "typed rows withheld from LWW-only wire forms "
                    "by direction").inc(withheld,
                                        direction="outbound",
                                        node=str(self._node_id))
                idx = idx[~typed]
        elif resolved == "include":
            sem_src = self._sem if slots is None else self._sem[slots]
        return pack_into_arena(idx, lt, node, val, tomb, sem=sem_src,
                               slot_ids=slots)

    def _pack_cache_store(self, key, out) -> None:
        """Insert a finished pack, LRU-evicting past PACK_CACHE_SLOTS
        with the eviction counter — churn storms stay bounded AND
        visible."""
        self._pack_cache[key] = out
        if len(self._pack_cache) > self.PACK_CACHE_SLOTS:
            from ..obs.registry import default_registry
            ev = default_registry().counter(
                "crdt_tpu_pack_cache_evictions_total",
                "pack_since cache entries LRU-evicted at the "
                "PACK_CACHE_SLOTS depth bound")
            while len(self._pack_cache) > self.PACK_CACHE_SLOTS:
                self._pack_cache.popitem(last=False)
                ev.inc(node=str(self._node_id))

    #: Slots per digest leaf (ops/digest.py, docs/ANTIENTROPY.md):
    #: the granularity at which the Merkle walk localizes divergence
    #: and the range pack re-ships rows. Mirrors
    #: `ops.digest.DEFAULT_LEAF_WIDTH`; both peers must agree (the
    #: walk checks geometry) — override in lockstep only.
    DIGEST_LEAF_WIDTH = 8

    def _digest_key(self):
        """Digest-cache key: clock head + semantics version + store
        generation. The generation term is what keeps a post-`gc_purge`
        /`compact` tree distinct — those replace the store WITHOUT
        advancing the canonical clock (docs/STORAGE.md)."""
        return (self._canonical_time.logical_time, self._sem_version,
                self._store_gen)

    def _digest_levels(self):
        """Device digest-tree levels (root-first) over the current
        store — overridden by the sharded model to fan per-shard
        subtrees in through `parallel/fanin.py`."""
        from ..ops.digest import digest_tree_device
        sem = self._sem_device() if self._sem is not None else None
        return digest_tree_device(self._store, sem,
                                  self.DIGEST_LEAF_WIDTH)

    def digest_tree(self):
        """Merkle anti-entropy digest tree (docs/ANTIENTROPY.md): a
        segment-tree of 64-bit digests over the replicated lanes,
        computed ON DEVICE in one jit-cached reduction and fetched with
        a single ``device_get``. Two replicas compare roots, walk only
        differing subtrees (O(log n) round trips over the ``digest``
        wire op), and re-ship just the divergent leaf ranges through
        ``pack_since(ranges=...)`` — cold-join traffic scales with
        divergence, not store size.

        Cached exactly like the pack cache, keyed on ``(clock,
        sem_version)``: every store replacement clears it through the
        ``_store`` setter and `set_semantics` migrations drop it, so
        an unchanged store recomputes (and dispatches) nothing.
        Lookups are counted in ``crdt_tpu_digest_cache_total``."""
        from ..obs.registry import default_registry
        from ..obs.trace import span
        from ..ops.digest import DeviceDigestLevels, build_digest_tree
        # Drain BEFORE the key reads the canonical clock — same
        # aliasing hazard as pack_since.
        self.drain_ingest()
        key = self._digest_key()
        counter = default_registry().counter(
            "crdt_tpu_digest_cache_total",
            "digest_tree cache lookups by outcome")
        cached = self._digest_cache
        if cached is not None and cached[0] == key:
            tree = cached[1]
            if isinstance(tree, DeviceDigestLevels):
                # A collective join left the levels on the device.
                tree = tree.tree()
                self._digest_cache = (key, tree)
            counter.inc(outcome="hit", node=str(self._node_id))
            return tree
        counter.inc(outcome="miss", node=str(self._node_id))
        with span("digest_tree", kind="digest",
                  hlc=lambda: self._canonical_time,
                  node=str(self._node_id)):
            tree = build_digest_tree(self.n_slots,
                                     self.DIGEST_LEAF_WIDTH,
                                     self._digest_levels())
        self._digest_cache = (key, tree)
        return tree

    def _normalize_ranges(self, ranges):
        """Validate/canonicalize a ``pack_since`` range mask: a
        sequence of half-open ``(lo, hi)`` slot spans -> sorted tuple
        with empty spans dropped. None means unrestricted."""
        if ranges is None:
            return None
        out = []
        for pair in ranges:
            lo, hi = pair
            lo, hi = int(lo), int(hi)
            if not 0 <= lo <= hi <= self.n_slots:
                raise ValueError(
                    f"pack range ({lo}, {hi}) out of bounds for "
                    f"{self.n_slots} slots")
            if lo < hi:
                out.append((lo, hi))
        return tuple(sorted(out))

    def _range_delta_mask(self, since: Optional[Hlc], ranges):
        """Device mask for `pack_since(ranges=...)`: the delta mask
        AND a union of slot spans. Span arrays pad to a power of two
        with empty ``(0, 0)`` spans so the jit cache sees O(log)
        distinct shapes across walks."""
        from ..ops.dense import dense_range_delta_mask
        k = max(1, len(ranges))
        pad = 1
        while pad < k:
            pad *= 2
        los = np.zeros(pad, np.int64)
        his = np.zeros(pad, np.int64)
        for i, (lo, hi) in enumerate(ranges):
            los[i] = lo
            his[i] = hi
        since_lt = 0 if since is None else since.logical_time
        return dense_range_delta_mask(self._store, jnp.int64(since_lt),
                                      jnp.asarray(los),
                                      jnp.asarray(his))

    def pack_since(self, since: Optional[Hlc] = None,
                   sem_mode: str = "auto", ranges=None
                   ) -> Tuple[PackedDelta, List[Any]]:
        """Outbound O(k) columnar delta: host lanes for the rows with
        ``modified >= since`` (inclusive, the `export_delta` bound) —
        the wire form `merge_packed` ingests. Unlike `export_delta` /
        `export_split_delta` this ships only MODIFIED rows (the
        `count_modified_since` mask), so steady-state gossip bytes are
        proportional to what changed, not to capacity.

        ``sem_mode`` is how the transport's capability negotiation
        reaches the pack (docs/WIRE.md): ``"include"`` attaches the
        uint8 ``sem`` tag lane (peer negotiated the ``semantics``
        hello cap); ``"withhold"`` drops non-LWW rows instead —
        withheld, never corrupted — counting them in
        ``crdt_tpu_sync_semantics_downgrade_total``; ``"auto"``
        (in-process callers) withholds only when the store actually
        holds typed slots. An all-LWW replica omits the lane under
        every mode — the legacy 5-lane frame stays byte-identical.

        ``ranges`` restricts the pack to a union of half-open
        ``(lo, hi)`` slot spans — the anti-entropy tail
        (docs/ANTIENTROPY.md): after a Merkle walk localizes
        divergence, only the divergent leaf ranges re-ship.
        ``ranges=((0, n_slots),)`` is bit-identical to the
        unrestricted pack.

        Results are cached keyed on ``(since, canonical, semantics
        version, mode, ranges)``; every store replacement — puts, deletes,
        merges, grow, ordinal remaps — clears the cache through the
        ``_store`` setter, and a `set_semantics` migration bumps the
        version (and clears outright), so a cached pack can never leak
        rows under stale tags. Hits/misses are counted in
        ``crdt_tpu_pack_cache_total``. The device lanes are copied to
        host here, so packing does NOT escape the store snapshot
        (later merges may still donate)."""
        from ..obs.registry import default_registry
        from ..obs.trace import span
        resolved = self._resolve_sem_mode(sem_mode)
        ranges = self._normalize_ranges(ranges)
        # Drain BEFORE the cache key reads the canonical: a flush
        # advances the clock AND replaces the store, so a key built
        # first would alias a pre-flush pack under a stale watermark.
        self.drain_ingest()
        key = (None if since is None else since.logical_time,
               self._canonical_time.logical_time,
               self._sem_version, self._store_gen, resolved, ranges)
        counter = default_registry().counter(
            "crdt_tpu_pack_cache_total",
            "pack_since cache lookups by outcome")
        cached = self._pack_cache.get(key)
        if cached is not None:
            self._pack_cache.move_to_end(key)
            counter.inc(outcome="hit", node=str(self._node_id))
            return cached
        counter.inc(outcome="miss", node=str(self._node_id))
        with span("pack_since", kind="pack",
                  hlc=lambda: self._canonical_time,
                  node=str(self._node_id)):
            mask = (self._delta_mask(since) if ranges is None
                    else self._range_delta_mask(since, ranges))
            # One batched device->host fetch; `modified` lanes are
            # local-only and never serialized (record.dart:28-31).
            mask, lt, node, val, tomb = jax.device_get(
                (mask, self._store.lt, self._store.node,
                 self._store.val, self._store.tomb))
            packed = self._pack_host_columns(mask, lt, node, val, tomb,
                                             resolved)
        out = (packed, self._table.ids())
        self._pack_cache_store(key, out)
        return out

    def merge_packed(self, packed: PackedDelta,
                     node_ids: Sequence[Any]) -> None:
        """Fan-in a `pack_since` delta: ``packed.node`` holds ordinals
        into ``node_ids`` (the peer's table order). Validation —
        aligned lanes, ordinal range, slot bounds, value width — runs
        BEFORE the first clock mutation, and duplicate slots collapse
        last-wins (`_last_wins_keep`), the same contract every other
        columnar ingest path honors. Cost is O(k) in the delta."""
        self._merge_packed_impl(packed, node_ids, None)

    def merge_and_repack(self, packed: PackedDelta,
                         node_ids: Sequence[Any],
                         since: Optional[Hlc] = None,
                         sem_mode: str = "auto"
                         ) -> Tuple[PackedDelta, List[Any]]:
        """`merge_packed` + `pack_since` fused into ONE device
        dispatch — the gossip relay op. The sparse join emits the next
        pack's delta mask from the same jitted program
        (`ops.dense.merge_repack_step`, donated store), so a relay
        round costs one dispatch instead of merge + cache-missed
        repack. Returns exactly what ``pack_since(since, sem_mode)``
        would return right after the merge, and seeds the pack cache
        under that key, so the NEXT watermark-aligned `pack_since`
        hits. Falls back to the two-step path whenever the fused
        kernel can't run (empty delta, wide join cutover, typed
        store)."""
        from ..obs.registry import default_registry
        resolved = self._resolve_sem_mode(sem_mode)
        since_lt = 0 if since is None else int(since.logical_time)
        mask = self._merge_packed_impl(packed, node_ids, since_lt)
        if mask is None:
            return self.pack_since(since, sem_mode)
        default_registry().counter(
            "crdt_tpu_fused_repack_total",
            "gossip relays served by the fused merge+repack "
            "dispatch").inc(node=str(self._node_id))
        key = (None if since is None else since.logical_time,
               self._canonical_time.logical_time,
               self._sem_version, self._store_gen, resolved, None)
        mask, lt, node, val, tomb = jax.device_get(
            (mask, self._store.lt, self._store.node,
             self._store.val, self._store.tomb))
        packed_out = self._pack_host_columns(mask, lt, node, val, tomb,
                                             resolved)
        out = (packed_out, self._table.ids())
        # Seed AFTER the merge assigned `_store` (the setter cleared
        # the cache), so the entry survives until the next store
        # replacement — exactly pack_since's lifetime rules.
        self._pack_cache_store(key, out)
        return out

    def _merge_packed_impl(self, packed: PackedDelta,
                           node_ids: Sequence[Any],
                           repack_since_lt: Optional[int]
                           ) -> Optional[jax.Array]:
        self._refuse_in_pipeline("merge_packed")  # host recv fold
        self.drain_ingest()
        slots = np.asarray(packed.slots)
        lt = np.asarray(packed.lt, np.int64)
        ni = np.asarray(packed.node)
        val = np.asarray(packed.val, np.int64)
        tomb = np.asarray(packed.tomb).astype(bool)
        sem = (None if getattr(packed, "sem", None) is None
               else np.asarray(packed.sem).astype(np.int8))
        k = len(slots)
        if not (len(lt) == len(ni) == len(val) == len(tomb) == k) \
                or (sem is not None and len(sem) != k):
            raise ValueError("packed delta lanes are ragged")
        if k == 0:
            self.merge_many([])
            return None
        if int(ni.min()) < 0 or int(ni.max()) >= len(node_ids):
            raise ValueError(
                f"packed node ordinal out of range for {len(node_ids)} "
                "wire node ids")
        keep = self._last_wins_keep(slots)
        if keep is not None:
            slots, lt, ni, val, tomb = (slots[keep], lt[keep], ni[keep],
                                        val[keep], tomb[keep])
            if sem is not None:
                sem = sem[keep]
            k = len(slots)
        self.stats.merges += 1
        self.stats.add_seen_lazy(k)
        self._check_slots(slots)
        if sem is not None:
            # Two replicas must never join one slot under two
            # different lattices: the peer's announced tag has to
            # match the local column exactly (LWW rows included), and
            # the rejection lands BEFORE the first clock mutation.
            mism = sem != self._sem_host()[slots]
            if bool(mism.any()):
                i = int(np.nonzero(mism)[0][0])
                raise ValueError(
                    f"semantics tag mismatch at slot {int(slots[i])}: "
                    f"peer sent tag {int(sem[i])}, local column holds "
                    f"{int(self._sem_host()[slots[i]])}; run the same "
                    "set_semantics migration on both replicas before "
                    "syncing")
        self._check_value_width(val)
        self._intern_ids(node_ids)
        node = self._table.encode(node_ids)[ni]
        return self._merge_validated(slots, lt, node, val, tomb,
                                     sem_ok=sem is not None,
                                     repack_since_lt=repack_since_lt)

    def _pipe_send_bump(self, wall: int) -> None:
        """The final crdt.dart:93 send bump, on device, flags
        accumulated (a device op can't raise; flush checks them)."""
        from ..ops.merge import send_step
        pipe = self._pipe
        new_lt, overflow, drift = send_step(pipe.canonical,
                                            jnp.int64(wall))
        pipe.canonical = new_lt
        # merges was already incremented for this merge; attribute the
        # send-bump flags to it (merges - 1 in 0-based window order).
        pipe.note(overflow | drift, idx=pipe.merges - 1)
        pipe.overflow = pipe.overflow | overflow
        pipe.drift = pipe.drift | drift


class ShardedDenseCrdt(DenseCrdt):
    """`DenseCrdt` with its key space sharded across a device mesh.

    Store lanes carry a ``NamedSharding`` over the mesh's key axis
    (replicated over the replica axis); ``merge``/``merge_many`` run
    the `crdt_tpu.parallel` fan-in — replica-axis lexicographic-max
    collectives over ICI, DCN across slices. Incoming changesets are
    padded with invalid rows up to a multiple of the mesh's replica
    dimension, then sharded ``(replica, key)``.

    On TPU meshes whose per-device key shards are tile-aligned (and
    under forced ``executor="pallas"``/``"pallas-interpret"``), the
    per-device reduce inside the collective step runs through the
    Mosaic batch kernel (`parallel.fanin.make_sharded_pallas_fanin`) —
    the same executor as the single-chip headline path — with the
    pmax/pmin/psum replica reduction combining the per-shard partial
    stores. ``executor="xla"`` forces the plain shard_map fold.
    Results are lane-exact across all three executors.

    Guard semantics: the collective flags are per-device (coarser than
    the sequential visit order); when one trips, the guards are
    recomputed exactly on the unsharded changeset (`_exact_guards`), so
    raised exceptions carry the same first-offender payload as the
    single-device model and per-device false positives never reject a
    merge the sequential order accepts.
    """

    def __init__(self, node_id: Any, n_slots: int, mesh,
                 wall_clock: Optional[Callable[[], int]] = None,
                 store: Optional[DenseStore] = None,
                 node_ids: Optional[Sequence[Any]] = None,
                 executor: str = "auto", value_width: int = 64):
        from ..parallel import KEY_AXIS, make_sharded_fanin, shard_store
        self._mesh = mesh
        self._sharded_step = make_sharded_fanin(mesh)
        self._sharded_pallas_step = None
        self._shard = lambda s: shard_store(s, mesh)
        if executor in ("pallas", "pallas-interpret"):
            # Per-shard alignment, validated eagerly like the base
            # model: each device's key shard feeds the kernel whole.
            from ..ops.pallas_merge import TILE
            k = mesh.shape[KEY_AXIS]
            if n_slots % k or (n_slots // k) % TILE:
                raise ValueError(
                    f"executor={executor!r} needs n_slots divisible by "
                    f"the mesh's {k} key shards with each shard a "
                    f"multiple of {TILE}; got n_slots={n_slots}")
        super().__init__(node_id, n_slots, wall_clock=wall_clock,
                         store=store, node_ids=node_ids,
                         executor=executor, value_width=value_width)
        self._store = self._shard(self._store)

    def _dispatch_fanin(self, cs: DenseChangeset, wall: int):
        from ..parallel import (make_sharded_pallas_fanin, replica_extent,
                                shard_changeset)
        if self._sem is not None:
            # Typed joins are elementwise — the shared typed fold runs
            # directly on the key-sharded lanes, no collective step.
            return self._typed_fanin(
                cs, self._canonical_lt(),
                jnp.int32(self._table.ordinal(self._node_id)), wall)
        # The replica dim shards over EVERY non-key mesh axis (just
        # "replica" on a flat mesh; ("slice", "replica") on a
        # multi-slice one).
        extent = replica_extent(self._mesh)
        if self._use_pallas_sharded():
            # Kernel path: each device's shard must walk in whole
            # chunk_rows groups, so the replica padding is coarser.
            if self._sharded_pallas_step is None:
                self._sharded_pallas_step = make_sharded_pallas_fanin(
                    self._mesh, chunk_rows=self.STREAM_CHUNK_ROWS,
                    interpret=self._executor == "pallas-interpret")
            cs = pad_replica_rows(cs, extent * self.STREAM_CHUNK_ROWS)
            cs = shard_changeset(cs, self._mesh)
            return self._sharded_pallas_step(
                self._store, cs,
                self._canonical_lt(),
                jnp.int32(self._table.ordinal(self._node_id)),
                jnp.int64(wall))
        cs = pad_replica_rows(cs, extent)
        cs = shard_changeset(cs, self._mesh)
        return self._sharded_step(
            self._store, cs,
            self._canonical_lt(),
            jnp.int32(self._table.ordinal(self._node_id)),
            jnp.int64(wall))

    def _use_pallas(self) -> bool:
        # False on purpose: merge_many's generic branch must keep its
        # seen-count / value-width device ops (the sharded collective
        # step doesn't fold them in). The kernel still runs — PER
        # SHARD, inside the shard_map body — when
        # `_use_pallas_sharded` routes `_dispatch_fanin` to
        # `make_sharded_pallas_fanin`.
        return False

    def _use_pallas_sharded(self) -> bool:
        """Route the sharded fan-in through the per-device Mosaic
        kernel? Forced by ``executor=`` ("pallas"/"pallas-interpret"
        on, "xla" off); "auto" takes the kernel when each device's key
        shard is tile-aligned, the node table fits the kernel's int16
        wire lane, and the backend is TPU."""
        if self._sem is not None:
            return False  # typed stores route through _typed_fanin
        from ..ops.pallas_merge import MAX_NODE_ORDINAL, mosaic_auto
        from ..parallel import KEY_AXIS
        if len(self._table) > MAX_NODE_ORDINAL:
            if self._executor in ("pallas", "pallas-interpret"):
                raise ValueError(
                    f"executor={self._executor!r} supports at most "
                    f"{MAX_NODE_ORDINAL} node ordinals; table holds "
                    f"{len(self._table)}")
            return False
        if self._executor == "xla":
            return False
        if self._executor in ("pallas", "pallas-interpret"):
            return True
        k = self._mesh.shape[KEY_AXIS]
        # Gate on the MESH's devices, not the process default: a CPU
        # validation mesh on a TPU host (or vice versa) must route by
        # where the store actually lives.
        return (self.n_slots % k == 0
                and mosaic_auto(self.n_slots // k,
                                self._mesh.devices.flat[0].platform))

    # _exact_guards: inherited — ShardedFaninResult carries no
    # first_bad field, so the base recompute path handles the sharded
    # collectives' superset flags (see `crdt_tpu.parallel.fanin`).

    def _digest_levels(self):
        # Per-shard subtree leaves fan in along the key axis
        # (`parallel.make_sharded_digest`); falls back to the base
        # single-program reduction (still on device, GSPMD-sharded)
        # when leaf boundaries would straddle shards.
        from ..parallel import KEY_AXIS, make_sharded_digest
        k = self._mesh.shape[KEY_AXIS]
        if self.n_slots % k or (self.n_slots // k) % self.DIGEST_LEAF_WIDTH:
            return super()._digest_levels()
        has_sem = self._sem is not None
        fn = make_sharded_digest(self._mesh, self.DIGEST_LEAF_WIDTH,
                                 has_sem)
        if has_sem:
            return fn(self._store, self._sem_device())
        return fn(self._store)

    def _postprocess_store(self, store):
        # Sparse scatters land with XLA-chosen output sharding; pin the
        # key-axis NamedSharding back on. When every lane already
        # carries it (the in-jit with_sharding_constraint and the
        # shard_map programs both produce exactly this layout), skip
        # the 7-lane device_put round-trip outright — the sub-ms
        # dispatch path never pays for an identity re-shard.
        from ..parallel import store_sharding
        want = store_sharding(self._mesh)
        try:
            if all(getattr(lane, "sharding", None) == want
                   for lane in store):
                return store
        except Exception:  # non-addressable / tracer lanes: re-pin
            pass
        return self._shard(store)

    def _write_sharding(self):
        from ..parallel import store_sharding
        return store_sharding(self._mesh)

    def _resident_for(self, n_slots: int) -> bool:
        # Key-sharded lanes commit through their own shard_map program.
        return False

    def _commit_scatter(self, slots, lt, vals, tombs):
        # ONE shard_map program: every device takes its shard-local
        # rows of the (replicated) batch — no unsharded scatter, no
        # per-lane re-shard afterwards (the output is born on the
        # key-axis NamedSharding).
        from ..parallel import make_sharded_ingest
        d = len(slots)
        padded = 1 << max(d - 1, 1).bit_length()
        slot_l = np.full(padded, self.n_slots,
                         np.int32 if self.n_slots < 2 ** 31 - 1
                         else np.int64)
        lt_l = np.zeros(padded, np.int64)
        val_l = np.zeros(padded, np.int64)
        tomb_l = np.zeros(padded, bool)
        slot_l[:d] = slots
        lt_l[:d] = lt
        val_l[:d] = vals
        tomb_l[:d] = tombs
        step = make_sharded_ingest(self._mesh, self._donate_writes())
        return step(self._store, jnp.asarray(slot_l),
                    jnp.asarray(lt_l), jnp.asarray(val_l),
                    jnp.asarray(tomb_l),
                    jnp.int32(self._table.ordinal(self.node_id)))

    # put_batch/delete_batch need no override: the unstaged scatter
    # pins the key-axis sharding inside the jit (_write_sharding), and
    # _postprocess_store now recognizes that layout without a re-shard
    # dispatch; staged calls touch no device state until the
    # combiner's flush routes through _commit_scatter.

    def purge(self) -> None:
        super().purge()
        self._store = self._shard(self._store)

    def compact(self, ranges=None) -> np.ndarray:
        """Per-shard compaction inside ONE `shard_map`
        (`parallel.make_sharded_compact`): each device packs its own
        key shard to its local prefix, so the remap never crosses
        shard boundaries and the output is born on the key-axis
        sharding. Restricted ``ranges`` (or leaf-straddling shard
        geometry) fall back to the base single-program kernel, which
        is correct but may move rows across shards before
        `_postprocess_store` re-pins the layout."""
        from ..parallel import KEY_AXIS, make_sharded_compact
        k = self._mesh.shape[KEY_AXIS]
        if (ranges is not None or self.n_slots % k
                or (self.n_slots // k) % self.DIGEST_LEAF_WIDTH):
            return super().compact(ranges)
        self._refuse_in_pipeline("compact")
        self.drain_ingest()
        from ..ops.digest import build_digest_tree
        has_sem = self._sem is not None
        fn = make_sharded_compact(self._mesh, self.DIGEST_LEAF_WIDTH,
                                  has_sem, self._donate_writes())
        out = fn(self._store,
                 *((self._sem_device(),) if has_sem else ()))
        if has_sem:
            new_store, new_sem, translation, levels = out
        else:
            new_store, translation, levels = out
            new_sem = None
        translation = np.asarray(jax.device_get(translation))
        self._store = self._postprocess_store(new_store)
        self._store_escaped = False
        if new_sem is not None:
            sem_h = np.asarray(jax.device_get(new_sem)).astype(np.int8)
            self._sem = sem_h if sem_h.any() else None
            self._sem_dev = None
            self._sem_version += 1
        self._gc_purged = None
        self._gc_fence_dev = None
        tree = build_digest_tree(self.n_slots, self.DIGEST_LEAF_WIDTH,
                                 levels)
        self._digest_cache = (self._digest_key(), tree)
        from ..obs.registry import default_registry
        default_registry().counter(
            "crdt_tpu_compact_passes_total",
            "compact_remap dispatches").inc(node=str(self._node_id))
        return translation

    def grow(self, n_slots: int) -> None:
        from ..parallel import KEY_AXIS
        k = self._mesh.shape[KEY_AXIS]
        if n_slots % k:
            raise ValueError(
                f"n_slots={n_slots} not divisible by the mesh's "
                f"{k} key shards")
        if self._executor in ("pallas", "pallas-interpret"):
            from ..ops.pallas_merge import TILE
            if (n_slots // k) % TILE:
                raise ValueError(
                    f"executor={self._executor!r} needs each of the "
                    f"{k} key shards a multiple of {TILE}; got "
                    f"n_slots={n_slots}")
        if n_slots != self.n_slots:
            # jnp.concatenate on a key-sharded lane of this 2D mesh
            # folds the replicated 'replica' axis into a partial sum
            # (values double per replica) on current jax CPU meshes.
            # Pull the lanes off the mesh first; the base concat then
            # runs unsharded and _shard pins the grown layout back on.
            self.drain_ingest()
            self._store = DenseStore(
                *(jnp.asarray(np.asarray(lane)) for lane in self._store))
            if self._gc_fence_dev is not None:
                # Same off-mesh pull as the store lanes: the base
                # grow's concat must not run on a key-sharded mask.
                self._gc_fence_dev = jnp.asarray(
                    np.asarray(self._gc_fence_dev))
        super().grow(n_slots)
        self._store = self._shard(self._store)


def sync_dense(local: DenseCrdt, remote: DenseCrdt) -> None:
    """One anti-entropy round between two dense replicas
    (test/map_crdt_test.dart:273-279 semantics)."""
    time = local.canonical_time
    cs, ids = local.export_delta()
    remote.merge(cs, ids)
    cs, ids = remote.export_delta(since=time)
    local.merge(cs, ids)
