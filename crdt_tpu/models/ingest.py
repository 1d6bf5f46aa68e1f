"""Write-path fast lane: the host-side `WriteCombiner`.

The reference stamps and applies one record per ``put``
(crdt.dart:77-94) and the dense port inherited that shape: every
local write pays one `Hlc.send` plus one full scatter dispatch, so
the write path is dispatch-bound — ~4.8 ms for 1024 slots on a
sharded store against a ~2.1 ms dispatch floor
(MULTICHIP_SCALE_r05.json). The combiner coalesces local writes the
LSM way (log-structured staging, PAPERS.md) and commits them as ONE
fused, sharding-aware device program:

- ``put_batch``/``delete_batch`` issued inside a
  ``DenseCrdt.ingest()`` window append to growable columnar host
  buffers (slots/values/tombs lanes) — no device work per call.
- At flush the whole backlog is stamped by ONE vectorized
  `Hlc.send_batch` (one wall read, one counter run; each staged call
  keeps its own strictly-later stamp, so per-record monotonic order
  and putAll batch-stamp semantics both survive).
- The commit is a single `ops.dense.ingest_scatter` dispatch —
  donated, jit-cached, with the owner's precomputed ``NamedSharding``
  pinned on the output so sharded commits place rows shard-locally.
- The commit is double-buffered and non-blocking: the padded commit
  lanes are fresh buffers handed to the dispatch and never touched
  again, so the stage-side buffers accept flush N+1's writes while
  flush N executes on device — no fence anywhere in the fast lane.

Read-your-writes: ``get``/``count_modified_since``/``contains_slot``/
``is_deleted`` consult the staging overlay before the device store.
Every other read/merge/pack/serialization path is a BARRIER that
drains the combiner first (`DenseCrdt.drain_ingest`), so nothing
outside the window can observe a store missing staged writes. The
storage-plane passes are barriers too: `DenseCrdt.gc_purge` drains
before purging (a staged delete must land before its stamp is judged
against the floor) and `DenseCrdt.compact` drains before remapping
(staged rows address PRE-remap slots; committing them after the
translation would scatter into the wrong rows — docs/STORAGE.md).
See docs/INGEST.md for the lifecycle and visibility rules.
"""

from __future__ import annotations

import time
from typing import Optional, TYPE_CHECKING

import numpy as np

import jax.numpy as jnp

from ..hlc import Hlc

if TYPE_CHECKING:                       # pragma: no cover
    from .dense_crdt import DenseCrdt

_INITIAL_ROWS = 1024

# Flush-path instruments, resolved once per process (the default
# registry is a fixed singleton): the flush is the latency-sensitive
# leg of the fast lane, so it should not pay four registry lookups
# per commit.
_METRICS = None


def _metrics():
    global _METRICS
    if _METRICS is None:
        from ..obs.registry import default_registry
        reg = default_registry()
        _METRICS = (
            reg.counter("crdt_tpu_ingest_flush_total",
                        "write-combiner flushes by trigger"),
            reg.counter("crdt_tpu_ingest_flush_rows_total",
                        "rows committed by write-combiner flushes "
                        "(post-dedup)"),
            reg.counter("crdt_tpu_ingest_flush_groups_total",
                        "staged put/delete calls committed by flushes"),
            reg.histogram("crdt_tpu_ingest_flush_seconds",
                          "write-combiner flush wall time (stamp + "
                          "dispatch, no fence)"),
        )
    return _METRICS


class WriteCombiner:
    """Columnar staging buffers for one `DenseCrdt.ingest()` window.

    Not thread-safe on its own — like every other local-write surface,
    callers serialize through the replica lock (`GossipNode.lock`)
    when other threads gossip concurrently.
    """

    __slots__ = ("_owner", "_auto", "_slots", "_vals", "_tombs",
                 "_group", "_k", "_groups", "_pending", "flushes",
                 "rows_committed", "last_phase_seconds")

    def __init__(self, owner: "DenseCrdt",
                 auto_flush_rows: int = 1 << 16):
        if auto_flush_rows < 1:
            raise ValueError(
                f"auto_flush_rows must be >= 1; got {auto_flush_rows}")
        self._owner = owner
        self._auto = auto_flush_rows
        cap = _INITIAL_ROWS
        self._slots = np.empty(cap, np.int64)
        self._vals = np.empty(cap, np.int64)
        self._tombs = np.empty(cap, bool)
        self._group = np.empty(cap, np.int64)
        self._k = 0          # staged rows
        self._groups = 0     # staged API calls (one HLC stamp each)
        # slot -> value-or-None (tombstone), LAST staged occurrence:
        # the read-your-writes overlay answers point reads in O(1).
        self._pending: dict = {}
        self.flushes = 0
        self.rows_committed = 0
        # Per-phase wall time of the LAST flush: {"stamp": s,
        # "scatter": s} — the HLC stamp leg (wall read + counter run)
        # vs the dedup + device-scatter dispatch leg. The serving tier
        # reads this after each tick commit to attribute write-ack
        # latency (crdt_tpu_serve_ack_phase_seconds).
        self.last_phase_seconds: dict = {}

    # --- staging ---

    @property
    def pending_rows(self) -> int:
        """Rows staged and not yet committed."""
        return self._k

    @property
    def pending_groups(self) -> int:
        """Staged API calls awaiting their flush stamp."""
        return self._groups

    def _grow_to(self, need: int) -> None:
        cap = len(self._slots)
        if need <= cap:
            return
        while cap < need:
            cap *= 2
        for name in ("_slots", "_vals", "_tombs", "_group"):
            old = getattr(self, name)
            grown = np.empty(cap, old.dtype)
            grown[:self._k] = old[:self._k]
            setattr(self, name, grown)

    def stage(self, slots: np.ndarray, values: np.ndarray,
              tombs: Optional[np.ndarray]) -> None:
        """Append one put/delete batch as a single stamp group. The
        owner has already validated slots/value-width — staging fails
        loudly at the call site, exactly like the unbatched path."""
        n = len(slots)
        k = self._k
        if n:
            self._grow_to(k + n)
            self._slots[k:k + n] = slots
            self._vals[k:k + n] = values
            self._tombs[k:k + n] = False if tombs is None else tombs
            self._group[k:k + n] = self._groups
            self._k = k + n
            # Overlay refresh is one bulk dict.update off vectorized
            # lanes — the per-row Python loop this replaces was the
            # staging hot path's last O(n)-interpreter cost, visible
            # once binop frames land whole client batches here.
            # zip(list, list) keeps insertion order, so the LAST
            # staged occurrence of a repeated slot wins, same as the
            # loop it replaces.
            pend = self._pending
            if tombs is None:
                pend.update(zip(slots.tolist(), values.tolist()))
            else:
                vals_obj = values.astype(object)
                vals_obj[np.asarray(tombs, bool)] = None
                pend.update(zip(slots.tolist(), vals_obj.tolist()))
        # An EMPTY batch still counts as a group: the unbatched path
        # spends one send per call regardless, so the flush stamps it
        # too — stats.puts and per-call stamp spacing stay uniform.
        self._groups += 1
        if self._k >= self._auto:
            self.flush("auto")

    # --- read-your-writes overlay ---

    def pending_value(self, slot: int):
        """``(staged, value)`` for the overlay: ``value`` is None for
        a staged tombstone (the same answer `get` gives for a
        committed one)."""
        if slot in self._pending:
            return True, self._pending[slot]
        return False, None

    def pending_slot_array(self) -> np.ndarray:
        """Distinct staged slots (for the count_modified_since
        overlay — staged rows commit at-or-after the canonical head,
        so they count as modified under any watermark bound)."""
        return np.fromiter(self._pending.keys(), np.int64,
                           count=len(self._pending))

    # --- commit ---

    def flush(self, trigger: str = "explicit") -> bool:
        """Stamp and commit every staged row as ONE device dispatch.

        Returns True when a commit was dispatched (False on an empty
        backlog). On a clock exception (drift/overflow from
        `Hlc.send_batch`) nothing is stamped or dispatched and the
        backlog stays staged — no write is silently dropped."""
        if self._groups == 0:
            return False
        k = self._k
        owner = self._owner
        from ..obs.trace import span
        node = str(owner.node_id)
        t0 = time.perf_counter()
        with span("ingest_flush", kind="ingest",
                  hlc=lambda: owner.canonical_time,
                  node=node, rows=k, trigger=trigger):
            # ONE wall read + one counter run for the whole backlog;
            # group g's stamp == the g'th sequential send under a
            # frozen clock, so batch (putAll) stamp-sharing and
            # strict cross-group monotonicity both hold.
            new_canonical, group_lts = Hlc.send_batch(
                owner.canonical_time, self._groups,
                millis=owner._wall_clock())
            t_stamp = time.perf_counter()
            d = 0
            if k:
                slots = self._slots[:k]
                lt = np.asarray(group_lts, np.int64)[self._group[:k]]
                vals = self._vals[:k]
                tombs = self._tombs[:k]
                # Duplicate staged slots collapse last-wins BEFORE the
                # scatter (XLA duplicate-index winner order is
                # backend-dependent); the last occurrence also carries
                # the dominating stamp, so this IS the LWW outcome.
                keep = owner._last_wins_keep(slots)
                if keep is not None:
                    slots, lt, vals, tombs = (slots[keep], lt[keep],
                                              vals[keep], tombs[keep])
                d = len(slots)
                # ONE dispatch, routed per backend: the owner picks
                # the touched-tile Mosaic kernel, the lax scatter, or
                # (sharded) a single shard_map program. Padding and
                # sentinel rows live with the route that needs them.
                owner._store = owner._commit_scatter(slots, lt, vals,
                                                     tombs)
                owner._store_escaped = False
            t_scatter = time.perf_counter()
            owner._canonical_time = new_canonical
            owner.stats.puts += self._groups
            owner.stats.records_put += k
            groups = self._groups
            self._k = 0
            self._groups = 0
            self._pending = {}
            self.flushes += 1
            self.rows_committed += d
            if d:
                self._emit_commit(slots, vals, tombs)
        dt = time.perf_counter() - t0
        self.last_phase_seconds = {"stamp": t_stamp - t0,
                                   "scatter": t_scatter - t_stamp}
        # Store-bytes census: lane nbytes is array metadata, no device
        # work — the commit is where the store's footprint last moved.
        from ..obs import device as _obs_device
        _obs_device.census(owner._held)
        flushes_c, rows_c, groups_c, seconds_h = _metrics()
        flushes_c.inc(trigger=trigger, node=node)
        rows_c.inc(d, node=node)
        groups_c.inc(groups, node=node)
        seconds_h.observe(dt, node=node)
        return True

    def _emit_commit(self, slots: np.ndarray, vals: np.ndarray,
                     tombs: np.ndarray) -> None:
        """Change events fire AT COMMIT, with the winning post-dedup
        value per slot — a slot staged twice in the window emits once,
        with the value the store actually holds (docs/INGEST.md)."""
        owner = self._owner
        hub = owner._hub
        if not hub.active:
            return
        sl = [int(s) for s in slots.tolist()]
        if owner._has_typed:
            # Typed lanes (counter/orset/mvreg) carry packed encodings;
            # subscribers must see the decoded committed value, same as
            # the unbatched emit paths (docs/TYPES.md).
            svals = [None if t else owner._watch_decode(s, int(v))
                     for s, v, t in zip(sl, vals.tolist(),
                                        tombs.tolist())]
        else:
            svals = [None if t else int(v)
                     for v, t in zip(vals.tolist(), tombs.tolist())]
        pos = {s: i for i, s in enumerate(sl)}
        # crdtlint: disable=add-batch-unique-keys -- slots are deduplicated last-wins by flush() before reaching here, so the batch repeats no key
        hub.add_batch(lambda: (sl, svals),
                      lambda q: ((True, svals[pos[q]])
                                 if isinstance(q, (int, np.integer))
                                 and q in pos else (False, None)))
