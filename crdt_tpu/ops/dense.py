"""Dense slot-aligned store + N-replica fan-in lattice join.

This is the TPU-native realization of the reference's replica merge
protocol (C9) at scale: instead of N replicas converging by N-1
sequential pairwise ``merge`` calls (crdt.dart:77-94, each O(n_remote)
with hash lookups), a *dense* changeset batch ``[R, N]`` — R replicas ×
N key slots — fans into the local store in one fused reduction:

1. **Replica reduce**: per key slot, the winning remote record is the
   lexicographic ``(lt, node)`` maximum over the R axis, with the
   LOWEST replica index winning exact ties — exactly what sequential
   pairwise merging produces (the first replica to merge a record wins;
   later identical records lose the local-wins-on-tie compare,
   crdt.dart:84).
2. **LWW vs local** (crdt.dart:83-84): strict ``(lt, node)`` compare so
   local wins exact ties.
3. **Clock absorption + guards** (crdt.dart:82, hlc.dart:80-97): the
   per-record ``Hlc.recv`` fold collapses to one max-reduction; the
   duplicate-node / drift guard masks are computed against the running
   canonical clock (exclusive cummax over the records in r-major
   order — the order a single sequential merge of the concatenated
   changesets would visit them), because recv's fast path skips the
   checks whenever the canonical clock is already ahead (hlc.dart:85).
4. **Re-stamp** (crdt.dart:86-87): winners keep the remote event hlc;
   ``modified`` lanes get the final canonical time.

Semantics note: on the *store lanes and canonical clock*,
``fanin_step`` ≡ ONE ``Crdt.merge`` of the conflict-resolved union of
the R changesets (ties to the lowest r) — differentially tested against
the scalar oracle in exactly that formulation. The *guard masks* are
stricter than a union merge: they visit EVERY record in r-major order
(like sequential merging, where recv runs for winners and losers alike,
crdt.dart:82), so a duplicate-node/drift record that would lose its
per-key conflict still trips — the conservative choice for a safety
check. Sequential pairwise merging additionally bumps the clock to wall
time between rounds (crdt.dart:93), which can shield later rounds'
records from the slow path; the fan-in evaluates all records against
one pre-bump running clock.

Values ride in an int64 ``val`` lane — either the scalar payload itself
or an index into a host-side payload table (SURVEY.md §7 hard part 4:
variable-length values never enter the reduction).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .merge import recv_guards
from ..obs import device as _obs_device

_NEG = -(2 ** 62)
_I32_NEG = -(2 ** 31)

# Dispatch-ledger registration (docs/OBSERVABILITY.md device plane):
# every host wrapper below reports its device dispatches; declaring the
# names at import time is what the crdtlint
# `dispatch-ledger-unregistered` gate verifies.
_obs_device.register(
    "dense.fanin_step", "dense.fanin_stream", "dense.sparse_fanin_step",
    "dense.wire_join_step", "dense.merge_repack_step",
    "dense.delta_mask", "dense.range_delta_mask",
    "dense.max_logical_time", "dense.put_scatter",
    "dense.record_scatter", "dense.delete_scatter",
    "dense.ingest_scatter", "dense.gc_purge", "dense.compact_remap")


class DenseStore(NamedTuple):
    """Key-slot-aligned columnar record store: slot i holds key i.

    The dense layout drops the host-side key<->slot dict of
    `ops.merge.Store` entirely — the natural fit for integer key spaces
    and for key-space sharding across a device mesh (`crdt_tpu.parallel`).
    """
    lt: jax.Array        # int64[N] record hlc logicalTime (0 = never set)
    node: jax.Array      # int32[N] record hlc node ordinal
    val: jax.Array       # int64[N] payload (scalar or host-table index)
    mod_lt: jax.Array    # int64[N] modified logicalTime (local-only lane)
    mod_node: jax.Array  # int32[N] modified node ordinal
    occupied: jax.Array  # bool[N]
    tomb: jax.Array      # bool[N] value is None (record.dart:17)

    @property
    def n_slots(self) -> int:
        return self.lt.shape[0]


class DenseChangeset(NamedTuple):
    """R replica changesets over the same N key slots, padded with
    ``valid=False``. Lane [r, k] is replica r's record for key k."""
    lt: jax.Array     # int64[R, N]
    node: jax.Array   # int32[R, N]
    val: jax.Array    # int64[R, N]
    tomb: jax.Array   # bool[R, N]
    valid: jax.Array  # bool[R, N]


class FaninResult(NamedTuple):
    new_canonical: jax.Array   # int64 scalar (pre final-send-bump)
    win_count: jax.Array       # int32 number of adopted records
    win: jax.Array             # bool[N] per-slot adopted mask (watch/C13)
    any_bad: jax.Array         # bool — some recv guard tripped
    first_bad: jax.Array       # flat r-major index of first offender
    #                            (int32 one-shot; int64 from streams)
    first_is_dup: jax.Array    # bool — duplicate-node (vs drift) there
    canonical_at_fail: jax.Array  # int64 canonical BEFORE failing record


def empty_dense_store(n_slots: int) -> DenseStore:
    return DenseStore(
        lt=jnp.zeros((n_slots,), jnp.int64),
        node=jnp.zeros((n_slots,), jnp.int32),
        val=jnp.zeros((n_slots,), jnp.int64),
        mod_lt=jnp.zeros((n_slots,), jnp.int64),
        mod_node=jnp.zeros((n_slots,), jnp.int32),
        occupied=jnp.zeros((n_slots,), bool),
        tomb=jnp.zeros((n_slots,), bool),
    )


def lex_fold(cs: DenseChangeset, lt: jax.Array, node: jax.Array,
             val: jax.Array, tomb: jax.Array
             ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                        jax.Array]:
    """Fold the replica rows into per-key running-best lanes via the
    strict lexicographic (lt, node) compare.

    Seeded with ``(lt, node, val, tomb)`` — the local store lanes (so
    the LWW join and the replica reduce fuse into one pass; local keeps
    exact ties because the compare is strict, crdt.dart:84) or ``_NEG``
    sentinels (pure reduce). Ties between replica rows go to the LOWEST
    index — sequential-merge parity (see module docstring). The row
    loop is Python-unrolled over the static R dimension: each row is
    one fused elementwise compare+select, no argmax/gather — the shape
    XLA tiles well on TPU, where int64 lanes are emulated and gather is
    expensive.

    Returns ``(lt, node, val, tomb, from_row)`` where ``from_row``
    marks keys whose running best came from a replica row."""
    from_row = jnp.zeros(lt.shape, bool)
    for r in range(cs.lt.shape[0]):
        lt_r = jnp.where(cs.valid[r], cs.lt[r], _NEG)
        # Mask node as well: at sentinel lt an invalid row must not win
        # the node tie-break against the sentinel seed.
        node_r = jnp.where(cs.valid[r], cs.node[r], _I32_NEG)
        better = (lt_r > lt) | ((lt_r == lt) & (node_r > node))
        lt = jnp.where(better, lt_r, lt)
        node = jnp.where(better, cs.node[r], node)
        val = jnp.where(better, cs.val[r], val)
        tomb = jnp.where(better, cs.tomb[r], tomb)
        from_row = from_row | better
    return lt, node, val, tomb, from_row


def reduce_replicas(cs: DenseChangeset) -> Tuple[jax.Array, jax.Array,
                                                 jax.Array, jax.Array,
                                                 jax.Array]:
    """Stable lexicographic (lt, node) max over the replica axis.

    Returns per-key ``(best_lt, best_node, best_val, best_tomb,
    any_valid)``; ties on (lt, node) go to the LOWEST replica index
    (sequential-merge parity — see module docstring). Keys with no
    valid record report ``best_lt == _NEG``/``best_node == _I32_NEG``."""
    n = cs.lt.shape[1]
    lt, node, val, tomb, any_valid = lex_fold(
        cs,
        jnp.full((n,), _NEG, cs.lt.dtype),
        jnp.full((n,), _I32_NEG, cs.node.dtype),
        jnp.zeros((n,), cs.val.dtype),
        jnp.zeros((n,), bool),
    )
    return lt, node, val, tomb, any_valid


@jax.jit
def _fanin_step_jit(store: DenseStore, cs: DenseChangeset,
                    canonical_lt: jax.Array, local_node: jax.Array,
                    wall_millis: jax.Array,
                    stamp_lt: Optional[jax.Array] = None
                    ) -> Tuple[DenseStore, FaninResult]:
    any_bad, first_bad, first_is_dup, canonical_at_fail = recv_guards(
        cs.lt, cs.node, cs.valid, canonical_lt, local_node, wall_millis)

    new_canonical = jnp.maximum(
        canonical_lt, jnp.max(jnp.where(cs.valid, cs.lt, _NEG)))
    stamp = new_canonical if stamp_lt is None else stamp_lt

    # Replica reduce + LWW join in ONE fused fold: seed the running best
    # with the local store lanes (empty slots as _NEG sentinels so any
    # valid remote beats them; occupied slots win exact ties because the
    # fold's compare is strict, crdt.dart:84).
    lt, node, val, tomb, win = lex_fold(
        cs,
        jnp.where(store.occupied, store.lt, _NEG),
        store.node, store.val, store.tomb)

    new_store = DenseStore(
        lt=jnp.where(win, lt, store.lt),
        node=jnp.where(win, node, store.node),
        val=val,
        mod_lt=jnp.where(win, stamp, store.mod_lt),
        mod_node=jnp.where(win, local_node, store.mod_node),
        occupied=store.occupied | win,
        tomb=tomb,
    )
    return new_store, FaninResult(
        new_canonical=new_canonical,
        win_count=jnp.sum(win).astype(jnp.int32),
        win=win,
        any_bad=any_bad,
        first_bad=first_bad,
        first_is_dup=first_is_dup,
        canonical_at_fail=canonical_at_fail,
    )


def fanin_step(store: DenseStore, cs: DenseChangeset,
               canonical_lt: jax.Array, local_node: jax.Array,
               wall_millis: jax.Array,
               stamp_lt: Optional[jax.Array] = None
               ) -> Tuple[DenseStore, FaninResult]:
    """One fused R-replica fan-in lattice join. See module docstring.

    ``stamp_lt`` overrides the ``modified`` stamp for winners (default:
    this step's post-absorption canonical). Streaming executors pass the
    whole stream's final canonical so chunked execution stays
    bit-identical to the one-shot join (crdt.dart:86-87 stamps winners
    with the canonical AFTER all records were absorbed)."""
    with _obs_device.record("dense.fanin_step", dim=cs.lt.shape[0]):
        return _fanin_step_jit(store, cs, canonical_lt, local_node,
                               wall_millis, stamp_lt)


@jax.jit
def _fanin_stream_jit(store: DenseStore, chunks: DenseChangeset,
                      canonical_lt: jax.Array, local_node: jax.Array,
                      wall_millis: jax.Array,
                      stamp_lt: Optional[jax.Array] = None
                      ) -> Tuple[DenseStore, FaninResult]:
    """Streaming fan-in over [C, Rc, N] chunked changesets via lax.scan.

    Replica counts too large for one resident [R, N] batch stream
    through in chunks; the store is the scan carry. With the default
    ``stamp_lt=None`` this is equivalent to C sequential ``fanin_step``
    merges (each chunk's winners stamped with that chunk's
    post-absorption canonical — the ``modified`` semantics sequential
    pairwise merging produces, crdt.dart:87). Passing the stream-final
    canonical as ``stamp_lt`` instead makes the result bit-identical to
    ONE fused join of all C×Rc rows (union semantics — what
    ``DenseCrdt.merge_many`` promises regardless of executor)."""

    chunk_size = chunks.lt.shape[1] * chunks.lt.shape[2]

    def step(carry, chunk):
        st, canon, offset, bad, fb, fd, caf, wins, winm = carry
        st2, res = _fanin_step_jit(st, chunk, canon, local_node,
                                   wall_millis, stamp_lt)
        # Keep the FIRST failure's diagnostics across chunks; first_bad
        # is reported as a GLOBAL flat r-major index across the whole
        # stream — int64: C*Rc*N exceeds int32 at exactly the scales
        # this streaming path exists for.
        keep_old = bad
        return (st2, res.new_canonical, offset + chunk_size,
                bad | res.any_bad,
                jnp.where(keep_old, fb,
                          offset + res.first_bad.astype(jnp.int64)),
                jnp.where(keep_old, fd, res.first_is_dup),
                jnp.where(keep_old, caf, res.canonical_at_fail),
                wins + res.win_count, winm | res.win), None

    init = (store, canonical_lt, jnp.int64(0),
            jnp.asarray(False), jnp.int64(0), jnp.asarray(False),
            jnp.int64(0), jnp.int32(0),
            jnp.zeros((store.n_slots,), bool))
    (st, canon, _, bad, fb, fd, caf, wins, winm), _ = jax.lax.scan(
        step, init, chunks)
    # Adopted-record accounting follows the stamping semantics: the
    # sequential mode (stamp_lt=None) counts a slot once per chunk that
    # re-won it, like C sequential merges would; the union mode counts
    # winning SLOTS from the final mask, like the one-shot join.
    win_count = (wins if stamp_lt is None
                 else jnp.sum(winm).astype(jnp.int32))
    return st, FaninResult(new_canonical=canon, win_count=win_count,
                           win=winm, any_bad=bad, first_bad=fb,
                           first_is_dup=fd, canonical_at_fail=caf)


def fanin_stream(store: DenseStore, chunks: DenseChangeset,
                 canonical_lt: jax.Array, local_node: jax.Array,
                 wall_millis: jax.Array,
                 stamp_lt: Optional[jax.Array] = None
                 ) -> Tuple[DenseStore, FaninResult]:
    """See `_fanin_stream_jit` — this host wrapper only adds the
    dispatch-ledger record (one dispatch per whole stream; the chunks
    scan inside it is a single program)."""
    with _obs_device.record("dense.fanin_stream",
                            dim=chunks.lt.shape[0] * chunks.lt.shape[1]):
        return _fanin_stream_jit(store, chunks, canonical_lt,
                                 local_node, wall_millis, stamp_lt)


def _sparse_fanin_body(store: DenseStore, slot: jax.Array,
                       lt: jax.Array, node: jax.Array, val: jax.Array,
                       tomb: jax.Array, valid: jax.Array,
                       stamp_lt: jax.Array, local_node: jax.Array
                       ) -> Tuple[DenseStore, jax.Array]:
    l_lt = store.lt.at[slot].get(mode="fill", fill_value=0)
    l_node = store.node.at[slot].get(mode="fill", fill_value=0)
    l_occ = store.occupied.at[slot].get(mode="fill", fill_value=False)

    # Strict (lt, node) compare: local wins exact ties (crdt.dart:84).
    remote_newer = (lt > l_lt) | ((lt == l_lt) & (node > l_node))
    win = valid & (~l_occ | remote_newer)

    target = jnp.where(win, slot, store.n_slots).astype(jnp.int32)
    k = slot.shape[0]
    new_store = DenseStore(
        lt=store.lt.at[target].set(lt, mode="drop"),
        node=store.node.at[target].set(node, mode="drop"),
        val=store.val.at[target].set(val, mode="drop"),
        mod_lt=store.mod_lt.at[target].set(
            jnp.zeros((k,), jnp.int64) + stamp_lt, mode="drop"),
        mod_node=store.mod_node.at[target].set(
            jnp.zeros((k,), jnp.int32) + local_node, mode="drop"),
        occupied=store.occupied.at[target].set(True, mode="drop"),
        tomb=store.tomb.at[target].set(tomb, mode="drop"),
    )
    return new_store, win


def _wire_join_body(store: DenseStore, lt: jax.Array, node: jax.Array,
                    val: jax.Array, tomb: jax.Array, valid: jax.Array,
                    stamp_lt: jax.Array, local_node: jax.Array
                    ) -> Tuple[DenseStore, jax.Array]:
    lt = jnp.where(valid, lt, _NEG)
    node = node.astype(jnp.int32)
    val = val.astype(jnp.int64)
    # Strict (lt, node) compare: local wins exact ties (crdt.dart:84).
    remote_newer = ((lt > store.lt) |
                    ((lt == store.lt) & (node > store.node)))
    win = valid & (~store.occupied | remote_newer)
    new_store = DenseStore(
        lt=jnp.where(win, lt, store.lt),
        node=jnp.where(win, node, store.node),
        val=jnp.where(win, val, store.val),
        mod_lt=jnp.where(win, stamp_lt, store.mod_lt),
        mod_node=jnp.where(win, local_node, store.mod_node),
        occupied=store.occupied | win,
        tomb=jnp.where(win, tomb, store.tomb),
    )
    return new_store, win


# Jit-cached merge entry points, keyed on (donate, sharding) like the
# local-write scatters below: donating the old store lets XLA update
# the O(n_slots) lanes in place for an O(k) delta (on backends that
# honor donation), and the sharding constraint pins a sharded model's
# merge output onto its key-axis layout — without it XLA picks, and
# every sharded merge pays a full-store re-shard copy on top of the
# multi-chip dispatch floor (docs/PERF.md MULTICHIP_SCALE_r05).

import functools as _ft


@_ft.lru_cache(maxsize=None)
def _sparse_fanin_jit(donate: bool, sharding=None):
    def step(store, slot, lt, node, val, tomb, valid, stamp_lt,
             local_node):
        new_store, win = _sparse_fanin_body(
            store, slot, lt, node, val, tomb, valid, stamp_lt,
            local_node)
        if sharding is not None:
            new_store = jax.lax.with_sharding_constraint(new_store,
                                                         sharding)
        return new_store, win
    return jax.jit(step, donate_argnums=(0,) if donate else ())


@_ft.lru_cache(maxsize=None)
def _wire_join_jit(donate: bool, sharding=None):
    def step(store, lt, node, val, tomb, valid, stamp_lt, local_node):
        new_store, win = _wire_join_body(store, lt, node, val, tomb,
                                         valid, stamp_lt, local_node)
        if sharding is not None:
            new_store = jax.lax.with_sharding_constraint(new_store,
                                                         sharding)
        return new_store, win
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def sparse_fanin_step(store: DenseStore, slot: jax.Array, lt: jax.Array,
                      node: jax.Array, val: jax.Array, tomb: jax.Array,
                      valid: jax.Array, stamp_lt: jax.Array,
                      local_node: jax.Array, *, donate: bool = False,
                      sharding=None) -> Tuple[DenseStore, jax.Array]:
    """O(k) slot-indexed scatter join of a k-record delta into an
    N-slot store — the wire-delta shape (a 10-record JSON sync into a
    1M-slot replica must not materialize 1M-wide lanes).

    Clock absorption and recv guards are the CALLER's job (run
    host-side in the payload's visit order, crdt.dart:80-85, before
    invoking); ``stamp_lt`` is the post-absorption canonical that
    winners' ``modified`` lanes take (crdt.dart:86-87). Slots must be
    unique (a dict-keyed delta guarantees it). ``donate`` hands the old
    store buffers to XLA (caller must not reuse them); ``sharding``
    pins the output layout. Returns ``(new_store, win)`` with ``win``
    over the k entries."""
    with _obs_device.record("dense.sparse_fanin_step",
                            dim=slot.shape[0],
                            donated=store.lt if donate else None):
        return _sparse_fanin_jit(donate, sharding)(
            store, slot, lt, node, val, tomb, valid, stamp_lt,
            local_node)


def wire_join_step(store: DenseStore, lt: jax.Array, node: jax.Array,
                   val: jax.Array, tomb: jax.Array, valid: jax.Array,
                   stamp_lt: jax.Array, local_node: jax.Array, *,
                   donate: bool = False, sharding=None
                   ) -> Tuple[DenseStore, jax.Array]:
    """Elementwise N-wide join of a SLOT-ALIGNED wire delta (lane i is
    slot i's record, ``valid`` masking absent slots) — the large-k
    companion of `sparse_fanin_step`: no gather, no scatter (TPU
    scatters serialize per index; at k ≈ n_slots the elementwise form
    is >10× faster), just one fused compare/select sweep.

    Clock absorption and recv guards are the CALLER's job (the host
    recv fold, crdt.dart:80-85); ``stamp_lt`` is the post-absorption
    canonical for winners' ``modified`` lanes (crdt.dart:86-87).
    ``node`` may arrive int16 and ``val`` int32 (narrow wire
    transfers); both widen in-jit, so the host→device bytes shrink
    without touching the compare semantics. ``donate``/``sharding``
    follow `sparse_fanin_step`. Returns ``(new_store, win)`` with
    ``win`` over the N slots."""
    with _obs_device.record("dense.wire_join_step", dim=lt.shape[0],
                            donated=store.lt if donate else None):
        return _wire_join_jit(donate, sharding)(
            store, lt, node, val, tomb, valid, stamp_lt, local_node)


@_ft.lru_cache(maxsize=None)
def _merge_repack_jit(donate: bool, sharding=None):
    def step(store, slot, lt, node, val, tomb, valid, stamp_lt,
             local_node, since_lt):
        new_store, win = _sparse_fanin_body(
            store, slot, lt, node, val, tomb, valid, stamp_lt,
            local_node)
        if sharding is not None:
            new_store = jax.lax.with_sharding_constraint(new_store,
                                                         sharding)
        mask = new_store.occupied & (new_store.mod_lt >= since_lt)
        return new_store, win, mask
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def merge_repack_step(store: DenseStore, slot: jax.Array, lt: jax.Array,
                      node: jax.Array, val: jax.Array, tomb: jax.Array,
                      valid: jax.Array, stamp_lt: jax.Array,
                      local_node: jax.Array, since_lt: jax.Array, *,
                      donate: bool = False, sharding=None
                      ) -> Tuple[DenseStore, jax.Array, jax.Array]:
    """`sparse_fanin_step` fused with the NEXT pack's delta mask — the
    gossip relay op: merging a peer's delta and computing
    ``occupied & (mod_lt >= since_lt)`` over the post-merge store in
    ONE program replaces the two dispatches (merge, then
    `dense_delta_mask` on the following `pack_since` miss) a relay
    round otherwise pays. Same caller contract as `sparse_fanin_step`;
    ``since_lt`` is the watermark the next outbound pack will be
    bounded by (inclusive, map_crdt.dart:44-45). Returns
    ``(new_store, win, mask)`` with ``mask`` over the N slots."""
    with _obs_device.record("dense.merge_repack_step",
                            dim=slot.shape[0],
                            donated=store.lt if donate else None):
        return _merge_repack_jit(donate, sharding)(
            store, slot, lt, node, val, tomb, valid, stamp_lt,
            local_node, since_lt)


@jax.jit
def _delta_mask_jit(store: DenseStore, since_lt: jax.Array) -> jax.Array:
    return store.occupied & (store.mod_lt >= since_lt)


def dense_delta_mask(store: DenseStore, since_lt: jax.Array) -> jax.Array:
    """modifiedSince filter — INCLUSIVE bound on the modified lane
    (map_crdt.dart:44-45)."""
    with _obs_device.record("dense.delta_mask", dim=store.lt.shape[0]):
        return _delta_mask_jit(store, since_lt)


@_ft.lru_cache(maxsize=None)
def _range_mask_jit():
    def step(store: DenseStore, since_lt: jax.Array, los: jax.Array,
             his: jax.Array) -> jax.Array:
        base = store.occupied & (store.mod_lt >= since_lt)
        idx = jnp.arange(store.lt.shape[0], dtype=jnp.int64)
        in_range = jnp.any((idx[None, :] >= los[:, None])
                           & (idx[None, :] < his[:, None]), axis=0)
        return base & in_range

    return jax.jit(step)


def dense_range_delta_mask(store: DenseStore, since_lt: jax.Array,
                           los: jax.Array, his: jax.Array) -> jax.Array:
    """`dense_delta_mask` restricted to a union of half-open slot
    spans ``[los[i], his[i])`` — the anti-entropy range pack
    (docs/ANTIENTROPY.md): after a Merkle walk localizes divergence to
    a few leaf ranges, only those slots feed the pack. Callers pad the
    span arrays to a power-of-two length with empty ``lo == hi == 0``
    spans so the jit cache sees O(log) distinct shapes. Pass
    ``since_lt = 0`` for a clock-unbounded range scan (every occupied
    slot has ``mod_lt > 0``, so 0 never filters)."""
    with _obs_device.record("dense.range_delta_mask",
                            dim=los.shape[0]):
        return _range_mask_jit()(store, since_lt, los, his)


@jax.jit
def _max_logical_time_jit(store: DenseStore) -> jax.Array:
    return jnp.max(jnp.where(store.occupied, store.lt, 0))


def dense_max_logical_time(store: DenseStore) -> jax.Array:
    """refreshCanonicalTime's reduction (crdt.dart:114-121)."""
    with _obs_device.record("dense.max_logical_time",
                            dim=store.lt.shape[0]):
        return _max_logical_time_jit(store)


def pad_replica_rows(cs: DenseChangeset, multiple: int) -> DenseChangeset:
    """Pad the replica axis with ``valid=False`` rows (all-zero lanes)
    up to a multiple — shared by the streamed and sharded executors so
    padding semantics can't diverge."""
    pad = (-cs.lt.shape[0]) % multiple
    if not pad:
        return cs
    return DenseChangeset(*(
        jnp.concatenate([lane, jnp.zeros((pad,) + lane.shape[1:],
                                         lane.dtype)])
        for lane in cs))


def store_to_changeset(store: DenseStore,
                       since_lt: Optional[jax.Array] = None
                       ) -> DenseChangeset:
    """Export a store as a 1-replica changeset (the outbound half of the
    anti-entropy round, crdt.dart:124-135): full state, or the delta of
    records with ``modified >= since_lt``."""
    valid = (store.occupied if since_lt is None
             else dense_delta_mask(store, since_lt))
    return DenseChangeset(lt=store.lt[None], node=store.node[None],
                          val=store.val[None], tomb=store.tomb[None],
                          valid=valid[None])


# --- local-write scatters (putAll/delete, crdt.dart:46-58) ---
#
# One fused jit per batch shape instead of seven eager `.at[].set`
# dispatches, with store-buffer donation where the backend supports it
# (TPU; CPU ignores donation with a warning, so the caller picks) —
# a local write into an n-slot store must not copy n-wide lanes.

import functools as _functools


# ``sharding``: optional NamedSharding pinned onto the OUTPUT store
# inside the jit (with_sharding_constraint) — a sharded model's local
# write then lands already laid out, instead of XLA choosing and the
# model paying a full-store re-shard copy afterwards.
@_functools.lru_cache(maxsize=None)
def _put_scatter(donate: bool, sharding=None):
    # mode="drop": `DenseCrdt.put_batch` pads the batch to a power of
    # two with slot == n_slots sentinels (stable jit shapes).
    def step(store: DenseStore, slots, values, tombs, t, me) -> DenseStore:
        out = DenseStore(
            lt=store.lt.at[slots].set(t, mode="drop"),
            node=store.node.at[slots].set(me, mode="drop"),
            val=store.val.at[slots].set(values, mode="drop"),
            mod_lt=store.mod_lt.at[slots].set(t, mode="drop"),
            mod_node=store.mod_node.at[slots].set(me, mode="drop"),
            occupied=store.occupied.at[slots].set(True, mode="drop"),
            tomb=store.tomb.at[slots].set(tombs, mode="drop"),
        )
        if sharding is not None:
            out = jax.lax.with_sharding_constraint(out, sharding)
        return out
    return jax.jit(step, donate_argnums=(0,) if donate else ())


@_functools.lru_cache(maxsize=None)
def _record_scatter(donate: bool, sharding=None):
    # mode="drop": callers pad the batch to a power of two with
    # slot == n_slots sentinels (stable jit shapes); those rows must
    # scatter nowhere.
    def step(store: DenseStore, slots, lt, node, val, mod_lt, mod_node,
             tomb) -> DenseStore:
        out = DenseStore(
            lt=store.lt.at[slots].set(lt, mode="drop"),
            node=store.node.at[slots].set(node, mode="drop"),
            val=store.val.at[slots].set(val, mode="drop"),
            mod_lt=store.mod_lt.at[slots].set(mod_lt, mode="drop"),
            mod_node=store.mod_node.at[slots].set(mod_node, mode="drop"),
            occupied=store.occupied.at[slots].set(True, mode="drop"),
            tomb=store.tomb.at[slots].set(tomb, mode="drop"),
        )
        if sharding is not None:
            out = jax.lax.with_sharding_constraint(out, sharding)
        return out
    return jax.jit(step, donate_argnums=(0,) if donate else ())


@_functools.lru_cache(maxsize=None)
def _delete_scatter(donate: bool, sharding=None):
    def step(store: DenseStore, slots, t, me) -> DenseStore:
        out = DenseStore(
            lt=store.lt.at[slots].set(t),
            node=store.node.at[slots].set(me),
            val=store.val,
            mod_lt=store.mod_lt.at[slots].set(t),
            mod_node=store.mod_node.at[slots].set(me),
            occupied=store.occupied.at[slots].set(True),
            tomb=store.tomb.at[slots].set(True),
        )
        if sharding is not None:
            out = jax.lax.with_sharding_constraint(out, sharding)
        return out
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def put_scatter(store: DenseStore, slots, values, t, me, tombs=None,
                donate: bool = False, sharding=None) -> DenseStore:
    """Batch put: scatter one shared HLC + values at ``slots``.
    ``tombs`` marks entries written as tombstones under the SAME batch
    stamp (a mixed putAll, crdt.dart:46-54 + delete-as-put-None)."""
    if tombs is None:
        tombs = jnp.zeros(values.shape, bool)
    with _obs_device.record("dense.put_scatter", dim=slots.shape[0],
                            donated=store.lt if donate else None):
        return _put_scatter(donate, sharding)(store, slots, values,
                                              tombs, t, me)


def record_scatter(store: DenseStore, slots, lt, node, val, mod_lt,
                   mod_node, tomb, donate: bool = False,
                   sharding=None) -> DenseStore:
    """Raw record writes preserving the given hlc/modified stamps —
    the putRecords storage primitive (crdt.dart:151-155): stores
    records verbatim, no LWW compare, no clock involvement."""
    with _obs_device.record("dense.record_scatter", dim=slots.shape[0],
                            donated=store.lt if donate else None):
        return _record_scatter(donate, sharding)(store, slots, lt,
                                                 node, val, mod_lt,
                                                 mod_node, tomb)


def delete_scatter(store: DenseStore, slots, t, me,
                   donate: bool = False, sharding=None) -> DenseStore:
    """Batch tombstone: scatter one shared HLC at ``slots``."""
    with _obs_device.record("dense.delete_scatter", dim=slots.shape[0],
                            donated=store.lt if donate else None):
        return _delete_scatter(donate, sharding)(store, slots, t, me)


@_functools.lru_cache(maxsize=None)
def _ingest_scatter(donate: bool, sharding=None):
    # mode="drop": the write combiner pads its flush lanes to a power
    # of two with slot == n_slots sentinels (stable jit shapes), same
    # trick as record_scatter.
    def step(store: DenseStore, slots, lt, val, tomb, me) -> DenseStore:
        out = DenseStore(
            lt=store.lt.at[slots].set(lt, mode="drop"),
            node=store.node.at[slots].set(me, mode="drop"),
            val=store.val.at[slots].set(val, mode="drop"),
            mod_lt=store.mod_lt.at[slots].set(lt, mode="drop"),
            mod_node=store.mod_node.at[slots].set(me, mode="drop"),
            occupied=store.occupied.at[slots].set(True, mode="drop"),
            tomb=store.tomb.at[slots].set(tomb, mode="drop"),
        )
        if sharding is not None:
            out = jax.lax.with_sharding_constraint(out, sharding)
        return out
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def ingest_scatter(store: DenseStore, slots, lt, val, tomb, me,
                   donate: bool = False, sharding=None) -> DenseStore:
    """Fused write-combiner commit: like `put_scatter` but with a
    PER-ROW hlc lane (each staged group carries its own batch stamp
    from `Hlc.send_batch`) and mixed put/tombstone rows in one
    program. Writer attribution (``me``) and the modified stamps
    (``mod_lt = lt`` for local writes) broadcast in-jit, so the host
    ships 4 lanes per flush instead of `record_scatter`'s 7. One jit
    per (donate, sharding) pair; ``sharding`` pins the output store's
    NamedSharding so sharded commits land rows shard-locally."""
    with _obs_device.record("dense.ingest_scatter", dim=slots.shape[0],
                            donated=store.lt if donate else None):
        return _ingest_scatter(donate, sharding)(store, slots, lt, val,
                                                 tomb, me)


# --- tombstone epoch GC + online compaction (docs/STORAGE.md) ---
#
# Dense slots never reclaim on their own: a tombstone is lattice state
# (the delete must dominate concurrent writes), so it can only leave
# the store once the fleet stability watermark proves every peer's
# durable state already dominates it. `gc_purge` masks those stable
# tombstones out of every lane in one dispatch; `compact_remap` then
# spends the reclaimed slots, packing survivors to a dense prefix and
# rebuilding the digest tree in the same program. Both follow the
# (donate, sharding) factory idiom of the merge kernels above.


@_functools.lru_cache(maxsize=None)
def _gc_purge_jit(donate: bool, sharding=None):
    def step(store: DenseStore, floor_lt):
        purged = store.occupied & store.tomb & (store.lt <= floor_lt)
        keep = ~purged
        z64 = jnp.int64(0)
        z32 = jnp.int32(0)
        out = DenseStore(
            lt=jnp.where(keep, store.lt, z64),
            node=jnp.where(keep, store.node, z32),
            val=jnp.where(keep, store.val, z64),
            mod_lt=jnp.where(keep, store.mod_lt, z64),
            mod_node=jnp.where(keep, store.mod_node, z32),
            occupied=store.occupied & keep,
            tomb=store.tomb & keep,
        )
        if sharding is not None:
            out = jax.lax.with_sharding_constraint(out, sharding)
        return out, jnp.sum(purged).astype(jnp.int32), purged
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def gc_purge(store: DenseStore, floor_lt, *, donate: bool = False,
             sharding=None) -> Tuple[DenseStore, jax.Array, jax.Array]:
    """Epoch tombstone purge: zero EVERY lane of tombstones whose
    record stamp is at or below ``floor_lt`` (inclusive — a durable
    watermark means delivered THROUGH the stamp) — ONE elementwise
    dispatch, no gather, no scatter.

    ``floor_lt`` must derive from a fleet stability watermark (every
    peer's durable watermark past the delete stamp, minus the HLC
    drift allowance — `GossipNode.stability_hlc`); the crdtlint
    ``purge-watermark-unfenced`` rule rejects call sites that invent
    one locally. A purged slot returns to the all-zero never-written
    state, so the caller must also arm its merge-side resurrection
    floor (`DenseCrdt.gc_purge`) — the kernel alone cannot stop a
    delayed pre-purge delta from re-occupying the slot. Returns
    ``(new_store, purged_count, purged_mask)``; the mask stays on
    device unless the caller (sanitizer, sem-column owner) fetches
    it."""
    with _obs_device.record("dense.gc_purge", dim=store.lt.shape[0],
                            donated=store.lt if donate else None):
        return _gc_purge_jit(donate, sharding)(store, floor_lt)


@_functools.lru_cache(maxsize=None)
def _compact_remap_jit(donate: bool, leaf_width: int, has_sem: bool,
                       sharding=None):
    # Imported here (not at module top): ops/digest.py imports
    # DenseStore from this module.
    from .digest import digest_levels_from_lanes

    def step(store: DenseStore, los, his, *sem):
        n = store.lt.shape[0]
        idx = jnp.arange(n, dtype=jnp.int64)
        # [S, N] span membership; spans are half-open, non-overlapping
        # (host-validated), padded to a power of two with lo == hi == 0
        # like dense_range_delta_mask.
        in_span = ((idx[None, :] >= los[:, None])
                   & (idx[None, :] < his[:, None]))
        keep = store.occupied
        k_in = in_span & keep[None, :]
        # Survivor rank within each span: running count along the slot
        # axis. Each slot is in at most one span, so summing the
        # masked per-span targets recovers its destination.
        rank = jnp.cumsum(k_in.astype(jnp.int64), axis=1)
        pos = los[:, None] + rank - 1
        tgt_in = jnp.sum(jnp.where(k_in, pos, 0), axis=0)
        moved = jnp.any(in_span, axis=0) & keep
        new_slot = jnp.where(moved, tgt_in, idx)
        translation = jnp.where(keep, new_slot, -1).astype(jnp.int32)
        # mode="drop": dropped rows target the out-of-range sentinel n,
        # same trick as record_scatter's padding.
        target = jnp.where(keep, new_slot, n).astype(jnp.int32)

        def scat(lane):
            return jnp.zeros(lane.shape, lane.dtype).at[target].set(
                lane, mode="drop")

        out = DenseStore(
            lt=scat(store.lt), node=scat(store.node),
            val=scat(store.val), mod_lt=scat(store.mod_lt),
            mod_node=scat(store.mod_node),
            occupied=scat(store.occupied), tomb=scat(store.tomb))
        new_sem = scat(sem[0]) if has_sem else None
        if sharding is not None:
            out = jax.lax.with_sharding_constraint(out, sharding)
        live = jnp.sum(keep.astype(jnp.int32))
        levels = digest_levels_from_lanes(
            out.lt, out.val, out.tomb, out.occupied, sem=new_sem,
            leaf_width=leaf_width)
        if has_sem:
            return out, new_sem, translation, live, levels
        return out, translation, live, levels
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def compact_remap(store: DenseStore, los, his, sem=None, *,
                  leaf_width: int, donate: bool = False, sharding=None):
    """Online compaction: remap surviving rows to the dense prefix of
    their span AND rebuild the digest-tree levels in ONE donated
    dispatch. ``(los, his)`` are half-open, non-overlapping slot spans
    (power-of-two padded with empty ``lo == hi == 0`` spans); rows
    outside every span keep their slot, so per-partition/per-shard
    compaction is range-preserving by construction. ``sem`` is the
    optional per-slot semantics tag column, remapped with the rows so
    typed lanes keep their kernels.

    Returns ``(new_store[, new_sem], translation, live_count,
    digest_levels)`` — ``translation[old] = new`` (int32, ``-1`` for
    unoccupied slots) is what the host layers rewrite against:
    `KeyedDenseCrdt`'s intern map and the routing layer's range arcs.
    Slot identity is wire identity, so a full-store remap is only
    externally safe for single-owner stores or when every replica
    applies the identical translation (docs/STORAGE.md)."""
    with _obs_device.record("dense.compact_remap",
                            dim=store.lt.shape[0],
                            donated=store.lt if donate else None):
        if sem is not None:
            return _compact_remap_jit(donate, leaf_width, True,
                                      sharding)(store, los, his, sem)
        return _compact_remap_jit(donate, leaf_width, False,
                                  sharding)(store, los, his)
