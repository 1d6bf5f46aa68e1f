"""Sharded replica fan-in over a device mesh — C9/C10 on ICI/DCN.

The reference's replication machinery is N replicas converging by
pairwise JSON exchange over an application transport
(crdt.dart:77-135, crdt_json.dart:8-37). The TPU-native equivalent
maps both onto a 2-D ``jax.sharding.Mesh``:

- **key axis** — the record store's key slots are sharded across
  devices (the scale axis of this framework is keys × replicas,
  SURVEY.md §5: the "context" being the record store). Each key shard
  is replicated across the replica axis.
- **replica axis** — incoming replica changesets are sharded across
  devices; the per-key lattice join fans in over this axis with XLA
  collectives riding ICI (cross-slice replica groups ride DCN when the
  mesh spans slices — same code, the mesh shape decides).

The cross-device reduction is a **lexicographic (lt, node) max**, which
ICI reductions don't provide natively (SURVEY.md §5); it is composed
from primitive collectives:

1. ``pmax`` of the per-device best ``lt``;
2. ``pmax`` of ``node`` masked to devices holding that ``lt`` —
   node-ordinal tie-break (hlc.dart:158-161);
3. ``pmin`` of the replica-axis rank masked to devices holding the
   winning ``(lt, node)`` — stable lowest-rank tie on identical HLCs
   (sequential-merge parity, see ops/dense.py);
4. one-hot ``psum`` to broadcast the winner's payload/tombstone lanes.

Guard semantics (documented difference from the single-device path):
``Hlc.recv``'s fast-path shielding (hlc.dart:85) is evaluated per
device block — the running canonical clock cummaxes over the records
*this device* visits, seeded with the pre-merge canonical time. Records
on one device do not shield records on another, so the sharded guards
are strictly more sensitive than the r-major sequential visit (they can
only flag a superset). Store lanes and the canonical clock are
bit-identical to the single-device ``fanin_step`` either way; detailed
first-offender diagnostics come from the single-device path.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import functools

import jax
import jax.numpy as jnp

from ..obs import device as _obs_device

_obs_device.register(
    "parallel.sharded_fanin", "parallel.sharded_pallas_fanin",
    "parallel.sharded_ingest", "parallel.sharded_digest",
    "parallel.sharded_delta_mask", "parallel.sharded_max_logical_time",
    "parallel.sharded_compact")
from jax import P
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding

from ..hlc import MAX_COUNTER, MAX_DRIFT, SHIFT
from ..ops.dense import (DenseChangeset, DenseStore, reduce_replicas,
                         _NEG, _I32_NEG)
from ..ops.merge import recv_guards

REPLICA_AXIS = "replica"
KEY_AXIS = "key"
# Optional outer replica axis for multi-slice meshes: collectives over
# ("slice", "replica") decompose into an ICI reduction within each
# slice and a DCN exchange across slices — XLA picks the decomposition
# from the mesh's device layout; the fan-in code is identical.
SLICE_AXIS = "slice"

# Plain int (not a jnp scalar): a module-level concrete array would
# initialize the jax backend at import time, foreclosing the platform
# selection entry points need to do first.
_BIG_RANK = 2 ** 30


def pmax_i64(x: jax.Array, axes) -> jax.Array:
    """``jax.lax.pmax`` of an int64 array as two int32 all-reduces: the
    TPU lowers only the sum of a 64-bit all-reduce. The hi words
    reduce first; the lo words (biased so that signed int32 order is
    their unsigned order) reduce masked to the members holding the
    winning hi word. Bit-identical to the int64 pmax."""
    hi = (x >> 32).astype(jnp.int32)
    bias = jnp.uint32(0x80000000)
    lo = jax.lax.bitcast_convert_type(x.astype(jnp.uint32) ^ bias,
                                      jnp.int32)
    g_hi = jax.lax.pmax(hi, axes)
    g_lo = jax.lax.pmax(jnp.where(hi == g_hi, lo, _I32_NEG), axes)
    lo_u = jax.lax.bitcast_convert_type(g_lo, jnp.uint32) ^ bias
    return (g_hi.astype(jnp.int64) << 32) | lo_u.astype(jnp.int64)


class ShardedFaninResult(NamedTuple):
    new_canonical: jax.Array  # int64 scalar (pre final-send-bump)
    win_count: jax.Array      # int32 adopted records across all shards
    win: jax.Array            # bool[N] per-slot adopted mask (key-sharded)
    any_bad: jax.Array        # bool — some recv guard tripped
    any_dup: jax.Array        # bool — a duplicate-node guard tripped
    any_drift: jax.Array      # bool — a drift guard tripped


def _make_mesh(shape: tuple, axis_names: tuple, devices) -> Mesh:
    import numpy as np
    devices = np.asarray(devices if devices is not None else jax.devices())
    want = int(np.prod(shape))
    assert devices.size == want, (
        f"{devices.size} devices != "
        + "×".join(str(s) for s in shape))
    return Mesh(devices.reshape(shape), axis_names)


def make_fanin_mesh(n_replica_shards: int, n_key_shards: int,
                    devices=None) -> Mesh:
    """A (replica, key) mesh over the given/default devices."""
    return _make_mesh((n_replica_shards, n_key_shards),
                      (REPLICA_AXIS, KEY_AXIS), devices)


def make_multislice_fanin_mesh(n_slices: int, n_replica_shards: int,
                               n_key_shards: int, devices=None) -> Mesh:
    """A (slice, replica, key) mesh for multi-slice deployments.

    The replica fan-in runs over ``(slice, replica)`` jointly: the
    inner axis reduces over ICI within each slice, the outer over DCN
    across slices (scaling-book recipe — the mesh's device layout
    decides which hops each collective takes). Pass the device array
    slice-major so the outer axis really is the DCN boundary.
    """
    return _make_mesh((n_slices, n_replica_shards, n_key_shards),
                      (SLICE_AXIS, REPLICA_AXIS, KEY_AXIS), devices)


def _replica_axes(mesh: Mesh) -> tuple:
    """Every mesh axis except the key axis fans replicas in, in mesh
    order — ('replica',) on a 2-D mesh, ('slice', 'replica') on a
    multi-slice mesh."""
    return tuple(n for n in mesh.axis_names if n != KEY_AXIS)


def replica_extent(mesh: Mesh) -> int:
    """Total replica shards = product of every non-key axis size; the
    changeset's R dim must pad to a multiple of this before
    `shard_changeset`."""
    extent = 1
    for a in _replica_axes(mesh):
        extent *= mesh.shape[a]
    return extent


@functools.lru_cache(maxsize=None)
def store_sharding(mesh: Mesh) -> NamedSharding:
    """Store lanes: sharded over keys, replicated over the replica
    (and slice, if present) axes.

    Cached per mesh (Mesh is hashable): the write fast lane asks for
    this on EVERY commit (`DenseCrdt._write_sharding` feeds the
    scatter jit cache key), so the precomputed NamedSharding is
    handed back instead of re-built per flush."""
    return NamedSharding(mesh, P(KEY_AXIS))


@functools.lru_cache(maxsize=None)
def changeset_sharding(mesh: Mesh) -> NamedSharding:
    """Changeset lanes [R, N]: replicas × keys over the full mesh (the
    R dim spans every replica axis on a multi-slice mesh). Cached per
    mesh, like `store_sharding`."""
    return NamedSharding(mesh, P(_replica_axes(mesh), KEY_AXIS))


def shard_store(store: DenseStore, mesh: Mesh) -> DenseStore:
    s = store_sharding(mesh)
    return DenseStore(*(jax.device_put(lane, s) for lane in store))


def shard_changeset(cs: DenseChangeset, mesh: Mesh) -> DenseChangeset:
    s = changeset_sharding(mesh)
    return DenseChangeset(*(jax.device_put(lane, s) for lane in cs))


def _fanin_block(replica_axes: tuple, store: DenseStore,
                 cs: DenseChangeset, canonical_lt: jax.Array,
                 local_node: jax.Array, wall_millis: jax.Array
                 ) -> Tuple[DenseStore, ShardedFaninResult]:
    """Per-device body under shard_map: local reduce, then the
    lexicographic max fan-in over the replica axes (one axis on a flat
    mesh; (slice, replica) on a multi-slice mesh — ICI inside a slice,
    DCN across)."""
    all_axes = replica_axes + (KEY_AXIS,)
    # --- per-device guards (see module docstring for semantics) ---
    # The three flags ride ONE two-lane pmax (lane 0 dup, lane 1
    # drift); exception payloads come from the model's exact host-side
    # recompute on the failure path, not from here.
    any_bad, first_bad, first_is_dup, _ = recv_guards(
        cs.lt, cs.node, cs.valid, canonical_lt, local_node, wall_millis)
    flags = jnp.stack([(any_bad & first_is_dup).astype(jnp.int32),
                       (any_bad & ~first_is_dup).astype(jnp.int32)])
    flags = jax.lax.pmax(flags, all_axes)
    any_dup = flags[0] > 0
    any_drift = flags[1] > 0
    any_bad = any_dup | any_drift

    # --- local replica reduce on this device's [R_blk, N_blk] block ---
    best_lt, best_node, best_val, best_tomb, any_valid = reduce_replicas(cs)
    best_lt = jnp.where(any_valid, best_lt, _NEG)
    best_node = jnp.where(any_valid, best_node, _I32_NEG)

    # --- cross-device lexicographic (lt, node) max over the replica
    # axes: pmax lt → masked pmax node → stable pmin rank → one-hot psum
    # of the winner's payload lanes. ICI within a slice, DCN across. ---
    m1 = pmax_i64(best_lt, replica_axes)
    node_cand = jnp.where(best_lt == m1, best_node, _I32_NEG)
    m2 = jax.lax.pmax(node_cand, replica_axes)
    has = (best_lt == m1) & (best_node == m2)
    rank = _flat_rank(replica_axes)
    winner_rank = jax.lax.pmin(jnp.where(has, rank, _BIG_RANK),
                               replica_axes)
    mine = has & (rank == winner_rank)
    g_val = jax.lax.psum(jnp.where(mine, best_val, 0), replica_axes)
    g_tomb = jax.lax.psum(jnp.where(mine, best_tomb, False
                                    ).astype(jnp.int32), replica_axes) > 0
    g_any = jax.lax.pmax(any_valid.astype(jnp.int32), replica_axes) > 0

    # --- canonical absorption: global max over every record seen ---
    new_canonical = jnp.maximum(
        canonical_lt,
        pmax_i64(jnp.max(jnp.where(g_any, m1, _NEG)), all_axes))

    # --- LWW vs the local key shard (strict: local wins exact ties,
    # crdt.dart:84). Identical on every device of a key column, so the
    # replicated store stays consistent without further collectives. ---
    remote_newer = ((m1 > store.lt) |
                    ((m1 == store.lt) & (m2 > store.node)))
    win = g_any & (~store.occupied | remote_newer)

    new_store = DenseStore(
        lt=jnp.where(win, m1, store.lt),
        node=jnp.where(win, m2, store.node),
        val=jnp.where(win, g_val, store.val),
        mod_lt=jnp.where(win, new_canonical, store.mod_lt),
        mod_node=jnp.where(win, local_node, store.mod_node),
        occupied=store.occupied | win,
        tomb=jnp.where(win, g_tomb, store.tomb),
    )
    win_count = jax.lax.psum(jnp.sum(win).astype(jnp.int32), KEY_AXIS)
    return new_store, ShardedFaninResult(
        new_canonical=new_canonical, win_count=win_count, win=win,
        any_bad=any_bad, any_dup=any_dup, any_drift=any_drift)


def _flat_rank(replica_axes: tuple) -> jax.Array:
    """Flat rank across the replica axes, outer-major — the order the
    [R, N] changeset rows are laid out over the mesh, so the lowest
    flat rank is the earliest replica row (sequential-merge parity)."""
    rank = jax.lax.axis_index(replica_axes[0])
    for a in replica_axes[1:]:
        rank = rank * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return rank


def _pallas_fanin_block(replica_axes: tuple, chunk_rows: int,
                        interpret: bool, store: DenseStore,
                        cs: DenseChangeset, canonical_lt: jax.Array,
                        local_node: jax.Array, wall_millis: jax.Array
                        ) -> Tuple[DenseStore, ShardedFaninResult]:
    """Per-device body with the Mosaic kernel doing the heavy reduce.

    Each device runs `ops.pallas_merge.pallas_fanin_batch` to merge its
    own [R_blk, N_blk] changeset rows into its (replica-replicated)
    store shard — the 24× single-chip kernel, per shard. The partial
    stores of a key column then differ only where a device adopted a
    remote record, and the final store is their lexicographic
    ``(lt, node)`` maximum: the same pmax → masked pmax → stable pmin
    rank → one-hot psum reduction the XLA block uses, applied to 2
    int64 + 1 int32 + 2 small lanes instead of the full R-row
    changeset. Winner ``modified`` lanes are re-stamped with the
    GLOBAL post-absorption canonical (the kernel's device-local stamp
    is discarded), so lanes match the single-device executor
    bit-for-bit.

    Guard flags are the kernel contract's closed-form optimistic
    superset (`pallas_fanin_batch` docstring): a local-node record
    above the pre-merge canonical, or any record past the drift
    threshold, pmaxed over the mesh. The model recomputes exactly on
    host when one trips (`DenseCrdt._exact_guards`), so spurious flags
    never reject a merge and raised exceptions keep first-offender
    parity.
    """
    from ..ops.pallas_merge import (join_store, pallas_fanin_batch,
                                    split_changeset, split_store)
    all_axes = replica_axes + (KEY_AXIS,)

    # --- closed-form guard bounds + canonical absorption: both ride
    # ONE two-lane pmax over the whole mesh ---
    masked_lt = jnp.where(cs.valid, cs.lt, _NEG)
    local_max = jnp.max(masked_lt)
    m_loc = jnp.max(jnp.where(cs.valid & (cs.node == local_node),
                              cs.lt, _NEG))
    g = pmax_i64(jnp.stack([local_max, m_loc]), all_axes)
    g_max, g_loc = g[0], g[1]
    new_canonical = jnp.maximum(canonical_lt, g_max)
    any_dup = g_loc > canonical_lt
    thresh = ((wall_millis + MAX_DRIFT) << SHIFT) | MAX_COUNTER
    any_drift = g_max > thresh
    any_bad = any_dup | any_drift

    # --- per-shard Mosaic kernel merge (store shard is replicated
    # across the replica axes; each device folds only its own rows) ---
    sst = split_store.__wrapped__(store)
    scs = split_changeset.__wrapped__(cs)
    out, pres = pallas_fanin_batch.__wrapped__(
        sst, scs, canonical_lt, local_node, wall_millis,
        chunk_rows=chunk_rows, interpret=interpret)
    partial = join_store.__wrapped__(out)

    # --- cross-device lexicographic (lt, node) max over the partial
    # stores. Adoption in-kernel is strictly greater than the store
    # record, so every partial >= the store record and the max IS the
    # full join; ties pick the lowest flat rank (earliest replica
    # rows — sequential-merge parity, matching the in-kernel strict
    # compare that keeps the earliest row). ---
    p_lt = jnp.where(partial.occupied, partial.lt, _NEG)
    m1 = pmax_i64(p_lt, replica_axes)
    node_cand = jnp.where(p_lt == m1, partial.node, _I32_NEG)
    m2 = jax.lax.pmax(node_cand, replica_axes)
    has = (p_lt == m1) & (partial.node == m2)
    rank = _flat_rank(replica_axes)
    winner_rank = jax.lax.pmin(jnp.where(has, rank, _BIG_RANK),
                               replica_axes)
    mine = has & (rank == winner_rank)
    g_val = jax.lax.psum(jnp.where(mine, partial.val, 0), replica_axes)
    g_tomb = jax.lax.psum(
        jnp.where(mine, partial.tomb, False).astype(jnp.int32),
        replica_axes) > 0
    # A slot was adopted iff the winning device's kernel adopted it
    # (devices that kept the store lose the rank tie or the lex max).
    win = jax.lax.psum(
        jnp.where(mine, pres.win, False).astype(jnp.int32),
        replica_axes) > 0

    new_store = DenseStore(
        lt=jnp.where(win, m1, store.lt),
        node=jnp.where(win, m2, store.node),
        val=jnp.where(win, g_val, store.val),
        mod_lt=jnp.where(win, new_canonical, store.mod_lt),
        mod_node=jnp.where(win, local_node, store.mod_node),
        occupied=store.occupied | win,
        tomb=jnp.where(win, g_tomb, store.tomb),
    )
    win_count = jax.lax.psum(jnp.sum(win).astype(jnp.int32), KEY_AXIS)
    return new_store, ShardedFaninResult(
        new_canonical=new_canonical, win_count=win_count, win=win,
        any_bad=any_bad, any_dup=any_dup, any_drift=any_drift)


def make_sharded_pallas_fanin(mesh: Mesh, *, chunk_rows: int = 8,
                              interpret: bool = False):
    """`make_sharded_fanin` with the per-device reduce running through
    the Mosaic batch kernel (`_pallas_fanin_block`) — the single-chip
    headline executor inside the multi-chip collective step.

    Requirements beyond the XLA step: each device's key shard must be
    a multiple of `ops.pallas_merge.TILE`, changeset replica rows must
    pad to ``replica_extent(mesh) * chunk_rows``, and node ordinals
    must fit the kernel's int16 wire lane (the model layer gates all
    three — `ShardedDenseCrdt._use_pallas_sharded`). ``interpret=True``
    runs the kernel in Pallas interpret mode for non-TPU meshes (the
    virtual-CPU validation path).
    """
    from functools import partial
    replica_axes = _replica_axes(mesh)
    step = _shard_map(
        partial(_pallas_fanin_block, replica_axes, chunk_rows, interpret),
        mesh=mesh,
        in_specs=(
            DenseStore(*([P(KEY_AXIS)] * len(DenseStore._fields))),
            DenseChangeset(*([P(replica_axes, KEY_AXIS)]
                             * len(DenseChangeset._fields))),
            P(), P(), P(),
        ),
        out_specs=(
            DenseStore(*([P(KEY_AXIS)] * len(DenseStore._fields))),
            ShardedFaninResult(
                new_canonical=P(), win_count=P(), win=P(KEY_AXIS),
                any_bad=P(), any_dup=P(), any_drift=P()),
        ),
        check_vma=False,
    )
    return _record_step(_obs_device.kernel_label(
        "parallel.sharded_pallas_fanin", interpret), jax.jit(step))


def _record_step(kernel: str, jitted, *, donated_store: bool = False,
                 dim_arg: int = 0):
    """Wrap a factory-built jitted step in a ledger-recording closure.
    ``dim_arg`` picks the positional arg whose store/batch leading dim
    feeds the compile census; ``donated_store`` marks arg 0's ``lt``
    lane for post-call donation checking."""

    @functools.wraps(jitted)
    def step(*args, **kw):
        ref = args[dim_arg]
        dim = ref.lt.shape[0] if hasattr(ref, "lt") else ref.shape[0]
        donated = args[0].lt if donated_store else None
        with _obs_device.record(kernel, dim=dim, donated=donated):
            return jitted(*args, **kw)

    return step


def make_sharded_fanin(mesh: Mesh):
    """Build the jitted sharded fan-in step for a mesh.

    Returns ``step(store, cs, canonical_lt, local_node, wall_millis) ->
    (new_store, ShardedFaninResult)`` with the store sharded by
    ``store_sharding(mesh)`` and changesets by
    ``changeset_sharding(mesh)``.
    """
    from functools import partial
    replica_axes = _replica_axes(mesh)
    step = _shard_map(
        partial(_fanin_block, replica_axes),
        mesh=mesh,
        in_specs=(
            DenseStore(*([P(KEY_AXIS)] * len(DenseStore._fields))),
            DenseChangeset(*([P(replica_axes, KEY_AXIS)]
                             * len(DenseChangeset._fields))),
            P(), P(), P(),
        ),
        out_specs=(
            DenseStore(*([P(KEY_AXIS)] * len(DenseStore._fields))),
            ShardedFaninResult(
                new_canonical=P(), win_count=P(), win=P(KEY_AXIS),
                any_bad=P(), any_dup=P(), any_drift=P()),
        ),
        check_vma=False,
    )
    return _record_step("parallel.sharded_fanin", jax.jit(step))


@functools.lru_cache(maxsize=None)
def make_sharded_ingest(mesh: Mesh, donate: bool = False):
    """ONE shard_map program for the write combiner's commit scatter:
    every device translates the (replicated) global slot batch to its
    key-shard's local rows and applies the blind ingest overwrite
    (`ops.dense.ingest_scatter` semantics) — out-of-shard and sentinel
    rows drop. Replaces the unsharded scatter + per-lane re-shard
    round-trip (`shard_store`) that used to cost a dispatch per lane.

    Returns ``step(store, slot, lt, val, tomb, me) -> new_store`` with
    the store sharded by ``store_sharding(mesh)`` and the batch lanes
    replicated. ``donate=True`` consumes the store buffers in place
    (the model layer gates donation exactly as for merges)."""

    def _ingest_block(store: DenseStore, slot, lt, val, tomb, me
                      ) -> DenseStore:
        n_local = store.lt.shape[0]
        loc = slot - jax.lax.axis_index(KEY_AXIS) * n_local
        # Rows outside this shard (and the caller's n_slots pad
        # sentinel) land out of range and drop.
        loc = jnp.where((loc < 0) | (loc >= n_local), n_local,
                        loc).astype(jnp.int32)
        return DenseStore(
            lt=store.lt.at[loc].set(lt, mode="drop"),
            node=store.node.at[loc].set(me, mode="drop"),
            val=store.val.at[loc].set(val, mode="drop"),
            mod_lt=store.mod_lt.at[loc].set(lt, mode="drop"),
            mod_node=store.mod_node.at[loc].set(me, mode="drop"),
            occupied=store.occupied.at[loc].set(True, mode="drop"),
            tomb=store.tomb.at[loc].set(tomb, mode="drop"))

    step = _shard_map(
        _ingest_block, mesh=mesh,
        in_specs=(
            DenseStore(*([P(KEY_AXIS)] * len(DenseStore._fields))),
            P(), P(), P(), P(), P(),
        ),
        out_specs=DenseStore(*([P(KEY_AXIS)] * len(DenseStore._fields))),
        check_vma=False,
    )
    return _record_step(
        "parallel.sharded_ingest",
        jax.jit(step, donate_argnums=(0,) if donate else ()),
        donated_store=donate, dim_arg=1)


def sharded_delta_mask(mesh: Mesh):
    """modifiedSince filter over the sharded store — INCLUSIVE bound
    (map_crdt.dart:44-45), computed shard-local (no collectives)."""

    def _mask(store: DenseStore, since_lt: jax.Array) -> jax.Array:
        return store.occupied & (store.mod_lt >= since_lt)

    return _record_step("parallel.sharded_delta_mask", jax.jit(_shard_map(
        _mask, mesh=mesh,
        in_specs=(DenseStore(*([P(KEY_AXIS)] * len(DenseStore._fields))),
                  P()),
        out_specs=P(KEY_AXIS),
        check_vma=False,
    )))


@functools.lru_cache(maxsize=None)
def make_sharded_digest(mesh: Mesh, leaf_width: int, has_sem: bool):
    """Merkle digest-tree levels over the sharded store
    (docs/ANTIENTROPY.md): per-shard subtree leaves computed
    shard-local — slot digests mixed against GLOBAL positions via the
    key-axis offset — fan in along the key axis, then the interior
    combines fold in the SAME jitted program (GSPMD inserts the
    gather; the leaf row is tiny next to the lanes). Requires the
    shard width to be a multiple of ``leaf_width`` so leaf boundaries
    never straddle shards; `ShardedDenseCrdt._digest_levels` falls
    back to the single-program reduction otherwise. Levels are
    bit-identical to the unsharded `ops.digest.digest_tree_device`."""
    from ..ops.digest import (fold_leaves, slot_digests,
                              tree_levels_from_leaves)

    def _leaves(store: DenseStore, *sem):
        shard = store.lt.shape[0]
        if shard % leaf_width:
            raise ValueError(
                f"shard width {shard} not a multiple of leaf_width "
                f"{leaf_width}")
        off = (jax.lax.axis_index(KEY_AXIS).astype(jnp.uint64)
               * jnp.uint64(shard))
        h = slot_digests(store.lt, store.val, store.tomb,
                         store.occupied,
                         sem=sem[0] if has_sem else None,
                         idx_offset=off)
        return fold_leaves(h, leaf_width)

    store_spec = DenseStore(*([P(KEY_AXIS)] * len(DenseStore._fields)))
    in_specs = ((store_spec, P(KEY_AXIS)) if has_sem
                else (store_spec,))
    leaves = _shard_map(_leaves, mesh=mesh, in_specs=in_specs,
                        out_specs=P(KEY_AXIS), check_vma=False)

    def step(store: DenseStore, *sem):
        return tree_levels_from_leaves(leaves(store, *sem))

    return _record_step("parallel.sharded_digest", jax.jit(step))


@functools.lru_cache(maxsize=None)
def make_sharded_compact(mesh: Mesh, leaf_width: int, has_sem: bool,
                         donate: bool = False):
    """Whole-store online compaction over the sharded store, ONE
    shard_map program (docs/STORAGE.md): every device packs its key
    shard's surviving rows to the SHARD-LOCAL prefix — the global
    remap is per-shard and never crosses shard boundaries — emits its
    rows of the global translation table, and rebuilds its digest
    leaves against global positions (`idx_offset`, exactly like
    `make_sharded_digest`); the interior combines fold in the same
    jitted program. Requires each shard a multiple of ``leaf_width``;
    `ShardedDenseCrdt.compact` falls back to the base single-program
    kernel otherwise.

    Returns ``step(store[, sem]) -> (new_store[, new_sem],
    translation, levels)`` where ``translation[old] = new`` global
    slot for occupied rows, ``-1`` for empty slots, and ``levels`` are
    root-first digest-tree levels bit-identical to what
    `ops.digest.digest_tree_device` would build over the compacted
    store. ``donate=True`` consumes the store buffers in place."""
    from ..ops.digest import (fold_leaves, slot_digests,
                              tree_levels_from_leaves)

    def _local(store: DenseStore, *sem):
        shard = store.lt.shape[0]
        if shard % leaf_width:
            raise ValueError(
                f"shard width {shard} not a multiple of leaf_width "
                f"{leaf_width}")
        idx = jnp.arange(shard, dtype=jnp.int64)
        off = jax.lax.axis_index(KEY_AXIS).astype(jnp.int64) * shard
        keep = store.occupied
        rank = jnp.cumsum(keep.astype(jnp.int64)) - 1
        new_local = jnp.where(keep, rank, idx)
        translation = jnp.where(keep, new_local + off,
                                -1).astype(jnp.int32)
        # Empty rows scatter to the `shard` sentinel and drop; the
        # zeros base IS the compacted tail.
        target = jnp.where(keep, new_local, shard).astype(jnp.int32)

        def scat(lane):
            return jnp.zeros(lane.shape, lane.dtype).at[target].set(
                lane, mode="drop")

        out = DenseStore(lt=scat(store.lt), node=scat(store.node),
                         val=scat(store.val), mod_lt=scat(store.mod_lt),
                         mod_node=scat(store.mod_node),
                         occupied=scat(store.occupied),
                         tomb=scat(store.tomb))
        new_sem = (scat(sem[0]),) if has_sem else ()
        offu = (jax.lax.axis_index(KEY_AXIS).astype(jnp.uint64)
                * jnp.uint64(shard))
        h = slot_digests(out.lt, out.val, out.tomb, out.occupied,
                         sem=new_sem[0] if has_sem else None,
                         idx_offset=offu)
        return (out,) + new_sem + (translation, fold_leaves(h, leaf_width))

    store_spec = DenseStore(*([P(KEY_AXIS)] * len(DenseStore._fields)))
    in_specs = ((store_spec, P(KEY_AXIS)) if has_sem
                else (store_spec,))
    out_specs = ((store_spec,)
                 + ((P(KEY_AXIS),) if has_sem else ())
                 + (P(KEY_AXIS), P(KEY_AXIS)))
    local = _shard_map(_local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    def step(store: DenseStore, *sem):
        parts = local(store, *sem)
        return parts[:-1] + (tree_levels_from_leaves(parts[-1]),)

    return _record_step(
        "parallel.sharded_compact",
        jax.jit(step, donate_argnums=(0,) if donate else ()),
        donated_store=donate)


def sharded_max_logical_time(mesh: Mesh):
    """refreshCanonicalTime's reduction over the sharded store
    (crdt.dart:114-121): shard-local max, then one pmax over the mesh."""

    def _max(store: DenseStore) -> jax.Array:
        local = jnp.max(jnp.where(store.occupied, store.lt, 0))
        return pmax_i64(local, mesh.axis_names)

    return _record_step(
        "parallel.sharded_max_logical_time",
        jax.jit(_shard_map(
            _max, mesh=mesh,
            in_specs=(DenseStore(*([P(KEY_AXIS)]
                                   * len(DenseStore._fields))),),
            out_specs=P(),
            check_vma=False,
        )))
