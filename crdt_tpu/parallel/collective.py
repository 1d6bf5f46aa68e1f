"""Pod-local collective anti-entropy: the lattice join as ONE
donated, jit-cached ``shard_map`` program over a 1-D member mesh.

The socket path replicates co-located members the same way it
replicates cross-pod peers: pack → frame → loopback → unpack → merge,
once per peer pair. But the state-based merge discipline says the
lattice join IS the whole protocol — so for N replicas that share a
mesh, anti-entropy can be an all-reduce instead of N(N-1) wire
exchanges. This module composes that all-reduce from the exact join
rules the pairwise kernels apply:

- **clock lanes** — the lexicographic ``(lt, node)`` max, built from
  primitive collectives the way `fanin._fanin_block` does: ``pmax``
  lt → masked ``pmax`` node → stable ``pmin`` flat-rank tie →
  one-hot ``psum`` winner broadcast. Ties on identical HLCs pick the
  lowest member rank (identical events carry identical payloads by
  the uniqueness invariant, so the pick is payload-neutral).
- **value lane** — per-tag G-ary joins matching a pairwise fold of
  `semantics.kernels.typed_join_lanes`: LWW takes the clock winner's
  payload; gcounter is a plain ``pmax`` (0 is the join identity);
  pncounter ``pmax``es each 31-bit half; orset ``pmax``es all 16
  causal-length nibbles in one stacked collective; mvreg
  ``all_gather``s the packs of members holding the winning lt and
  folds `_mvreg_union` over them (the empty pack 0 is its identity).
- **tomb / occupied** — the clock winner's flag; presence is the
  member-axis OR.

The post-join digest-tree leaves are computed in the SAME program
(the joined lanes are replicated across members by construction, so
each member digests its own output block and the levels come out
``P()``), and per-member ``mod`` stamps ride along. So do what the
host needs to seed each member's pack cache, already small: the
member's adopted-row count, its repack-row count, and its repack rows
compacted on the device (`compact_rows`, at most ``repack_rows`` a
member, in slot order) — one dispatch yields everything
`CollectiveGroup.join` needs, and what it fetches does not grow with
the store.

On a real pod the member axis rides ICI; on the tests' 1-core virtual
mesh the same program runs across virtual CPU devices — bit-identical
results, honest-downscale timing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..obs import device as _obs_device

_obs_device.register("parallel.collective_join")

from ..ops.dense import DenseStore, _NEG, _I32_NEG
from ..ops.digest import (fold_leaves, slot_digests,
                          tree_levels_from_leaves)
from ..semantics.kernels import (ORSET_UNIVERSE, SEM_GCOUNTER,
                                 SEM_MVREG, SEM_ORSET, SEM_PNCOUNTER,
                                 _PN_HALF, _mvreg_union)
from .fanin import (Mesh, NamedSharding, P, _BIG_RANK, _make_mesh,
                    _shard_map, pmax_i64)

#: The 1-D replica-group axis. Distinct from fanin's REPLICA_AXIS on
#: purpose: a fan-in mesh shards *changeset rows*; a collective mesh
#: places one whole member replica per device.
MEMBER_AXIS = "member"


#: Repack rows the join compacts on the device per member: a steady
#: round re-packs the member's own last batch plus the rows it adopted,
#: far below this. A member past it is packed from its whole lanes
#: (`CollectiveGroup.join`'s counted fallback).
REPACK_ROWS = 1 << 15
#: Slots per block of `compact_rows`' first level.
_COMPACT_BLOCK = 64


class RepackRows(NamedTuple):
    """Each member's repack rows, compacted: ``[G*K]`` lanes, member
    ``i``'s ``K`` rows at ``[i*K, (i+1)*K)`` in slot order; a row past
    the member's ``repack_count`` holds slot ``n_slots``."""
    slots: jax.Array                # int32
    lt: jax.Array                   # int64
    node: jax.Array                 # int32
    val: jax.Array                  # int64
    tomb: jax.Array                 # bool


class CollectiveJoinResult(NamedTuple):
    new_canonical: jax.Array        # int64 scalar, replicated
    win_count: jax.Array            # int32[G] rows each member adopted
    repack_count: jax.Array         # int32[G] rows each member re-packs
    rows: RepackRows                # the repack rows, compacted
    levels: Tuple[jax.Array, ...]   # post-join digest levels, root-first


def make_collective_mesh(n_members: int, devices=None) -> Mesh:
    """A 1-D ``(member,)`` mesh over the first ``n_members`` devices
    (or the given device list)."""
    if devices is None:
        devices = jax.devices()[:n_members]
    return _make_mesh((n_members,), (MEMBER_AXIS,), devices)


def _typed_group_val(sem, occ, l_lt, l_val, m1, w_val):
    """G-ary value join by tag over the member axis. Equal to folding
    `typed_join_lanes`'s pairwise value rule over the group in any
    order — each branch is a commutative/associative join with 0 as
    the absent-member identity, which is exactly what lets it ride
    collectives instead of a fold."""
    ax = MEMBER_AXIS
    gmax = pmax_i64(l_val, ax)
    # The 31-bit halves and the nibbles fit int32, the width a TPU
    # max all-reduce takes.
    pos = jax.lax.pmax(((l_val >> 32) & _PN_HALF).astype(jnp.int32), ax)
    neg = jax.lax.pmax((l_val & _PN_HALF).astype(jnp.int32), ax)
    pn = (pos.astype(jnp.int64) << 32) | neg.astype(jnp.int64)
    # orset: all 16 causal-length nibbles in ONE stacked pmax.
    nibs = jnp.stack([((l_val >> (4 * i)) & 0xF).astype(jnp.int32)
                      for i in range(ORSET_UNIVERSE)])
    g_nibs = jax.lax.pmax(nibs, ax).astype(jnp.int64)
    ors = jnp.zeros_like(l_val)
    for i in range(ORSET_UNIVERSE):
        ors = ors | (g_nibs[i] << (4 * i))
    # mvreg: strictly newer lt wins outright, equal lt unions — so
    # only members holding the winning lt contribute their pack, and
    # the union folds over a static G-row gather (0 = empty pack is
    # the `_mvreg_union` identity).
    cand = jnp.where(occ & (l_lt == m1), l_val, 0)
    packs = jax.lax.all_gather(cand, ax)
    mv = packs[0]
    for g in range(1, packs.shape[0]):
        mv = _mvreg_union(mv, packs[g])
    out = w_val
    out = jnp.where(sem == SEM_GCOUNTER, gmax, out)
    out = jnp.where(sem == SEM_PNCOUNTER, pn, out)
    out = jnp.where(sem == SEM_ORSET, ors, out)
    out = jnp.where(sem == SEM_MVREG, mv, out)
    return out


def compact_rows(mask, k: int):
    """The first ``k`` set positions of ``mask`` in ascending order
    (``n`` past the count), and the count, as int32. Two levels and no
    scatter: block counts and their running sums over ``n / 64``
    blocks, a binary search of those sums for each output row's block,
    then the row's rank within its 64 slots — no prefix sum over all
    ``n`` slots."""
    n = mask.shape[0]
    b = min(_COMPACT_BLOCK, n)
    nb = -(-n // b)
    if nb * b != n:
        mask = jnp.concatenate([mask, jnp.zeros(nb * b - n, bool)])
    blocks = mask.reshape(nb, b)
    counts = jnp.sum(blocks, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    j = jnp.arange(k, dtype=jnp.int32)
    # Row j lies in the first block whose running count exceeds j, at
    # rank j - (set bits before that block) within it.
    blk = jnp.minimum(jnp.searchsorted(ends, j, side="right"), nb - 1)
    rank = j - (ends[blk] - counts[blk])
    within = jnp.cumsum(blocks[blk], axis=1, dtype=jnp.int32)
    pos = blk * b + jnp.sum(within <= rank[:, None], axis=1,
                            dtype=jnp.int32)
    return jnp.where(j < ends[-1], pos, n), ends[-1]


def _join_block(leaf_width: int, has_sem: bool, repack_rows: int,
                store: DenseStore, *rest):
    """Per-member body under shard_map: lanes arrive as the member's
    own [N] block of the [G*N] group lanes, scalars per member arrive
    [1]; ``sem`` (when present) and ``canonical_in`` are replicated."""
    if has_sem:
        sem, since, me, canonical_in = rest
    else:
        since, me, canonical_in = rest
        sem = None
    lt, node, val = store.lt, store.node, store.val
    occ, tomb = store.occupied, store.tomb
    mod_lt, mod_node = store.mod_lt, store.mod_node
    since_i, me_i = since[0], me[0]

    # Mask absent slots to the join identities so an unoccupied member
    # can never win a lane (mirrors reduce_replicas' valid masking).
    l_lt = jnp.where(occ, lt, _NEG)
    l_node = jnp.where(occ, node, _I32_NEG)
    l_val = jnp.where(occ, val, 0)
    l_tomb = occ & tomb

    # Lexicographic (lt, node) max over the group — the fanin block's
    # collective composition, verbatim.
    ax = MEMBER_AXIS
    m1 = pmax_i64(l_lt, ax)
    node_cand = jnp.where(l_lt == m1, l_node, _I32_NEG)
    m2 = jax.lax.pmax(node_cand, ax)
    has = (l_lt == m1) & (l_node == m2)
    rank = jax.lax.axis_index(ax)
    winner_rank = jax.lax.pmin(jnp.where(has, rank, _BIG_RANK), ax)
    mine = has & (rank == winner_rank)
    w_val = jax.lax.psum(jnp.where(mine, l_val, 0), ax)
    w_tomb = jax.lax.psum(jnp.where(mine & l_tomb, 1, 0)
                          .astype(jnp.int32), ax) > 0
    g_occ = jax.lax.pmax(occ.astype(jnp.int32), ax) > 0

    if has_sem:
        v = _typed_group_val(sem, occ, l_lt, l_val, m1, w_val)
    else:
        v = w_val

    # Unoccupied-everywhere slots keep this member's own (zero) lanes
    # — never the _NEG/_I32_NEG sentinels.
    lt_out = jnp.where(g_occ, m1, lt)
    node_out = jnp.where(g_occ, m2, node)
    val_out = jnp.where(g_occ, v, val)
    tomb_out = jnp.where(g_occ, w_tomb, tomb)
    occ_out = occ | g_occ

    # Per-member adoption = changed-vs-own-input (the typed kernels'
    # `win` semantics; for LWW lanes it coincides with the strict
    # take mask, since adoption always moves lt or node or presence).
    win = ((lt_out != lt) | (node_out != node) | (val_out != val)
           | (tomb_out != tomb) | (occ_out & ~occ))

    new_canonical = jnp.maximum(
        canonical_in,
        pmax_i64(jnp.max(jnp.where(occ, lt, _NEG)), ax))
    mod_lt_out = jnp.where(win, new_canonical, mod_lt)
    mod_node_out = jnp.where(win, me_i, mod_node)
    repack = occ_out & (mod_lt_out >= since_i)
    # The rows the member's next watermark-aligned pack ships, gathered
    # here so the host fetches K rows instead of N-slot lanes.
    pos, repack_count = compact_rows(repack, repack_rows)
    at = jnp.minimum(pos, lt.shape[0] - 1)
    rows = RepackRows(slots=pos, lt=lt_out[at], node=node_out[at],
                      val=val_out[at], tomb=tomb_out[at])
    win_count = jnp.sum(win, dtype=jnp.int32)

    # Post-join digest leaves in the SAME program: the replicated
    # lanes are identical across members by construction, so every
    # member digests its own output block and the row is P().
    h = slot_digests(lt_out, val_out, tomb_out, occ_out,
                     sem=sem, idx_offset=None)
    leaves = fold_leaves(h, leaf_width)

    new_store = DenseStore(
        lt=lt_out, node=node_out, val=val_out, mod_lt=mod_lt_out,
        mod_node=mod_node_out, occupied=occ_out, tomb=tomb_out)
    return (new_store, win_count[None], repack_count[None], rows,
            new_canonical, leaves)


def _group_lanes(stores, mesh: Mesh) -> DenseStore:
    """The members' stores as [G*N] lanes sharded over the member
    axis, member ``i``'s block on the mesh's ``i``-th device. A member
    already living there moves nothing; one elsewhere (a fresh replica
    on the default device) is copied to its device once."""
    devices = list(mesh.devices.flat)
    sharding = NamedSharding(mesh, P(MEMBER_AXIS))
    lanes = []
    for f in range(len(DenseStore._fields)):
        rows = [jax.device_put(s[f], d) for s, d in zip(stores, devices)]
        lanes.append(jax.make_array_from_single_device_arrays(
            (len(rows) * rows[0].shape[0],), sharding, rows))
    return DenseStore(*lanes)


def _member_blocks(lane: jax.Array) -> list:
    """Split a member-sharded [G*N] lane into its G per-device [N]
    blocks, in member order — zero-copy: each block stays on the
    device that computed it."""
    shards = sorted(lane.addressable_shards,
                    key=lambda sh: sh.index[0].start or 0)
    return [sh.data for sh in shards]


@functools.lru_cache(maxsize=None)
def make_collective_join(mesh: Mesh, has_sem: bool, leaf_width: int,
                         donate: bool = False,
                         repack_rows: int = REPACK_ROWS):
    """Build the jitted single-dispatch collective join for a member
    mesh.

    Returns ``step(stores, [sem,] since, me, canonical_in) ->
    (member_stores, CollectiveJoinResult)`` where ``stores`` is a
    G-tuple of per-member `DenseStore`s (G = mesh extent), ``sem`` is
    the shared [N] int8 tag column (only when ``has_sem``), ``since``
    and ``me`` are [G] per-member watermark lts / node ordinals, and
    ``canonical_in`` is the max of the members' pre-join canonical
    lts. Member ``i``'s store joins on, and comes back on, the mesh's
    ``i``-th device: one whole replica per device. Replicated lanes
    are identical across members, ``mod`` lanes are per-member. Each
    member's repack rows come back compacted, ``repack_rows`` a
    member (`RepackRows`).
    ``donate=True`` consumes the input store buffers (gate it off on
    CPU, where XLA ignores donation with a warning).

    ``step.jitted`` is the program itself, over [G*N] member-sharded
    lanes (`_group_lanes`)."""
    g = mesh.shape[MEMBER_AXIS]
    store_spec = DenseStore(*([P(MEMBER_AXIS)]
                              * len(DenseStore._fields)))
    in_specs = ((store_spec,)
                + ((P(),) if has_sem else ())
                + (P(MEMBER_AXIS), P(MEMBER_AXIS), P()))
    member = P(MEMBER_AXIS)
    join = _shard_map(
        functools.partial(_join_block, leaf_width, has_sem, repack_rows),
        mesh=mesh, in_specs=in_specs,
        out_specs=(store_spec, member, member,
                   RepackRows(*([member] * len(RepackRows._fields))),
                   P(), P()),
        check_vma=False)

    def _step(group, *args):
        out_store, win_count, repack_count, rows, canonical, leaves = \
            join(group, *args)
        return out_store, CollectiveJoinResult(
            new_canonical=canonical, win_count=win_count,
            repack_count=repack_count, rows=rows,
            levels=tree_levels_from_leaves(leaves))

    jitted = jax.jit(_step, donate_argnums=(0,) if donate else ())

    def step(stores, *args):
        if len(stores) != g:
            raise ValueError(
                f"collective join over a {g}-member mesh got "
                f"{len(stores)} stores")
        group = _group_lanes(stores, mesh)
        with _obs_device.record("parallel.collective_join",
                                dim=stores[0].lt.shape[0],
                                donated=group.lt if donate else None):
            out, res = jitted(group, *args)
        blocks = [_member_blocks(lane) for lane in out]
        return tuple(DenseStore(*lanes) for lanes in zip(*blocks)), res

    # The raw jitted program, for jaxpr tracing (analysis/jaxpr_audit)
    # without the ledger accounting a real dispatch carries.
    step.jitted = jitted
    return step
