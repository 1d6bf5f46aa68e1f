"""The resident store layout and its touched-tile ingest commit.

Where the Mosaic commit engages, a `DenseCrdt` keeps its store between
commits as the nine `pallas_merge.SplitStore` planes, each already in
the kernel's 2-D ``(n_slots // _LANE, _LANE)`` shape: split (hi int32,
lo uint32) lanes, occupancy encoded as ``hi != NEG_HI``. A TPU emulates
int64, so any op on an int64 lane — even a one-element slice — splits
the whole lane into 32-bit halves first; planes that stay split never
pay that, and a 2-D plane needs no relayout to reach the kernel.

- **Flush** (`ingest_scatter_planes`): the write combiner's commit of a
  deduped slot batch with ONE stamp owner (the local node) — no
  lattice compare, every valid row overwrites its slot
  (`ops.dense.ingest_scatter` semantics). The batch is regrouped
  host-side onto the TILE blocks it touches; the grid walks ONLY those
  tiles (scalar-prefetched block ids), each rewritten in one VMEM pass
  — a masked select over ``(_SB, _LANE)`` vregs. All nine planes alias
  their outputs and are donated, so a flush moves its tiles and
  nothing else of the store.
- **Point read** (`gather_slot`): one program gathers the nine words
  of one slot into one array — one dispatch, one transfer. A store
  held as `DenseStore` lanes is read the same way, through a split of
  the one slot.
- **Moves** (`planes_to_store`, `store_to_planes`): the store is held
  in one form at a time. Cold paths (pack, digest, GC, compaction,
  checkpoint, grow, the XLA and sparse merges) read `DenseStore`
  lanes, so the model joins the planes into lanes for them and drops
  the planes; the next flush or point read splits the lanes back (the
  Mosaic merges take either form). Each move is O(store),
  like the passes those paths already run, and holds the store twice
  only while it runs. Ledger-counted (``dense.store_view`` /
  ``dense.store_split``), so a served path that moves the store shows
  as a miss.

The tile-id pad (to a power of two of DISTINCT grid sizes) uses
UNTOUCHED tile ids, never duplicates: the pipelined grid may prefetch
a revisited tile's input block before the first visit's write-back
lands, so a duplicated id could commit stale lanes. Padded tiles carry
an all-zero valid mask and write themselves back unchanged.

The lax fallback (`ops.dense.ingest_scatter`) stays the CPU/GPU path;
`models.dense_crdt.DenseCrdt._commit_scatter` picks per platform.
Buffer ownership and donation rules: docs/FASTPATH.md.
"""

from __future__ import annotations

import functools as _ft
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dense import DenseStore
from .pallas_merge import (_LANE, _SB, NEG_HI, NEG_LO, TILE, SplitStore,
                           _join64, _split64, join_store, split_store)
from ..obs import device as _obs_device
from ..obs.trace import span

_obs_device.register("pallas.ingest_scatter_planes", "dense.store_view",
                     "dense.store_split", "dense.point_read")


# --- the resident planes ---

def empty_planes(n_slots: int) -> SplitStore:
    """The never-written store, built directly as planes (every slot
    empty: ``hi == NEG_HI``). Nine distinct buffers, so the tree can
    be donated."""
    shape = (n_slots // _LANE, _LANE)

    def z(dtype):
        return jnp.zeros(shape, dtype)
    return SplitStore(
        hi=jnp.full(shape, NEG_HI, jnp.int32),
        lo=jnp.full(shape, NEG_LO, jnp.uint32), node=z(jnp.int32),
        val_hi=z(jnp.int32), val_lo=z(jnp.uint32), tomb=z(jnp.int32),
        mod_hi=z(jnp.int32), mod_lo=z(jnp.uint32), mod_node=z(jnp.int32))


def _to_planes(store: DenseStore) -> SplitStore:
    rows = store.lt.shape[0] // _LANE
    return SplitStore(*(ln.reshape(rows, _LANE)
                        for ln in split_store.__wrapped__(store)))


def _from_planes(planes: SplitStore) -> DenseStore:
    return join_store.__wrapped__(
        SplitStore(*(p.reshape(-1) for p in planes)))


_to_planes_jit = jax.jit(_to_planes)
_from_planes_jit = jax.jit(_from_planes)


def store_to_planes(store: DenseStore) -> SplitStore:
    """Split a `DenseStore` into resident planes (O(store))."""
    with _obs_device.record("dense.store_split", dim=store.lt.shape[0]):
        return _to_planes_jit(store)


def planes_to_store(planes: SplitStore) -> DenseStore:
    """The `DenseStore` the planes hold, as buffers of its own
    (O(store)): bit-identical to the lanes the XLA executor keeps
    after the same operations."""
    with _obs_device.record("dense.store_view", dim=planes.hi.size):
        return _from_planes_jit(planes)


@jax.jit
def _max_lt_jit(planes: SplitStore) -> jax.Array:
    occ = planes.hi != NEG_HI
    hi = jnp.where(occ, planes.hi, 0)
    lo = jnp.where(occ, planes.lo, jnp.uint32(0))
    m_hi = jnp.max(hi)
    return _join64(m_hi, jnp.max(jnp.where(hi == m_hi, lo, jnp.uint32(0))))


def planes_max_logical_time(planes: SplitStore) -> jax.Array:
    """`ops.dense.dense_max_logical_time` on the planes (unoccupied
    slots count as 0), with no int64 lane on the device."""
    with _obs_device.record("dense.max_logical_time", dim=planes.hi.size):
        return _max_lt_jit(planes)


def _words(scalars) -> jax.Array:
    return jnp.stack([jax.lax.bitcast_convert_type(w, jnp.int32)
                      for w in scalars])


@jax.jit
def _gather_jit(planes: SplitStore, slot) -> jax.Array:
    r, c = slot // _LANE, slot % _LANE
    return _words(p[r, c] for p in planes)


@jax.jit
def _gather_lanes_jit(store: DenseStore, slot) -> jax.Array:
    one = DenseStore(*(jax.lax.dynamic_slice(ln, (slot,), (1,))
                       for ln in store))
    return _words(w[0] for w in split_store.__wrapped__(one))


def gather_slot(store, slot: int) -> jax.Array:
    """Dispatch the point read of one slot of a store held as planes
    or as `DenseStore` lanes: its nine plane words as one int32[9]
    device array (fetch it with one ``device_get``; `decode_slot`
    reads it)."""
    gather = (_gather_jit if isinstance(store, SplitStore)
              else _gather_lanes_jit)
    with _obs_device.record("dense.point_read"):
        return gather(store, np.int32(slot))


def decode_slot(words) -> Tuple[bool, int, int, int, int, int, bool]:
    """``(occupied, lt, node, val, mod_lt, mod_node, tomb)`` of one
    slot from its fetched `gather_slot` words (lt is 0 when
    unoccupied, as `join_store` has it)."""
    hi, lo, node, vhi, vlo, tomb, mhi, mlo, mnode = (
        int(w) for w in np.asarray(words, np.int32))
    occupied = hi != NEG_HI
    m = 0xFFFFFFFF
    return (occupied, (hi << 32) | (lo & m) if occupied else 0, node,
            (vhi << 32) | (vlo & m), (mhi << 32) | (mlo & m), mnode,
            bool(tomb))


# --- the touched-tile commit ---

def prepare_tile_updates(slots: np.ndarray, lt: np.ndarray,
                         val: np.ndarray, tomb: np.ndarray,
                         n_slots: int) -> Tuple[np.ndarray, np.ndarray,
                                                np.ndarray, np.ndarray,
                                                np.ndarray]:
    """Host prep: regroup a deduped slot batch onto the TILE blocks it
    touches. Returns ``(tile_ids, valid, lt, val, tomb)`` — dense
    ``(n_tiles_padded * _SB, _LANE)`` lanes holding each row at its
    within-tile position, plus the int32 block ids the grid visits.
    Duplicate slots are the CALLER's bug (last-wins dedup happens in
    the combiner); a duplicate here would leave one write undefined."""
    n_tiles = n_slots // TILE
    tile_of = slots // TILE
    touched = np.unique(tile_of)
    t = len(touched)
    padded_t = min(1 << max(t - 1, 1).bit_length(), n_tiles)
    if padded_t > t:
        # Pad with DISTINCT untouched tiles (all-invalid → written back
        # unchanged); see the module docstring for why a duplicated id
        # is unsafe under the pipelined grid.
        spare = np.setdiff1d(np.arange(n_tiles, dtype=np.int64),
                             touched)[:padded_t - t]
        tile_ids = np.concatenate([touched, spare]).astype(np.int32)
    else:
        tile_ids = touched.astype(np.int32)
    pos = np.searchsorted(touched, tile_of)
    within = slots - tile_of * TILE
    r = within // _LANE
    c = within % _LANE
    valid = np.zeros((padded_t, _SB, _LANE), np.int32)
    lt_d = np.zeros((padded_t, _SB, _LANE), np.int64)
    val_d = np.zeros((padded_t, _SB, _LANE), np.int64)
    tomb_d = np.zeros((padded_t, _SB, _LANE), np.int32)
    valid[pos, r, c] = 1
    lt_d[pos, r, c] = lt
    val_d[pos, r, c] = val
    tomb_d[pos, r, c] = tomb
    flat = lambda a: a.reshape(padded_t * _SB, _LANE)
    return tile_ids, flat(valid), flat(lt_d), flat(val_d), flat(tomb_d)


def _ingest_kernel(ids_ref, me_ref, *refs):
    """One touched tile: masked overwrite of all nine store lanes.
    ``ids_ref``/``me_ref`` are the scalar-prefetch operands (block ids
    drive the index maps; ``me`` stamps node/mod_node)."""
    (s_hi, s_lo, s_node, s_vhi, s_vlo, s_tomb, s_mhi, s_mlo, s_mnode,
     v_ref, lhi_ref, llo_ref, vhi_ref, vlo_ref, tb_ref,
     o_hi, o_lo, o_node, o_vhi, o_vlo, o_tomb,
     o_mhi, o_mlo, o_mnode) = refs
    v = v_ref[...] != 0
    me = me_ref[0]
    lhi = lhi_ref[...]
    llo = llo_ref[...]
    o_hi[...] = jnp.where(v, lhi, s_hi[...])
    o_lo[...] = jnp.where(v, llo, s_lo[...])
    o_node[...] = jnp.where(v, me, s_node[...])
    o_vhi[...] = jnp.where(v, vhi_ref[...], s_vhi[...])
    o_vlo[...] = jnp.where(v, vlo_ref[...], s_vlo[...])
    o_tomb[...] = jnp.where(v, tb_ref[...], s_tomb[...])
    o_mhi[...] = jnp.where(v, lhi, s_mhi[...])
    o_mlo[...] = jnp.where(v, llo, s_mlo[...])
    o_mnode[...] = jnp.where(v, me, s_mnode[...])


def _planes_step(planes: SplitStore, tile_ids, valid, lt_d, val_d,
                 tomb_d, me, *, interpret: bool) -> SplitStore:
    """The kernel over 2-D planes: only the tile-sized update lanes
    are split; the planes alias their outputs in place."""
    lhi, llo = _split64(lt_d)
    vhi, vlo = _split64(val_d)
    padded_t = valid.shape[0] // _SB
    # Index maps see (grid index, *scalar prefetch operands): store
    # blocks follow the prefetched tile ids, update blocks walk 0..t.
    # Block indices must be int32: a bare ``0`` traces as int64 under
    # x64 and Mosaic refuses the index map.
    _i32 = jnp.int32
    st_spec = pl.BlockSpec((_SB, _LANE),
                           lambda i, ids, me: (ids[i], _i32(0)),
                           memory_space=pltpu.VMEM)
    up_spec = pl.BlockSpec((_SB, _LANE),
                           lambda i, ids, me: (i, _i32(0)),
                           memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(padded_t,),
        in_specs=[st_spec] * 9 + [up_spec] * 6,
        out_specs=[st_spec] * 9)
    # Alias numbering counts the scalar-prefetch operands: store lane
    # j is pallas_call input 2 + j.
    outs = pl.pallas_call(
        _ingest_kernel,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in planes],
        grid_spec=grid_spec,
        input_output_aliases={2 + j: j for j in range(9)},
        interpret=interpret,
    )(tile_ids, me, *planes, valid.astype(jnp.int32), lhi, llo, vhi, vlo,
      tomb_d.astype(jnp.int32))
    return SplitStore(*outs)


def _scatter_step(store: DenseStore, tile_ids, valid, lt_d, val_d,
                  tomb_d, me, *, interpret: bool) -> DenseStore:
    """The same commit on a 1-D `DenseStore`: split, kernel, join —
    each of the wrapper's passes O(store). The model never commits
    through it; it is kept only for `cells/memcheck.py`'s compile-only
    sizing."""
    return _from_planes(_planes_step(
        _to_planes(store), tile_ids, valid, lt_d, val_d, tomb_d, me,
        interpret=interpret))


@_ft.lru_cache(maxsize=None)
def _scatter_jit(donate: bool, interpret: bool):
    step = _ft.partial(_scatter_step, interpret=interpret)
    return jax.jit(step, donate_argnums=(0,) if donate else ())


@_ft.lru_cache(maxsize=None)
def _planes_jit(donate: bool, interpret: bool):
    step = _ft.partial(_planes_step, interpret=interpret)
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def ingest_scatter_planes(planes: SplitStore, slots: np.ndarray,
                          lt: np.ndarray, val: np.ndarray,
                          tomb: np.ndarray, me: int, *,
                          donate: bool = False,
                          interpret: bool = False) -> SplitStore:
    """Commit a deduped ingest batch into resident planes through the
    touched-tile kernel. Bit-identical to `ops.dense.ingest_scatter`
    over in-range slots (host prep drops nothing — callers bound slots
    beforehand). ``donate`` hands the planes to the commit, which then
    writes its tiles in place."""
    n_slots = planes.hi.size
    with span("scatter.prepare", kind="ingest", rows=len(slots)):
        tile_ids, valid, lt_d, val_d, tomb_d = prepare_tile_updates(
            np.asarray(slots, np.int64), np.asarray(lt, np.int64),
            np.asarray(val, np.int64), np.asarray(tomb), n_slots)
    name = _obs_device.kernel_label("pallas.ingest_scatter_planes",
                                    interpret)
    with _obs_device.record(name, dim=int(tile_ids.shape[0]),
                            donated=planes.hi if donate else None):
        return _planes_jit(donate, interpret)(
            planes, jnp.asarray(tile_ids), jnp.asarray(valid),
            jnp.asarray(lt_d), jnp.asarray(val_d), jnp.asarray(tomb_d),
            jnp.full((1,), me, jnp.int32))
