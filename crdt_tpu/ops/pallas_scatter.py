"""Pallas TPU kernel for the ingest commit scatter — touched tiles only.

The write combiner's flush commits a deduped slot batch with ONE
stamp owner (the local node): no lattice compare, no guards — every
valid row overwrites its slot (`ops.dense.ingest_scatter` semantics).
XLA lowers that k-index scatter as a serialized per-index loop on TPU;
here the batch is regrouped host-side onto the TILE blocks it touches
and each touched block is rewritten in one VMEM pass — a masked
select over ``(_SB, _LANE)`` vregs, not k sequential row updates. The
grid walks ONLY touched tiles (scalar-prefetched block ids), so a
64-row flush against a 16M-slot store moves a handful of tiles, not
the store.

Lanes ride split (hi int32, lo uint32) exactly like the merge kernel
(`pallas_merge.SplitStore`): no int64 emulation, occupancy encoded as
``hi != NEG_HI`` — writing a real logicalTime marks the slot occupied
with no separate lane.

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
from .pallas_merge import (_LANE, _SB, TILE, SplitStore, _join64, _split64,
                           join_store, split_store)
from ..obs import device as _obs_device
from ..obs.trace import span

_obs_device.register("pallas.ingest_scatter_tiles")


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


def _scatter_step(store: DenseStore, tile_ids, valid, lt_d, val_d,
                  tomb_d, me, *, interpret: bool):
    n = store.lt.shape[0]
    rows = n // _LANE
    s = split_store.__wrapped__(store)
    st = [ln.reshape(rows, _LANE) for ln in s]
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
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), ln.dtype)
                   for ln in st],
        grid_spec=grid_spec,
        input_output_aliases={2 + j: j for j in range(9)},
        interpret=interpret,
    )(tile_ids, me, *st, valid.astype(jnp.int32), lhi, llo, vhi, vlo,
      tomb_d.astype(jnp.int32))
    return join_store.__wrapped__(
        SplitStore(*(o.reshape(n) for o in outs)))


@_ft.lru_cache(maxsize=None)
def _scatter_jit(donate: bool, interpret: bool):
    step = _ft.partial(_scatter_step, interpret=interpret)
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def ingest_scatter_tiles(store: DenseStore, slots: np.ndarray,
                         lt: np.ndarray, val: np.ndarray,
                         tomb: np.ndarray, me: int, *,
                         donate: bool = False,
                         interpret: bool = False) -> DenseStore:
    """Commit a deduped ingest batch through the touched-tile kernel.
    Bit-identical to `ops.dense.ingest_scatter` over in-range slots
    (host prep drops nothing — callers bound slots beforehand)."""
    with span("scatter.prepare", kind="ingest", rows=len(slots)):
        tile_ids, valid, lt_d, val_d, tomb_d = prepare_tile_updates(
            np.asarray(slots, np.int64), np.asarray(lt, np.int64),
            np.asarray(val, np.int64), np.asarray(tomb),
            store.lt.shape[0])
    name = _obs_device.kernel_label("pallas.ingest_scatter_tiles",
                                    interpret)
    with _obs_device.record(name, dim=int(tile_ids.shape[0]),
                            donated=store.lt if donate else None):
        return _scatter_jit(donate, interpret)(
            store, jnp.asarray(tile_ids), jnp.asarray(valid),
            jnp.asarray(lt_d), jnp.asarray(val_d), jnp.asarray(tomb_d),
            jnp.full((1,), me, jnp.int32))
