"""Pallas TPU kernel for the fan-in lattice join — the hot op, fused.

Why a kernel when XLA already fuses the fold (`ops.dense.lex_fold`):

1. **No int64 emulation.** TPUs have no native 64-bit integers; XLA
   emulates every int64 compare/select as multi-op int32 sequences. Here
   the 64-bit logicalTime is carried as SPLIT (hi int32, lo uint32)
   lanes and the lexicographic LWW compare is
   ``(hi, lo, node)`` — three native int32/uint32 VPU compares.
2. **One VMEM pass.** Store lanes, changeset lanes, guard masks, and
   the win mask are produced in a single tiled sweep: each (R, BLK)
   changeset tile and its (1, BLK) store tile are resident in VMEM
   once; XLA's fold reads/writes store lanes across several fusions.
3. **Drift guard as a compare.** ``(lt >> 16) - wall > MAX_DRIFT`` is
   algebraically ``lt > ((wall + MAX_DRIFT) << 16) | 0xFFFF`` (the
   ``|0xFFFF`` makes the strict compare millis-level: counter bits at
   exactly wall+MAX_DRIFT millis must not trip); the threshold is split
   host-side so the in-kernel check is the same three-way lex compare —
   no 64-bit shifts on device.

Guard semantics match the sharded path (`crdt_tpu.parallel.fanin`):
recv's fast-path shielding (hlc.dart:85) is evaluated per key column —
the running clock cummaxes over the rows of this column only, seeded
with the pre-merge canonical time. Strictly more sensitive than the
r-major flat order of `ops.dense.fanin_step` (can only flag a
superset); store lanes and canonical time are bit-identical. On a
tripped guard, re-run the scalar oracle for first-offender diagnostics.

Empty/invalid encoding: a store slot is empty iff its ``hi`` lane holds
``NEG_HI`` (no occupied lane on device); an invalid changeset entry is
pre-masked to sentinels at split time (no valid lane on device).
Tombstones ride an int32 lane (record.dart:17 semantics).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..hlc import MAX_COUNTER, MAX_DRIFT, SHIFT
from ..obs import device as _obs_device
from .dense import DenseChangeset, DenseStore, _NEG

_obs_device.register(
    "pallas.model_fanin_batch", "pallas.model_fanin_split",
    "pallas.pipelined_model_step", "pallas.pipelined_model_step_split")

# Sentinel hi word of _NEG = -(2**62): anything real compares greater.
# Plain ints (not jnp scalars): module-level concrete arrays would
# initialize the jax backend at import time, foreclosing the platform
# selection the driver entry points must do first.
NEG_HI = _NEG >> 32
NEG_LO = _NEG & 0xFFFFFFFF


class SplitStore(NamedTuple):
    """DenseStore with 64-bit lanes split for native 32-bit compute.
    Slot empty ⇔ ``hi == NEG_HI``."""
    hi: jax.Array        # int32[N]  lt >> 32 (NEG_HI = empty)
    lo: jax.Array        # uint32[N] lt & 0xFFFFFFFF
    node: jax.Array      # int32[N]
    val_hi: jax.Array    # int32[N]
    val_lo: jax.Array    # uint32[N]
    tomb: jax.Array      # int32[N] 0/1
    mod_hi: jax.Array    # int32[N]
    mod_lo: jax.Array    # uint32[N]
    mod_node: jax.Array  # int32[N]


# Node ordinals ride an int16 changeset lane (ordinals count DISTINCT
# replica ids — far below 32k in any real deployment); the in-kernel
# compare widens to int32, so (lt, node) semantics are unchanged while
# the wire lane costs 2 bytes instead of 4. I16_NEG is the invalid
# sentinel (widens below any real ordinal, which are >= 0).
I16_NEG = -(2 ** 15)
MAX_NODE_ORDINAL = 2 ** 15 - 1


class SplitChangeset(NamedTuple):
    """[R, N] changeset lanes, invalid entries pre-masked to sentinels.
    Narrow wire lanes (int16 node, int8 tomb) cut HBM traffic per
    merge from 24 B to 19 B; compares run widened in-kernel."""
    hi: jax.Array      # int32[R, N] (NEG_HI = invalid)
    lo: jax.Array      # uint32[R, N]
    node: jax.Array    # int16[R, N] (I16_NEG when invalid)
    val_hi: jax.Array  # int32[R, N]
    val_lo: jax.Array  # uint32[R, N]
    tomb: jax.Array    # int8[R, N]


class NarrowSplitChangeset(NamedTuple):
    """[R, N] changeset lanes for the value-ref mode: ``val`` is a
    SINGLE int32 lane (sign-extended into the store's 64-bit payload
    in-kernel), for workloads whose values are int32-range scalars or
    indices into an application-side payload table (SURVEY.md §7 hard
    part 4 — the recommended shape for variable-length values). 15 B
    of HBM per merge instead of the wide form's 19 B."""
    hi: jax.Array    # int32[R, N] (NEG_HI = invalid)
    lo: jax.Array    # uint32[R, N]
    node: jax.Array  # int16[R, N] (I16_NEG when invalid)
    val: jax.Array   # int32[R, N]
    tomb: jax.Array  # int8[R, N]


@jax.jit
def split_changeset_narrow(cs: DenseChangeset
                           ) -> Tuple[NarrowSplitChangeset, jax.Array]:
    """`split_changeset` for the value-ref mode. Returns the narrow
    lanes AND a ``val_overflow`` device flag: True iff any valid value
    does not round-trip through int32 — the caller must check it (at
    its next batched fetch; merging out-of-range values in this mode
    would silently truncate payloads)."""
    v32 = cs.val.astype(jnp.int32)
    fits = v32.astype(jnp.int64) == cs.val
    overflow = jnp.any(cs.valid & ~fits)
    # Overflowing rows are masked INVALID, not truncated: a silently
    # narrowed payload under the peer's winning HLC could never be
    # repaired by any later merge (LWW ties keep the local record).
    ok = cs.valid & fits
    lt = jnp.where(ok, cs.lt, _NEG)
    hi, lo = _split64(lt)
    return NarrowSplitChangeset(
        hi=hi, lo=lo,
        node=jnp.where(ok, cs.node, I16_NEG).astype(jnp.int16),
        val=v32,
        tomb=cs.tomb.astype(jnp.int8)), overflow


def _split64(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    return ((x >> 32).astype(jnp.int32),
            (x & 0xFFFFFFFF).astype(jnp.uint32))


def _join64(hi: jax.Array, lo: jax.Array) -> jax.Array:
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)


@jax.jit
def split_store(store: DenseStore) -> SplitStore:
    lt = jnp.where(store.occupied, store.lt, _NEG)
    hi, lo = _split64(lt)
    val_hi, val_lo = _split64(store.val)
    mod_hi, mod_lo = _split64(store.mod_lt)
    return SplitStore(hi=hi, lo=lo, node=store.node, val_hi=val_hi,
                      val_lo=val_lo, tomb=store.tomb.astype(jnp.int32),
                      mod_hi=mod_hi, mod_lo=mod_lo,
                      mod_node=store.mod_node)


@jax.jit
def join_store(s: SplitStore) -> DenseStore:
    occupied = s.hi != NEG_HI
    return DenseStore(
        lt=jnp.where(occupied, _join64(s.hi, s.lo), 0),
        node=s.node, val=_join64(s.val_hi, s.val_lo),
        mod_lt=_join64(s.mod_hi, s.mod_lo), mod_node=s.mod_node,
        occupied=occupied, tomb=s.tomb.astype(bool))


@jax.jit
def split_changeset(cs: DenseChangeset) -> SplitChangeset:
    lt = jnp.where(cs.valid, cs.lt, _NEG)
    hi, lo = _split64(lt)
    val_hi, val_lo = _split64(cs.val)
    # Callers must keep node ordinals <= MAX_NODE_ORDINAL (the model
    # layer routes to the XLA fold beyond that); the cast would wrap
    # silently under jit, so the bound is enforced host-side.
    return SplitChangeset(
        hi=hi, lo=lo,
        node=jnp.where(cs.valid, cs.node, I16_NEG).astype(jnp.int16),
        val_hi=val_hi, val_lo=val_lo,
        tomb=cs.tomb.astype(jnp.int8))


def _lex_gt(a_hi, a_lo, a_node, b_hi, b_lo, b_node):
    """(hi, lo, node) strict lexicographic greater-than — native 32-bit."""
    return ((a_hi > b_hi) |
            ((a_hi == b_hi) & ((a_lo > b_lo) |
                               ((a_lo == b_lo) & (a_node > b_node)))))


class PallasFaninResult(NamedTuple):
    new_canonical: jax.Array  # int64 scalar (pre final-send-bump)
    win: jax.Array            # bool[N]
    any_dup: jax.Array        # bool
    any_drift: jax.Array      # bool


def _max64(a_hi, a_lo, b_hi, b_lo):
    """Scalar 64-bit max on split (i32 hi, u32 lo) pairs."""
    take_b = (b_hi > a_hi) | ((b_hi == a_hi) & (b_lo > a_lo))
    return jnp.where(take_b, b_hi, a_hi), jnp.where(take_b, b_lo, a_lo)


def _add_off64(hi, lo, off_u32):
    """(hi, lo) + off with carry propagation (off < 2**31)."""
    lo2 = lo + off_u32
    return hi + (lo2 < lo).astype(hi.dtype), lo2


def _fanin_stream_kernel(exact_guards, advance_clock, narrow_val,
                         scalars_ref, *refs):
    """Multi-chunk fan-in: grid (row_blocks, n_chunks); the store block
    stays VMEM-resident across the chunk dimension (block index constant
    in c), so HBM sees each store/changeset lane once per row block
    instead of once per chunk. Chunk ``c`` merges the changeset with
    every logicalTime advanced by ``c`` ms (the steady-state write
    stream that `benchmarks/changesets.py` `build_stream_fn` models);
    results are bit-identical to ``n_chunks`` sequential `fanin_step`
    folds threading the canonical clock.

    ``exact_guards`` (static): True runs the column-local shielded
    recv guards in-kernel (~half the per-row compute is the running
    cummax chain); False skips ALL in-kernel guard work — the caller
    derives superset flags from closed-form scalar reductions instead
    (see `pallas_fanin_stream`).

    ``advance_clock`` (static): True replays ONE changeset block with
    chunk clocks advancing 1ms per chunk (`pallas_fanin_stream`);
    False walks DISTINCT changeset row groups with no offsets — the
    chunked form of a single merge, stamped with the union-final
    canonical (`pallas_fanin_batch`)."""
    assert advance_clock or not exact_guards, (
        "exact guards are only defined for the clock-advancing stream")
    if narrow_val:
        # value-ref mode: ONE int32 val lane, sign-extended per row
        (cs_hi, cs_lo, cs_node, cs_v32, cs_tomb,
         st_hi, st_lo, st_node, st_vhi, st_vlo, st_tomb,
         st_mhi, st_mlo, st_mnode,
         o_hi, o_lo, o_node, o_vhi, o_vlo, o_tomb,
         o_mhi, o_mlo, o_mnode,
         win_ref, dup_ref, drift_ref, *acc_refs) = refs
    else:
        (cs_hi, cs_lo, cs_node, cs_vhi, cs_vlo, cs_tomb,
         st_hi, st_lo, st_node, st_vhi, st_vlo, st_tomb,
         st_mhi, st_mlo, st_mnode,
         o_hi, o_lo, o_node, o_vhi, o_vlo, o_tomb,
         o_mhi, o_mlo, o_mnode,
         win_ref, dup_ref, drift_ref, *acc_refs) = refs
    if not advance_clock:
        # Batch-only vector accumulators (unused outputs are not free:
        # three extra resident blocks measurably slowed the stream).
        bm_hi_ref, bm_lo_ref, dupacc_ref = acc_refs
    rb = pl.program_id(0)
    c = pl.program_id(1)
    first = c == 0

    @pl.when((rb == 0) & first)
    def _init():
        # Before ANY accumulation below (grid steps run in order).
        dup_ref[0, 0] = jnp.int32(0)
        drift_ref[0, 0] = jnp.int32(0)

    canon_hi = scalars_ref[0]
    canon_lo = scalars_ref[1].astype(jnp.uint32)
    local_node = scalars_ref[2]
    thresh_hi = scalars_ref[3]
    thresh_lo = scalars_ref[4].astype(jnp.uint32)
    bmax_hi = scalars_ref[5]
    bmax_lo = scalars_ref[6].astype(jnp.uint32)

    if advance_clock:
        off = (c << SHIFT).astype(jnp.uint32)
        # Canonical clock after chunk c (threaded exactly as the
        # sequential fold does): newc_c = max(canon_0,
        # basemax + c<<SHIFT); the run seed for chunk c is newc_{c-1}
        # (= canon_0 at c == 0).
        nc_hi, nc_lo = _max64(canon_hi, canon_lo,
                              *_add_off64(bmax_hi, bmax_lo, off))
    # else (batch): the union-final canonical is computed FROM this
    # kernel's own basemax output; winners' modified lanes are stamped
    # by the wrapper's cheap post-pass (the kernel carries the store's
    # mod lanes through untouched).

    b_hi = jnp.where(first, st_hi[...], o_hi[...])
    b_lo = jnp.where(first, st_lo[...], o_lo[...])
    b_node = jnp.where(first, st_node[...], o_node[...])
    b_vhi = jnp.where(first, st_vhi[...], o_vhi[...])
    b_vlo = jnp.where(first, st_vlo[...], o_vlo[...])
    b_tomb = jnp.where(first, st_tomb[...], o_tomb[...])
    win_prev = jnp.where(first, jnp.int32(0), win_ref[...])
    win = jnp.zeros(b_hi.shape, jnp.bool_)

    if exact_guards:
        pv_hi, pv_lo = _max64(
            canon_hi, canon_lo,
            *_add_off64(bmax_hi, bmax_lo,
                        ((c - 1) << SHIFT).astype(jnp.uint32)))
        seed_hi = jnp.where(first, canon_hi, pv_hi)
        seed_lo = jnp.where(first, canon_lo, pv_lo)
        run_hi = jnp.full(b_hi.shape, seed_hi, jnp.int32)
        run_lo = jnp.full(b_hi.shape, seed_lo, jnp.uint32)
        acc_dup = jnp.zeros(b_hi.shape, jnp.int32)
        acc_drift = jnp.zeros(b_hi.shape, jnp.int32)

    if advance_clock:
        # Sequential row walk. Exact guards NEED the running cummax
        # chain; the fast-guard replay stream ALSO keeps the chain —
        # its cs block is VMEM-resident across chunks (compute-bound),
        # and there the chain's smaller live set beats the tournament's
        # ILP (measured 72 vs 57 B merges/s on the stream row).
        for r in range(cs_hi.shape[0]):
            hi0 = cs_hi[r]
            lo0 = cs_lo[r]
            # Narrow wire lanes widen on load: compares are int32
            # either way, so (lt, node) semantics are untouched.
            node = cs_node[r].astype(jnp.int32)
            if advance_clock:
                # Advance the chunk clock on real lanes only: the NEG
                # sentinel must stay the unique minimum (its lo is 0,
                # so a masked offset also never carries into hi).
                lo = lo0 + jnp.where(hi0 == NEG_HI, jnp.uint32(0), off)
                hi = hi0 + (lo < lo0).astype(jnp.int32)
            else:
                hi, lo = hi0, lo0

            if exact_guards:
                slow = _lex_gt(hi, lo, jnp.int32(0),
                               run_hi, run_lo, jnp.int32(0))
                dup = slow & (node == local_node)
                drift = (slow & ~dup &
                         _lex_gt(hi, lo, jnp.int32(0),
                                 thresh_hi, thresh_lo, jnp.int32(0)))
                acc_dup = acc_dup | dup.astype(jnp.int32)
                acc_drift = acc_drift | drift.astype(jnp.int32)
                run_hi = jnp.where(slow, hi, run_hi)
                run_lo = jnp.where(slow, lo, run_lo)

            gt = _lex_gt(hi, lo, node, b_hi, b_lo, b_node)
            b_hi = jnp.where(gt, hi, b_hi)
            b_lo = jnp.where(gt, lo, b_lo)
            b_node = jnp.where(gt, node, b_node)
            if narrow_val:
                v = cs_v32[r]
                # sign-extend into the store's 64-bit payload: hi word
                # is the sign fill; lo word the int32 bits (signed->
                # unsigned convert is modular, a bit-preserving wrap)
                b_vhi = jnp.where(gt, v >> 31, b_vhi)
                b_vlo = jnp.where(gt, v.astype(jnp.uint32), b_vlo)
            else:
                b_vhi = jnp.where(gt, cs_vhi[r], b_vhi)
                b_vlo = jnp.where(gt, cs_vlo[r], b_vlo)
            b_tomb = jnp.where(gt, cs_tomb[r].astype(jnp.int32), b_tomb)
            win = win | gt
    else:
        # No in-kernel guard work: reduce the rows as a TOURNAMENT
        # TREE instead of a sequential running-best chain. Same op
        # count, but pair merges at each level are independent, so
        # Mosaic can hide the whole VPU cost behind the DMA — measured
        # 7.4 -> ~20 B merges/s on the distinct batch row (the
        # same-layout pure-copy ceiling; docs/PERF.md round 5).
        # Tie-break parity: pairs are (lower row, higher row) and the
        # higher row wins only on STRICT (lt, node) greatership, so
        # the lowest replica row survives ties at every level —
        # exactly the sequential chain's stable order (associative,
        # so the bracket shape doesn't matter).
        items = []
        dup_any = None
        for r in range(cs_hi.shape[0]):
            hi = cs_hi[r]
            lo = cs_lo[r]
            node = cs_node[r].astype(jnp.int32)
            # Batch self-reduction (see below): dup candidates are
            # local-node records above the pre-merge canonical — the
            # closed-form bound, evaluated while the rows are already
            # VMEM-resident instead of as a separate XLA sweep over
            # the whole changeset. Accumulated as a VECTOR mask
            # (elementwise OR per row) — per-row scalar reduces
            # measurably stall the VPU.
            row_dup = ((node == local_node) &
                       _lex_gt(hi, lo, jnp.int32(0),
                               canon_hi, canon_lo, jnp.int32(0)))
            dup_any = (row_dup if dup_any is None
                       else dup_any | row_dup)
            if narrow_val:
                v = cs_v32[r]
                vhi, vlo = v >> 31, v.astype(jnp.uint32)
            else:
                vhi, vlo = cs_vhi[r], cs_vlo[r]
            items.append((hi, lo, node, vhi, vlo,
                          cs_tomb[r].astype(jnp.int32)))
        while len(items) > 1:
            nxt = []
            for i in range(0, len(items) - 1, 2):
                a, b = items[i], items[i + 1]
                gt = _lex_gt(b[0], b[1], b[2], a[0], a[1], a[2])
                nxt.append(tuple(jnp.where(gt, bb, aa)
                                 for aa, bb in zip(a, b)))
            if len(items) % 2:
                nxt.append(items[-1])
            items = nxt
        w = items[0]
        gt = _lex_gt(w[0], w[1], w[2], b_hi, b_lo, b_node)
        b_hi = jnp.where(gt, w[0], b_hi)
        b_lo = jnp.where(gt, w[1], b_lo)
        b_node = jnp.where(gt, w[2], b_node)
        b_vhi = jnp.where(gt, w[3], b_vhi)
        b_vlo = jnp.where(gt, w[4], b_vlo)
        b_tomb = jnp.where(gt, w[5], b_tomb)
        win = win | gt
        # The canonical/flag reductions fold INTO this kernel (the
        # rows are right here in VMEM; the XLA sweeps they replace
        # cost more than the whole join) — but as ELEMENTWISE vector
        # accumulators, never in-kernel cross-lane reduces (those
        # stall the VPU measurably; the wrapper reduces the one
        # accumulated block in XLA):
        # - dupacc: per-position OR of the dup-candidate masks;
        # - bm: per-position (hi, lo) max64 of the per-key winners
        #   across grid steps.
        init = (rb == 0) & first
        prev_hi = jnp.where(init, jnp.int32(NEG_HI), bm_hi_ref[...])
        prev_lo = jnp.where(init, jnp.uint32(0), bm_lo_ref[...])
        take = ((w[0] > prev_hi) |
                ((w[0] == prev_hi) & (w[1] > prev_lo)))
        bm_hi_ref[...] = jnp.where(take, w[0], prev_hi)
        bm_lo_ref[...] = jnp.where(take, w[1], prev_lo)
        prev_dup = jnp.where(init, jnp.int32(0), dupacc_ref[...])
        dupacc_ref[...] = prev_dup | dup_any.astype(jnp.int32)

    o_hi[...] = b_hi
    o_lo[...] = b_lo
    o_node[...] = b_node
    o_vhi[...] = b_vhi
    o_vlo[...] = b_vlo
    o_tomb[...] = b_tomb
    m_hi = jnp.where(first, st_mhi[...], o_mhi[...])
    m_lo = jnp.where(first, st_mlo[...], o_mlo[...])
    m_node = jnp.where(first, st_mnode[...], o_mnode[...])
    if advance_clock:
        o_mhi[...] = jnp.where(win, nc_hi, m_hi)
        o_mlo[...] = jnp.where(win, nc_lo, m_lo)
        o_mnode[...] = jnp.where(win, local_node, m_node)
    else:
        # Batch: stamped post-kernel (nc needs this kernel's basemax).
        o_mhi[...] = m_hi
        o_mlo[...] = m_lo
        o_mnode[...] = m_node
    win_ref[...] = win_prev | win.astype(jnp.int32)

    if exact_guards:
        dup_ref[0, 0] = dup_ref[0, 0] | jnp.max(acc_dup)
        drift_ref[0, 0] = drift_ref[0, 0] | jnp.max(acc_drift)


# Tile geometry: (sublane, lane) int32 tiles (Mosaic floor: sublane %
# 8 == 0, lane % 128 == 0). The two kernels want DIFFERENT tiles
# (measured on v5e with the narrow int16/int8 wire lanes, 48-loop
# production-kernel runs — not synthetic probes, which mislead on
# this platform; docs/PERF.md):
#
# - distinct batch (HBM-bound; every chunk reads fresh rows):
#   (8, 512) → 7.40B merges/s vs 6.6B at (8, 1024), 7.0B at (8, 2048).
# - stream replay (compute-bound; the cs block is VMEM-resident
#   across chunks): (8, 1024) → 69B vs 58B at (8, 512).
#
# TILE (the n_slots alignment floor, 4096) is the batch tile; the
# stream path upgrades its lane width to 1024 when n_slots allows.
_SB = 8
_LANE = 512
TILE = _SB * _LANE  # n_slots must be a multiple of this
_STREAM_LANE = 1024


def mosaic_auto(n_slots: int, platform: str) -> bool:
    """The ``executor="auto"`` rule every Mosaic path shares (model
    merges and ingest commits, the sharded fan-in per key shard):
    Mosaic lowers on TPU only, over tile-aligned lanes."""
    return platform == "tpu" and n_slots % TILE == 0


def _stream_tile_lane(n: int) -> int:
    return _STREAM_LANE if n % (_SB * _STREAM_LANE) == 0 else _LANE


def tile_changeset(scs, lane: int = _LANE):
    """Pre-tile split wire lanes to the kernel's resident
    ``(r, n//lane, lane)`` layout. A TPU reshape across tile
    boundaries is a physical relayout copy (~2.4 GB for the 1M×128
    batch — comparable to the join's own HBM traffic, measured ~7 ms
    of the old 15 ms call); batches that LIVE in HBM between merges
    should be stored pre-tiled so each merge doesn't re-pay it. 2-D
    lanes remain accepted by every kernel wrapper (the reshape then
    happens in-jit, where it can fuse with a producing split)."""
    r, n = scs.hi.shape
    if n % (_SB * lane):
        raise ValueError(f"n={n} not tileable at lane={lane}")
    return type(scs)(*(l.reshape(r, n // lane, lane) for l in scs))


def _cs_shape(cs) -> Tuple[int, int]:
    """(r, n) for 2-D or pre-tiled 3-D changeset lanes."""
    if cs.hi.ndim == 3:
        r, rows, lane = cs.hi.shape
        return r, rows * lane
    return cs.hi.shape


def pad_split_rows(cs, multiple: int):
    """Pad a split (2-D or tiled 3-D) changeset's replica axis with
    INVALID rows (hi=NEG_HI, node=I16_NEG, zeros elsewhere) up to a
    multiple — the split-lane counterpart of `ops.dense.
    pad_replica_rows`, for callers feeding `pallas_fanin_batch`'s
    chunk_rows requirement with pre-split wire data."""
    r = cs.hi.shape[0]
    pad = (-r) % multiple
    if not pad:
        return cs
    out = {}
    for f in cs._fields:
        lane = getattr(cs, f)
        fill = NEG_HI if f == "hi" else (I16_NEG if f == "node" else 0)
        out[f] = jnp.concatenate([
            lane, jnp.full((pad,) + lane.shape[1:], fill, lane.dtype)])
    return type(cs)(**out)


@jax.jit
def split_guard_lanes(hi, lo, node, node_map):
    """Just the three lanes recv guards read — ``(lt, node, valid)``
    with LOCAL ordinals — from split wire lanes, without
    reconstructing the payload (exact-guard pipelined windows need
    these every merge; `split_to_wide` would rebuild all five)."""
    r = hi.shape[0]
    hi2 = hi.reshape(r, -1)
    valid = hi2 != NEG_HI
    lt = _join64(hi2, lo.reshape(r, -1))
    idx = jnp.clip(node.reshape(r, -1), 0,
                   node_map.shape[0] - 1).astype(jnp.int32)
    return lt, node_map.astype(jnp.int32)[idx], valid


@jax.jit
def split_to_wide(cs) -> DenseChangeset:
    """Reconstruct wide `DenseChangeset` lanes from split wire lanes
    (either width) — the exact inverse of `split_changeset`[`_narrow`]
    up to the masked content of invalid entries (which no consumer
    reads). Used by the model layer's non-kernel fallback and the
    failure-path exact guard recompute."""
    r, n = _cs_shape(cs)
    flat = type(cs)(*(l.reshape(r, n) if l.ndim == 3 else l
                      for l in cs))
    valid = flat.hi != NEG_HI
    lt = jnp.where(valid, _join64(flat.hi, flat.lo), 0)
    if isinstance(cs, NarrowSplitChangeset):
        val = flat.val.astype(jnp.int64)
    else:
        val = _join64(flat.val_hi, flat.val_lo)
    return DenseChangeset(
        lt=lt,
        node=jnp.where(valid, flat.node.astype(jnp.int32), 0),
        val=val,
        tomb=flat.tomb.astype(bool),
        valid=valid,
    )


@partial(jax.jit, static_argnames=("interpret",))
def pallas_fanin_step(store: SplitStore, cs: SplitChangeset,
                      canonical_lt: jax.Array, local_node: jax.Array,
                      wall_millis: jax.Array, *,
                      interpret: bool = False
                      ) -> Tuple[SplitStore, PallasFaninResult]:
    """Fused single-changeset fan-in on split lanes — the ``n_chunks=1``
    case of `pallas_fanin_stream` (one kernel, one semantics). Same
    store-lane/canonical results as `ops.dense.fanin_step`; guard flags
    per the module docstring. ``n_slots`` must be a multiple of
    ``TILE`` (= ``_SB * _LANE``)."""
    return pallas_fanin_stream(store, cs, canonical_lt, local_node,
                               wall_millis, n_chunks=1,
                               interpret=interpret)


@partial(jax.jit, static_argnames=("n_chunks", "guards", "interpret"))
def pallas_fanin_stream(store: SplitStore, cs: SplitChangeset,
                        canonical_lt: jax.Array, local_node: jax.Array,
                        wall_millis: jax.Array, *, n_chunks: int,
                        guards: str = "exact",
                        interpret: bool = False
                        ) -> Tuple[SplitStore, PallasFaninResult]:
    """``n_chunks`` sequential fan-in folds fused into ONE kernel launch.

    Chunk ``c`` applies ``cs`` with every logicalTime advanced by ``c``
    ms and the canonical clock threaded through (the steady-state write
    stream). Bit-identical store/canonical results to the equivalent
    loop of `fanin_step` / `pallas_fanin_step` calls, but the store
    block is VMEM-resident across the chunk grid dimension, so HBM
    traffic is ~``n_chunks``× lower than the sequential loop: the
    memory system sees each store and changeset lane once per row block.

    ``guards`` selects the recv-guard executor (store lanes, canonical
    and ``win`` are identical either way):

    - ``"exact"`` — the column-local shielded semantics in-kernel
      (flags bit-identical to `pallas_fanin_step` loops). The running
      cummax chain is ~half the per-row compute.
    - ``"fast"`` — optimistic guards: ZERO per-row guard work; flags
      come from closed-form scalar bounds (max local-node logicalTime
      vs the threaded canonical; changeset max vs the drift
      threshold). A strict SUPERSET of the exact flags — no anomaly is
      missed, but a shielded record may flag. The model layer's
      contract already handles this: on any trip it recomputes the
      guards exactly on host for first-offender diagnostics and clears
      false positives (`DenseCrdt._exact_guards`). Measured ~1.9×
      faster at the 1M×1024 headline.

    ``win`` is the OR across chunks (slots adopted at least once);
    ``new_canonical`` is the post-final-chunk canonical time.
    """
    r, n = _cs_shape(cs)
    if n % TILE:  # ValueError, not assert: must survive `python -O`
        raise ValueError(f"n_slots={n} not a multiple of TILE={TILE}")
    if not 0 < n_chunks < (1 << 15):  # c << 16 must fit int32
        raise ValueError(f"n_chunks={n_chunks} out of range [1, 2^15)")
    rows = n // _LANE

    if guards not in ("exact", "fast"):
        # ValueError, not assert: a stripped assert under `python -O`
        # would silently route an unknown mode to the fast branch.
        raise ValueError(f"unknown guards mode {guards!r}")
    # Base changeset max (chunk 0's clock ceiling): chunk c's ceiling is
    # basemax + c<<SHIFT, threaded against canonical in-kernel.
    m_hi = jnp.max(cs.hi)
    m_lo = jnp.max(jnp.where(cs.hi == m_hi, cs.lo, 0))
    # The replayed changeset block is VMEM-resident across the chunk
    # dim, so its lane width costs nothing in HBM — widen the narrow
    # wire lanes ONCE here and the in-kernel astype becomes identity
    # (the compute-bound replay loses no VPU cycles to widening).
    if isinstance(cs, NarrowSplitChangeset):
        v = cs.val
        cs = SplitChangeset(hi=cs.hi, lo=cs.lo,
                            node=cs.node.astype(jnp.int32),
                            val_hi=v >> 31,
                            val_lo=v.astype(jnp.uint32),
                            tomb=cs.tomb.astype(jnp.int32))
    else:
        cs = cs._replace(node=cs.node.astype(jnp.int32),
                         tomb=cs.tomb.astype(jnp.int32))
    outs = _launch_stream_grid(
        guards == "exact", True, store, cs, canonical_lt, local_node,
        wall_millis, m_hi, m_lo, cs_block_rows=r,
        cs_index_map=lambda i, c: (jnp.int32(0), jnp.int32(i),
                                   jnp.int32(0)),
        n_chunks=n_chunks, interpret=interpret,
        lane=_stream_tile_lane(n))

    final_off = ((n_chunks - 1) << SHIFT)
    basemax = _join64(m_hi, m_lo)
    new_canonical = jnp.maximum(canonical_lt, basemax + final_off)
    new_store = SplitStore(*(o.reshape(n) for o in outs[:9]))

    if guards == "exact":
        any_dup = outs[10][0, 0] > 0
        any_drift = outs[11][0, 0] > 0
    else:
        # Optimistic superset flags in closed form. A chunk-c dup
        # candidate is a local-node record above the threaded canonical
        # newc_{c-1} = max(canon_0, basemax + (c-1)<<SHIFT); with
        # M_loc = max local-node logicalTime, "exists c" collapses to
        # the c=0 test plus (for c>=1) a c-independent bound against
        # basemax and the last chunk's test against canon_0. Drift is
        # the changeset ceiling vs the wall threshold.
        m_loc = _max_local_lt(cs, local_node)
        any_dup = m_loc > canonical_lt
        if n_chunks > 1:
            any_dup = any_dup | ((m_loc > basemax - (1 << SHIFT))
                                 & (m_loc + final_off > canonical_lt))
        thresh = ((wall_millis + MAX_DRIFT) << SHIFT) | MAX_COUNTER
        any_drift = basemax + final_off > thresh

    return new_store, PallasFaninResult(
        new_canonical=new_canonical,
        win=outs[9].reshape(n).astype(bool),
        any_dup=any_dup,
        any_drift=any_drift,
    )


def _max_local_lt(cs: SplitChangeset, local_node: jax.Array) -> jax.Array:
    """Max logicalTime over the changeset's local-node records (the
    closed-form dup-candidate bound); NEG when there are none."""
    loc = cs.node.astype(jnp.int32) == local_node
    ml_hi = jnp.max(jnp.where(loc, cs.hi, NEG_HI))
    ml_lo = jnp.max(jnp.where(loc & (cs.hi == ml_hi), cs.lo, 0))
    return _join64(ml_hi, ml_lo)


def _launch_stream_grid(exact_guards, advance_clock, store, cs,
                        canonical_lt, local_node, wall_millis, m_hi, m_lo,
                        *, cs_block_rows, cs_index_map, n_chunks,
                        interpret, lane=_LANE):
    """Shared pallas_call plumbing for the (row_blocks, n_chunks) grid:
    scalar stack, block specs, reshapes, out shapes, store aliasing.
    The two wrappers differ only in the kernel's static flags, the
    changeset block geometry/index map, and the tile lane width."""
    r, n = _cs_shape(cs)
    rows = n // lane
    canon_hi, canon_lo = _split64(canonical_lt)
    thresh_hi, thresh_lo = _split64(
        ((wall_millis + MAX_DRIFT) << SHIFT) | MAX_COUNTER)
    scalars = jnp.stack([
        canon_hi, canon_lo.astype(jnp.int32), local_node,
        thresh_hi, thresh_lo.astype(jnp.int32),
        m_hi, m_lo.astype(jnp.int32)]).astype(jnp.int32)

    _i32 = jnp.int32
    cs_spec = pl.BlockSpec((cs_block_rows, _SB, lane), cs_index_map,
                           memory_space=pltpu.VMEM)
    st_spec = pl.BlockSpec((_SB, lane), lambda i, c: (_i32(i), _i32(0)),
                           memory_space=pltpu.VMEM)
    flag_spec = pl.BlockSpec((1, 1), lambda i, c: (_i32(0), _i32(0)),
                             memory_space=pltpu.SMEM)

    st2d = [ln.reshape(rows, lane) for ln in store]
    if cs.hi.ndim == 3 and cs.hi.shape[2] == lane:
        cs3d = list(cs)    # pre-tiled: no per-call relayout copy
    else:
        if cs.hi.ndim == 3:   # tiled at another lane width: flatten
            cs = type(cs)(*(ln.reshape(r, n) for ln in cs))
        cs3d = [ln.reshape(r, rows, lane) for ln in cs]

    out_shapes = (
        [jax.ShapeDtypeStruct((rows, lane), ln.dtype) for ln in st2d] +
        [jax.ShapeDtypeStruct((rows, lane), jnp.int32),   # win (OR)
         jax.ShapeDtypeStruct((1, 1), jnp.int32),         # any_dup
         jax.ShapeDtypeStruct((1, 1), jnp.int32)])        # any_drift
    out_specs = [st_spec] * 9 + [st_spec, flag_spec, flag_spec]
    if not advance_clock:
        # Batch-mode vector accumulators: ONE (_SB, lane) block shared
        # by every grid step (constant index map; TPU grids run
        # sequentially). Batch-only — unused resident outputs are not
        # free (three extra blocks measurably slowed the stream).
        acc_spec = pl.BlockSpec((_SB, lane),
                                lambda i, c: (_i32(0), _i32(0)),
                                memory_space=pltpu.VMEM)
        out_shapes += [jax.ShapeDtypeStruct((_SB, lane), jnp.int32),
                       jax.ShapeDtypeStruct((_SB, lane), jnp.uint32),
                       jax.ShapeDtypeStruct((_SB, lane), jnp.int32)]
        out_specs += [acc_spec] * 3

    n_cs = len(cs3d)   # 6 wide lanes, 5 in value-ref (narrow) mode
    return pl.pallas_call(
        partial(_fanin_stream_kernel, exact_guards, advance_clock,
                n_cs == 5),
        grid=(rows // _SB, n_chunks),
        in_specs=([pl.BlockSpec((7,), lambda i, c: (_i32(0),),
                                memory_space=pltpu.SMEM)] +
                  [cs_spec] * n_cs + [st_spec] * 9),
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
        input_output_aliases={1 + n_cs + j: j for j in range(9)},
        interpret=interpret,
    )(scalars, *cs3d, *st2d)


@partial(jax.jit,
         static_argnames=("chunk_rows", "interpret", "value_width"))
def _model_fanin_batch_jit(store, cs, canonical_lt, local_node,
                           wall_millis, *, chunk_rows: int = 16,
                           interpret: bool = False,
                           value_width: int = 64):
    """The model layer's ONE-dispatch merge: wide `DenseStore` +
    `DenseChangeset` in, wide store out — split/convert, the batch
    kernel, and the re-join all inside a single jit. Every separate
    dispatch costs a host round trip, and an unfused model merge was
    paying four of them per call. A store held as resident planes
    goes in and comes out as planes (`_batch_on_store`).

    Returns ``(new_store, PallasFaninResult, seen, val_overflow)``
    where ``seen`` counts valid lanes (the stats counter) and
    ``val_overflow`` flags value_width=32 range violations (those
    records are masked out of the join, never truncated)."""
    if value_width == 32:
        fits = cs.val.astype(jnp.int32).astype(jnp.int64) == cs.val
        val_overflow = jnp.any(cs.valid & ~fits)
        cs = cs._replace(valid=cs.valid & fits)
        scs, _ = split_changeset_narrow.__wrapped__(cs)
    else:
        val_overflow = jnp.asarray(False)
        scs = split_changeset.__wrapped__(cs)
    seen = jnp.sum(cs.valid)
    out, res = _batch_on_store(store, scs, canonical_lt, local_node,
                               wall_millis, chunk_rows, interpret)
    return out, res, seen, val_overflow


def _batch_on_store(store, scs, canonical_lt, local_node, wall_millis,
                    chunk_rows, interpret):
    """`pallas_fanin_batch` on the model's store in the form it holds:
    a wide `DenseStore` is split in and joined out; resident 2-D
    `SplitStore` planes (`ops.pallas_scatter`) go in and come back as
    planes, with no whole-store split or join."""
    if isinstance(store, SplitStore):
        out, res = pallas_fanin_batch.__wrapped__(
            store, scs, canonical_lt, local_node, wall_millis,
            chunk_rows=chunk_rows, interpret=interpret)
        return SplitStore(*(o.reshape(store.hi.shape) for o in out)), res
    out, res = pallas_fanin_batch.__wrapped__(
        split_store.__wrapped__(store), scs, canonical_lt, local_node,
        wall_millis, chunk_rows=chunk_rows, interpret=interpret)
    return join_store.__wrapped__(out), res


def model_fanin_batch(store, cs, canonical_lt, local_node, wall_millis,
                      **kw):
    """Ledger-recording host entry for `_model_fanin_batch_jit` (the
    fused model merge; see its docstring for semantics)."""
    name = _obs_device.kernel_label("pallas.model_fanin_batch",
                                    kw.get("interpret", False))
    with _obs_device.record(name, dim=cs.valid.shape[0]):
        return _model_fanin_batch_jit(store, cs, canonical_lt,
                                      local_node, wall_millis, **kw)


# Trace-time composition (`pipelined_model_step`) fuses through the
# UN-jitted body, bypassing the ledger wrapper — in-jit calls are not
# dispatches.
model_fanin_batch.__wrapped__ = _model_fanin_batch_jit.__wrapped__


@partial(jax.jit,
         static_argnames=("chunk_rows", "interpret", "value_width"))
def _model_fanin_split_jit(store, cs, node_map, canonical_lt,
                           local_node, wall_millis, *,
                           chunk_rows: int = 16, interpret: bool = False,
                           value_width: int = 64):
    """`model_fanin_batch` for a PRE-SPLIT (optionally pre-tiled)
    changeset — the zero-conversion gossip path: peers exchange the
    kernel wire form (`DenseCrdt.export_split_delta`) and the merge
    skips the per-call split/tile entirely.

    ``node_map`` (int16[peer_table_len]) rewrites the changeset's
    node ordinals into the local table IN-JIT (each eager dispatch is
    a host round trip; pass the identity map when tables already
    match — the gather fuses away to a copy).

    Same return contract as `model_fanin_batch`:
    ``(new_store, PallasFaninResult, seen, val_overflow)``. A
    value_width=32 replica receiving WIDE split lanes masks records
    whose payload is not a sign-extension of its low word (invalid,
    never truncated) and flags ``val_overflow``; narrow lanes fit by
    construction."""
    idx = jnp.clip(cs.node, 0, node_map.shape[0] - 1).astype(jnp.int32)
    cs = cs._replace(node=jnp.where(
        cs.node == jnp.int16(I16_NEG), jnp.int16(I16_NEG),
        node_map[idx]))
    if value_width == 32 and not isinstance(cs, NarrowSplitChangeset):
        fits = cs.val_hi == (
            cs.val_lo.astype(jnp.int32) >> 31)
        val_overflow = jnp.any((cs.hi != NEG_HI) & ~fits)
        inval = ~fits
        # Full sentinel masking (hi AND lo AND node): a half-masked
        # entry with hi=NEG_HI but a nonzero lo would beat an empty
        # store slot's (NEG_HI, 0) in the strict lex compare.
        cs = cs._replace(
            hi=jnp.where(inval, jnp.int32(NEG_HI), cs.hi),
            lo=jnp.where(inval, jnp.uint32(0), cs.lo),
            node=jnp.where(inval, jnp.int16(I16_NEG), cs.node))
    else:
        val_overflow = jnp.asarray(False)
    seen = jnp.sum(cs.hi != NEG_HI)
    out, res = _batch_on_store(store, cs, canonical_lt, local_node,
                               wall_millis, chunk_rows, interpret)
    return out, res, seen, val_overflow


def model_fanin_split(store, cs, node_map, canonical_lt, local_node,
                      wall_millis, **kw):
    """Ledger-recording host entry for `_model_fanin_split_jit` (the
    pre-split fused model merge; see its docstring for semantics)."""
    name = _obs_device.kernel_label("pallas.model_fanin_split",
                                    kw.get("interpret", False))
    with _obs_device.record(name, dim=cs.hi.shape[0]):
        return _model_fanin_split_jit(store, cs, node_map, canonical_lt,
                                      local_node, wall_millis, **kw)


model_fanin_split.__wrapped__ = _model_fanin_split_jit.__wrapped__


@partial(jax.jit,
         static_argnames=("chunk_rows", "interpret", "value_width"))
def _pipelined_model_step_jit(store, cs, canonical, any_bad, overflow,
                              drift, val_ovf, first_idx, local_node,
                              wall_merge, wall_send, merge_idx, *,
                              chunk_rows: int = 16,
                              interpret: bool = False,
                              value_width: int = 64):
    """One COARSE pipelined merge as a single dispatch: the fused
    model merge (`model_fanin_batch`) plus the window bookkeeping the
    model layer otherwise runs as separate eager ops — flag
    OR-accumulation, first-flag attribution, and the final send bump
    (`ops.merge.send_step`). Each separate dispatch is a host round
    trip; at the north-star e2e shape the bookkeeping dispatches were
    costing more than the merge itself.

    ``wall_merge`` is the absorption-phase wall read, ``wall_send``
    the send bump's — two reads, like the unfused path, so injected
    clocks tick identically. Returns the full window-state update:
    ``(new_store, new_canonical, any_bad, overflow, drift,
    val_overflow, first_flag_idx, win_count, win, seen)``."""
    new_store, pres, seen, voverflow = model_fanin_batch.__wrapped__(
        store, cs, canonical, local_node, wall_merge,
        chunk_rows=chunk_rows, interpret=interpret,
        value_width=value_width)
    return _pipelined_tail(new_store, pres, seen, voverflow,
                           value_width, any_bad, overflow, drift,
                           val_ovf, first_idx, merge_idx, wall_send)


def pipelined_model_step(store, cs, *args, **kw):
    """Ledger-recording host entry for `_pipelined_model_step_jit`
    (the coarse pipelined merge; see its docstring for semantics)."""
    name = _obs_device.kernel_label("pallas.pipelined_model_step",
                                    kw.get("interpret", False))
    with _obs_device.record(name, dim=cs.valid.shape[0]):
        return _pipelined_model_step_jit(store, cs, *args, **kw)


pipelined_model_step.__wrapped__ = _pipelined_model_step_jit.__wrapped__


@partial(jax.jit,
         static_argnames=("chunk_rows", "interpret", "value_width"))
def _pipelined_model_step_split_jit(store, cs, node_map, canonical,
                                    any_bad, overflow, drift, val_ovf,
                                    first_idx, local_node, wall_merge,
                                    wall_send, merge_idx, *,
                                    chunk_rows: int = 16,
                                    interpret: bool = False,
                                    value_width: int = 64):
    """`pipelined_model_step` for PRE-SPLIT changesets (`merge_split`
    in a coarse window) — the interchange path gets the same
    one-dispatch treatment, else fusing only the wide path would make
    the zero-conversion gossip route the slower of the two."""
    new_store, pres, seen, voverflow = model_fanin_split.__wrapped__(
        store, cs, node_map, canonical, local_node, wall_merge,
        chunk_rows=chunk_rows, interpret=interpret,
        value_width=value_width)
    return _pipelined_tail(new_store, pres, seen, voverflow,
                           value_width, any_bad, overflow, drift,
                           val_ovf, first_idx, merge_idx, wall_send)


def pipelined_model_step_split(store, cs, *args, **kw):
    """Ledger-recording host entry for
    `_pipelined_model_step_split_jit` (the pre-split coarse pipelined
    merge; see its docstring for semantics)."""
    name = _obs_device.kernel_label("pallas.pipelined_model_step_split",
                                    kw.get("interpret", False))
    with _obs_device.record(name, dim=cs.hi.shape[0]):
        return _pipelined_model_step_split_jit(store, cs, *args, **kw)


pipelined_model_step_split.__wrapped__ = \
    _pipelined_model_step_split_jit.__wrapped__


def _pipelined_tail(new_store, pres, seen, voverflow, value_width,
                    any_bad, overflow, drift, val_ovf, first_idx,
                    merge_idx, wall_send):
    """Shared in-jit window bookkeeping: flag OR-accumulation,
    first-flag attribution, and the final send bump."""
    from .merge import send_step
    recv_flag = pres.any_dup | pres.any_drift
    new_flags = recv_flag | (voverflow if value_width == 32
                             else jnp.asarray(False))
    newly = (first_idx < 0) & new_flags
    first_idx = jnp.where(newly, merge_idx, first_idx)
    new_lt, s_ovf, s_drift = send_step.__wrapped__(pres.new_canonical,
                                                   wall_send)
    newly2 = (first_idx < 0) & (s_ovf | s_drift)
    first_idx = jnp.where(newly2, merge_idx, first_idx)
    return (new_store, new_lt, any_bad | recv_flag, overflow | s_ovf,
            drift | s_drift, val_ovf | voverflow, first_idx,
            jnp.sum(pres.win).astype(jnp.int32), pres.win, seen)


@partial(jax.jit, static_argnames=("chunk_rows", "interpret"))
def pallas_fanin_batch(store: SplitStore, cs: SplitChangeset,
                       canonical_lt: jax.Array, local_node: jax.Array,
                       wall_millis: jax.Array, *, chunk_rows: int = 8,
                       interpret: bool = False
                       ) -> Tuple[SplitStore, PallasFaninResult]:
    """ONE logical merge of an [R, N] changeset, walked in-kernel as
    ``R / chunk_rows`` DISTINCT row groups with the store block
    VMEM-resident across the chunk grid dimension — the kernel
    counterpart of `ops.dense.fanin_stream` (union-final canonical
    stamping, no per-chunk clock offsets). Store lanes, ``win``, and
    ``new_canonical`` match `pallas_fanin_step` on the full batch
    bit-for-bit; guard flags are the optimistic closed-form superset
    (`pallas_fanin_stream` guards="fast" contract): the model layer
    recomputes exactly on host when one trips.

    ``r`` must be a multiple of ``chunk_rows`` (pad with invalid rows)
    and ``n_slots`` a multiple of ``TILE``."""
    r, n = _cs_shape(cs)
    if n % TILE:  # ValueError, not assert: must survive `python -O`
        raise ValueError(f"n_slots={n} not a multiple of TILE={TILE}")
    if r % chunk_rows:
        raise ValueError(f"replica rows {r} not a multiple of "
                         f"chunk_rows={chunk_rows} (pad with invalid rows)")
    n_chunks = r // chunk_rows

    # No XLA pre-reductions: basemax and the dup bound come OUT of the
    # kernel (the rows are resident in VMEM there anyway; separate XLA
    # sweeps over the [R, N] lanes cost more than the whole join —
    # docs/PERF.md round 5). Chunk c reads row group c — the block
    # index map's only difference from the replay stream.
    outs = _launch_stream_grid(
        False, False, store, cs, canonical_lt, local_node, wall_millis,
        jnp.int32(0), jnp.uint32(0), cs_block_rows=chunk_rows,
        cs_index_map=lambda i, c: (c, jnp.int32(i), jnp.int32(0)),
        n_chunks=n_chunks, interpret=interpret)

    # Reduce the kernel's one accumulated (_SB, lane) block here in
    # XLA (4096 elements — negligible next to the lanes themselves).
    acc_hi, acc_lo, dupacc = outs[12], outs[13], outs[14]
    bm_hi = jnp.max(acc_hi)
    bm_lo = jnp.max(jnp.where(acc_hi == bm_hi, acc_lo, 0))
    basemax = _join64(bm_hi, bm_lo)
    thresh = ((wall_millis + MAX_DRIFT) << SHIFT) | MAX_COUNTER
    new_canonical = jnp.maximum(canonical_lt, basemax)
    win2d = outs[9]
    # Winners' modified stamp as a cheap elementwise post-pass over the
    # three mod lanes only (the kernel carried the store's through):
    # nc wasn't known until the kernel's own basemax came back.
    nc_hi, nc_lo = _split64(new_canonical)
    winb = win2d > 0
    mod_hi = jnp.where(winb, nc_hi, outs[6])
    mod_lo = jnp.where(winb, nc_lo, outs[7])
    mod_node = jnp.where(winb, local_node, outs[8])
    new_store = SplitStore(*(
        o.reshape(n) for o in
        (outs[0], outs[1], outs[2], outs[3], outs[4], outs[5],
         mod_hi, mod_lo, mod_node)))

    # Optimistic superset flags (no offsets, so the c=0 bound covers
    # every chunk): a local-node record above the pre-merge canonical
    # (OR-accumulated in-kernel), or any record past the drift
    # threshold.
    return new_store, PallasFaninResult(
        new_canonical=new_canonical,
        win=win2d.reshape(n).astype(bool),
        any_dup=jnp.max(dupacc) > 0,
        any_drift=basemax > thresh,
    )
