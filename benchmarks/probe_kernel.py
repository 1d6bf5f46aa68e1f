"""Kernel-variant probe: where does the fan-in kernel's time go?

Runs the headline shape through three kernel variants to split the
compute vs HBM budget:

- ``full``    — the production kernel (guards + join).
- ``nojoin``  — guards removed, join only (upper bound on guard cost).
- ``copy``    — no compute: stream cs + store through VMEM, write
  store back (the pure memory-bandwidth ceiling for this layout).

The variant kernels deliberately carry their own copies of the
pallas_call scaffolding: they exist to measure layout effects, so they
must be free to drift from the production geometry without touching it.

Usage: python benchmarks/probe_kernel.py [--keys N] [--replicas N]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from changesets import make_changeset, _MILLIS
from crdt_tpu.hlc import SHIFT
from crdt_tpu.ops.dense import empty_dense_store
from crdt_tpu.ops.pallas_merge import (_SB, _LANE, _lex_gt, _split64,
                                       NEG_HI, pallas_fanin_step,
                                       pallas_fanin_stream,
                                       split_changeset, split_store)


def _join_only_kernel(scalars_ref,
                      cs_hi, cs_lo, cs_node, cs_vhi, cs_vlo, cs_tomb,
                      st_hi, st_lo, st_node, st_vhi, st_vlo, st_tomb,
                      st_mhi, st_mlo, st_mnode,
                      o_hi, o_lo, o_node, o_vhi, o_vlo, o_tomb,
                      o_mhi, o_mlo, o_mnode, win_ref):
    local_node = scalars_ref[2]
    newc_hi = scalars_ref[5]
    newc_lo = scalars_ref[6].astype(jnp.uint32)
    b_hi = st_hi[...]
    b_lo = st_lo[...]
    b_node = st_node[...]
    b_vhi = st_vhi[...]
    b_vlo = st_vlo[...]
    b_tomb = st_tomb[...]
    win = jnp.zeros(b_hi.shape, jnp.bool_)
    for r in range(cs_hi.shape[0]):
        hi = cs_hi[r]
        lo = cs_lo[r]
        node = cs_node[r]
        gt = _lex_gt(hi, lo, node, b_hi, b_lo, b_node)
        b_hi = jnp.where(gt, hi, b_hi)
        b_lo = jnp.where(gt, lo, b_lo)
        b_node = jnp.where(gt, node, b_node)
        b_vhi = jnp.where(gt, cs_vhi[r], b_vhi)
        b_vlo = jnp.where(gt, cs_vlo[r], b_vlo)
        b_tomb = jnp.where(gt, cs_tomb[r], b_tomb)
        win = win | gt
    o_hi[...] = b_hi
    o_lo[...] = b_lo
    o_node[...] = b_node
    o_vhi[...] = b_vhi
    o_vlo[...] = b_vlo
    o_tomb[...] = b_tomb
    o_mhi[...] = jnp.where(win, newc_hi, st_mhi[...])
    o_mlo[...] = jnp.where(win, newc_lo, st_mlo[...])
    o_mnode[...] = jnp.where(win, local_node, st_mnode[...])
    win_ref[...] = win.astype(jnp.int32)


def _copy_kernel(scalars_ref,
                 cs_hi, cs_lo, cs_node, cs_vhi, cs_vlo, cs_tomb,
                 st_hi, st_lo, st_node, st_vhi, st_vlo, st_tomb,
                 st_mhi, st_mlo, st_mnode,
                 o_hi, o_lo, o_node, o_vhi, o_vlo, o_tomb,
                 o_mhi, o_mlo, o_mnode, win_ref):
    r_last = cs_hi.shape[0] - 1
    # Touch every cs row so nothing is DCE'd, with one add per lane.
    a_hi = cs_hi[0]
    a_lo = cs_lo[0]
    for r in range(1, r_last + 1):
        a_hi = a_hi + cs_hi[r]
        a_lo = a_lo + cs_lo[r]
    o_hi[...] = st_hi[...] + a_hi
    o_lo[...] = st_lo[...] + a_lo
    o_node[...] = st_node[...] + cs_node[r_last]
    o_vhi[...] = st_vhi[...] + cs_vhi[r_last]
    o_vlo[...] = st_vlo[...] + cs_vlo[r_last]
    o_tomb[...] = st_tomb[...] + cs_tomb[r_last]
    o_mhi[...] = st_mhi[...]
    o_mlo[...] = st_mlo[...]
    o_mnode[...] = st_mnode[...]
    win_ref[...] = cs_node[r_last]


def _stream_noguard_kernel(n_chunks_ignored, scalars_ref,
                           cs_hi, cs_lo, cs_node, cs_vhi, cs_vlo, cs_tomb,
                           st_hi, st_lo, st_node, st_vhi, st_vlo, st_tomb,
                           st_mhi, st_mlo, st_mnode,
                           o_hi, o_lo, o_node, o_vhi, o_vlo, o_tomb,
                           o_mhi, o_mlo, o_mnode, win_ref):
    """The stream kernel's join with ALL guard work removed — isolates
    the guard cost inside the fused chunk loop."""
    c = pl.program_id(1)
    first = c == 0
    local_node = scalars_ref[2]
    off = (c << SHIFT).astype(jnp.uint32)
    b_hi = jnp.where(first, st_hi[...], o_hi[...])
    b_lo = jnp.where(first, st_lo[...], o_lo[...])
    b_node = jnp.where(first, st_node[...], o_node[...])
    b_vhi = jnp.where(first, st_vhi[...], o_vhi[...])
    b_vlo = jnp.where(first, st_vlo[...], o_vlo[...])
    b_tomb = jnp.where(first, st_tomb[...], o_tomb[...])
    win_prev = jnp.where(first, jnp.int32(0), win_ref[...])
    win = jnp.zeros(b_hi.shape, jnp.bool_)
    for r in range(cs_hi.shape[0]):
        hi0 = cs_hi[r]
        lo0 = cs_lo[r]
        node = cs_node[r]
        lo = lo0 + jnp.where(hi0 == NEG_HI, jnp.uint32(0), off)
        hi = hi0 + (lo < lo0).astype(jnp.int32)
        gt = _lex_gt(hi, lo, node, b_hi, b_lo, b_node)
        b_hi = jnp.where(gt, hi, b_hi)
        b_lo = jnp.where(gt, lo, b_lo)
        b_node = jnp.where(gt, node, b_node)
        b_vhi = jnp.where(gt, cs_vhi[r], b_vhi)
        b_vlo = jnp.where(gt, cs_vlo[r], b_vlo)
        b_tomb = jnp.where(gt, cs_tomb[r], b_tomb)
        win = win | gt
    o_hi[...] = b_hi
    o_lo[...] = b_lo
    o_node[...] = b_node
    o_vhi[...] = b_vhi
    o_vlo[...] = b_vlo
    o_tomb[...] = b_tomb
    o_mhi[...] = jnp.where(win, scalars_ref[5], st_mhi[...])
    o_mlo[...] = jnp.where(win, scalars_ref[6].astype(jnp.uint32),
                           st_mlo[...])
    o_mnode[...] = jnp.where(win, local_node, st_mnode[...])
    win_ref[...] = win_prev | win.astype(jnp.int32)


def _stream_call(kernel, store, cs, scalars, n_chunks):
    from functools import partial
    r, n = cs.hi.shape
    rows = n // _LANE
    _i32 = jnp.int32
    cs_spec = pl.BlockSpec((r, _SB, _LANE),
                           lambda i, c: (_i32(0), _i32(i), _i32(0)),
                           memory_space=pltpu.VMEM)
    st_spec = pl.BlockSpec((_SB, _LANE), lambda i, c: (_i32(i), _i32(0)),
                           memory_space=pltpu.VMEM)
    st2d = [lane.reshape(rows, _LANE) for lane in store]
    cs3d = [lane.reshape(r, rows, _LANE) for lane in cs]
    out_shapes = (
        [jax.ShapeDtypeStruct((rows, _LANE), lane.dtype) for lane in st2d] +
        [jax.ShapeDtypeStruct((rows, _LANE), jnp.int32)])
    outs = pl.pallas_call(
        partial(kernel, n_chunks),
        grid=(rows // _SB, n_chunks),
        in_specs=([pl.BlockSpec((7,), lambda i, c: (_i32(0),),
                                memory_space=pltpu.SMEM)] +
                  [cs_spec] * 6 + [st_spec] * 9),
        out_specs=tuple([st_spec] * 10),
        out_shape=tuple(out_shapes),
        input_output_aliases={1 + 6 + j: j for j in range(9)},
    )(scalars, *cs3d, *st2d)
    return outs[0].reshape(n)


def _variant_call(kernel, store, cs, scalars):
    r, n = cs.hi.shape
    rows = n // _LANE
    _i32 = jnp.int32
    cs_spec = pl.BlockSpec((r, _SB, _LANE),
                           lambda i: (_i32(0), _i32(i), _i32(0)),
                           memory_space=pltpu.VMEM)
    st_spec = pl.BlockSpec((_SB, _LANE), lambda i: (_i32(i), _i32(0)),
                           memory_space=pltpu.VMEM)
    st2d = [lane.reshape(rows, _LANE) for lane in store]
    cs3d = [lane.reshape(r, rows, _LANE) for lane in cs]
    out_shapes = (
        [jax.ShapeDtypeStruct((rows, _LANE), lane.dtype) for lane in st2d] +
        [jax.ShapeDtypeStruct((rows, _LANE), jnp.int32)])
    outs = pl.pallas_call(
        kernel,
        grid=(rows // _SB,),
        in_specs=([pl.BlockSpec((7,), lambda i: (_i32(0),),
                                memory_space=pltpu.SMEM)] +
                  [cs_spec] * 6 + [st_spec] * 9),
        out_specs=tuple([st_spec] * 10),
        out_shape=tuple(out_shapes),
        input_output_aliases={1 + 6 + j: j for j in range(9)},
    )(scalars, *cs3d, *st2d)
    return outs[0].reshape(n)


def run_variant(name: str, n_keys: int, n_replicas: int, chunk: int,
                repeats: int = 3) -> float:
    n_chunks = n_replicas // chunk
    store = split_store(empty_dense_store(n_keys))
    cs = split_changeset(make_changeset(chunk, n_keys, seed=0))
    canonical = jnp.int64(_MILLIS << SHIFT)
    wall = jnp.int64(_MILLIS + 10_000)

    if name == "full":
        @jax.jit
        def run(store, cs):
            def body(i, carry):
                st, canon = carry
                st2, res = pallas_fanin_step(st, cs, canon, jnp.int32(0),
                                             wall)
                return (st2, res.new_canonical)
            st, canon = jax.lax.fori_loop(0, n_chunks, body,
                                          (store, canonical))
            return st.hi, canon
    elif name == "stream":
        @jax.jit
        def run(store, cs):
            st, res = pallas_fanin_stream(store, cs, canonical,
                                          jnp.int32(0), wall,
                                          n_chunks=n_chunks)
            return st.hi, res.new_canonical
    elif name == "stream-noguard":
        canon_hi, canon_lo = _split64(canonical)
        scalars = jnp.stack([canon_hi, canon_lo.astype(jnp.int32),
                             jnp.int32(0), canon_hi,
                             canon_lo.astype(jnp.int32), canon_hi,
                             canon_lo.astype(jnp.int32)]).astype(jnp.int32)

        @jax.jit
        def run(store, cs):
            hi = _stream_call(_stream_noguard_kernel, store, cs, scalars,
                              n_chunks)
            return hi, hi[0]
    else:
        kernel = _join_only_kernel if name == "nojoin" else _copy_kernel
        canon_hi, canon_lo = _split64(canonical)
        scalars = jnp.stack([canon_hi, canon_lo.astype(jnp.int32),
                             jnp.int32(0), canon_hi,
                             canon_lo.astype(jnp.int32), canon_hi,
                             canon_lo.astype(jnp.int32)]).astype(jnp.int32)

        @jax.jit
        def run(store, cs):
            def body(i, st):
                hi = _variant_call(kernel, st, cs, scalars)
                return st._replace(hi=hi)
            st = jax.lax.fori_loop(0, n_chunks, body, store)
            return st.hi, st.hi[0]

    out, tok = run(store, cs)
    jax.device_get(tok)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out, tok = run(store, cs)
        jax.device_get(tok)
        best = min(best, time.perf_counter() - t0)
    merges = int(jnp.sum(cs.hi != cs.hi.min())) * n_chunks
    gbytes = ((6 * chunk + 2 * 9) * n_keys * 4) * n_chunks / 1e9
    print(f"{name:8s} {best * 1e3:8.1f} ms   {merges / best / 1e9:6.2f} "
          f"B merges/s   {gbytes / best:6.1f} GB/s effective")
    return best


def _copy_batch_kernel(narrow_val, scalars_ref, *refs):
    """Pure-copy at the EXACT production batch geometry (VERDICT r4
    item 4): same narrow wire lanes, same (8, 512) tile, same
    (row_block, chunk) grid and index maps as `pallas_fanin_batch` —
    chunk c reads row group c while the store block stays resident
    across c. One add per lane defeats DCE; no compares, no selects.
    What this measures IS the memory system's ceiling for the
    distinct-batch layout."""
    if narrow_val:
        (cs_hi, cs_lo, cs_node, cs_v32, cs_tomb,
         st_hi, st_lo, st_node, st_vhi, st_vlo, st_tomb,
         st_mhi, st_mlo, st_mnode,
         o_hi, o_lo, o_node, o_vhi, o_vlo, o_tomb,
         o_mhi, o_mlo, o_mnode, win_ref) = refs
    else:
        (cs_hi, cs_lo, cs_node, cs_vhi, cs_vlo, cs_tomb,
         st_hi, st_lo, st_node, st_vhi, st_vlo, st_tomb,
         st_mhi, st_mlo, st_mnode,
         o_hi, o_lo, o_node, o_vhi, o_vlo, o_tomb,
         o_mhi, o_mlo, o_mnode, win_ref) = refs
    c = pl.program_id(1)
    first = c == 0
    a_hi = cs_hi[0]
    a_lo = cs_lo[0]
    a_node = cs_node[0]
    # i8 vector adds don't lower on Mosaic; widen on load like the
    # production kernel (the VMEM read is still 1 B/lane)
    a_tomb = cs_tomb[0].astype(jnp.int32)
    if narrow_val:
        a_v = cs_v32[0]
    else:
        a_vhi = cs_vhi[0]
        a_vlo = cs_vlo[0]
    for r in range(1, cs_hi.shape[0]):
        a_hi = a_hi + cs_hi[r]
        a_lo = a_lo + cs_lo[r]
        a_node = a_node + cs_node[r]
        a_tomb = a_tomb + cs_tomb[r].astype(jnp.int32)
        if narrow_val:
            a_v = a_v + cs_v32[r]
        else:
            a_vhi = a_vhi + cs_vhi[r]
            a_vlo = a_vlo + cs_vlo[r]
    o_hi[...] = jnp.where(first, st_hi[...], o_hi[...]) + a_hi
    o_lo[...] = jnp.where(first, st_lo[...], o_lo[...]) + a_lo
    o_node[...] = (jnp.where(first, st_node[...], o_node[...])
                   + a_node.astype(jnp.int32))
    if narrow_val:
        o_vhi[...] = jnp.where(first, st_vhi[...], o_vhi[...]) + (a_v >> 31)
        o_vlo[...] = (jnp.where(first, st_vlo[...], o_vlo[...])
                      + a_v.astype(jnp.uint32))
    else:
        o_vhi[...] = jnp.where(first, st_vhi[...], o_vhi[...]) + a_vhi
        o_vlo[...] = jnp.where(first, st_vlo[...], o_vlo[...]) + a_vlo
    o_tomb[...] = jnp.where(first, st_tomb[...], o_tomb[...]) + a_tomb
    o_mhi[...] = jnp.where(first, st_mhi[...], o_mhi[...])
    o_mlo[...] = jnp.where(first, st_mlo[...], o_mlo[...])
    o_mnode[...] = jnp.where(first, st_mnode[...], o_mnode[...])
    win_ref[...] = a_node.astype(jnp.int32)


def run_batch_copy(n_keys: int, n_rows: int, chunk_rows: int = 16,
                   loops: int = 48, value_width: int = 64,
                   repeats: int = 3) -> float:
    """The distinct-rows protocol with `pallas_fanin_batch` swapped
    for the same-layout pure-copy kernel: identical narrow lanes,
    tiles, grid, index maps, store aliasing, loop chaining, and fence.
    The merges/s this prints is the HBM ceiling the production
    distinct row can be compared against directly."""
    from functools import partial
    from crdt_tpu.ops.pallas_merge import split_changeset_narrow
    store = split_store(empty_dense_store(n_keys))
    cs = make_changeset(n_rows, n_keys, seed=0)
    merges = int(jnp.sum(cs.valid))
    if value_width == 32:
        scs, _ = split_changeset_narrow(cs._replace(val=cs.val & 0x7FFFFFFF))
    else:
        scs = split_changeset(cs)
    jax.block_until_ready(scs)
    del cs
    n_cs = len(scs)
    r, n = scs.hi.shape
    rows = n // _LANE
    n_chunks = r // chunk_rows
    _i32 = jnp.int32
    scalars = jnp.zeros((7,), jnp.int32)
    cs_spec = pl.BlockSpec((chunk_rows, _SB, _LANE),
                           lambda i, c: (c, _i32(i), _i32(0)),
                           memory_space=pltpu.VMEM)
    st_spec = pl.BlockSpec((_SB, _LANE), lambda i, c: (_i32(i), _i32(0)),
                           memory_space=pltpu.VMEM)
    cs3d = [lane.reshape(r, rows, _LANE) for lane in scs]
    st_dtypes = [lane.dtype for lane in store]
    out_shapes = ([jax.ShapeDtypeStruct((rows, _LANE), d)
                   for d in st_dtypes] +
                  [jax.ShapeDtypeStruct((rows, _LANE), jnp.int32)])

    call = pl.pallas_call(
        partial(_copy_batch_kernel, n_cs == 5),
        grid=(rows // _SB, n_chunks),
        in_specs=([pl.BlockSpec((7,), lambda i, c: (_i32(0),),
                                memory_space=pltpu.SMEM)] +
                  [cs_spec] * n_cs + [st_spec] * 9),
        out_specs=tuple([st_spec] * 10),
        out_shape=tuple(out_shapes),
        input_output_aliases={1 + n_cs + j: j for j in range(9)},
    )

    @jax.jit
    def run(st2d, cs3d):
        outs = call(scalars, *cs3d, *st2d)
        return list(outs[:9]), outs[0][0, 0]

    st2d = [lane.reshape(rows, _LANE) for lane in store]
    st2d, tok = run(st2d, cs3d)
    jax.device_get(tok)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(loops):
            st2d, tok = run(st2d, cs3d)
        jax.device_get(tok)
        best = min(best, time.perf_counter() - t0)
    cs_bytes = sum(ln.dtype.itemsize for ln in scs) * r * n
    gbytes = cs_bytes * loops / 1e9   # store blocks amortize over chunks
    name = f"copy-batch{'-valref' if n_cs == 5 else ''}"
    print(f"{name:18s} {best * 1e3:8.1f} ms   "
          f"{merges * loops / best / 1e9:6.2f} B merges/s   "
          f"{gbytes / best:6.1f} GB/s cs-lane traffic")
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1 << 20)
    ap.add_argument("--replicas", type=int, default=1024)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--variants", default="full,nojoin,copy")
    ap.add_argument("--rows", type=int, default=128,
                    help="copy-batch: HBM-resident distinct rows")
    ap.add_argument("--loops", type=int, default=48)
    args = ap.parse_args()
    for name in args.variants.split(","):
        if name == "copy-batch":
            run_batch_copy(args.keys, args.rows, loops=args.loops)
        elif name == "copy-batch-valref":
            run_batch_copy(args.keys, args.rows, loops=args.loops,
                           value_width=32)
        else:
            run_variant(name, args.keys, args.replicas, args.chunk)


if __name__ == "__main__":
    main()
