"""Hardware validation: the Mosaic kernels vs the XLA fold ON THE CHIP.

The test suite proves kernel correctness in interpreter mode on CPU;
this script closes the remaining gap — Mosaic compilation could in
principle diverge from the interpreter — by running randomized
differentials on the real accelerator:

1. `pallas_fanin_stream` (exact and fast guards) vs the sequential
   XLA fold with threaded clocks — store lanes, win, canonical.
2. `pallas_fanin_batch` vs one-shot `fanin_step` on the same batch.
3. `DenseCrdt(executor="pallas")` vs `DenseCrdt(executor="xla")`
   through the model API, including a guard-trip exception.

Exits 0 and prints PASS per section; any mismatch raises.

Usage: python benchmarks/validate_on_chip.py [--keys 32768] [--seeds 3]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root

import numpy as np

import jax
import jax.numpy as jnp

from changesets import build_stream_fn, make_changeset, _MILLIS
from crdt_tpu.hlc import SHIFT
from crdt_tpu.ops.dense import empty_dense_store, fanin_step
from crdt_tpu.ops.pallas_merge import (join_store, pallas_fanin_batch,
                                       pallas_fanin_stream,
                                       split_changeset, split_store)


from crdt_tpu.testing import assert_dense_stores_equal as \
    assert_lanes_equal  # noqa: E402  (one definition of store equality)


def validate_stream(n_keys, n_chunks, seed):
    cs = make_changeset(4, n_keys, seed=seed, fill=0.7)
    canonical = jnp.int64(_MILLIS << SHIFT)
    wall = jnp.int64(_MILLIS + 10_000)
    store = empty_dense_store(n_keys)

    # The reference semantics ARE changesets.build_stream_fn — the
    # sequential XLA fold with threaded clocks.
    ref_store, ref_canon = build_stream_fn(n_chunks)(
        store, cs, canonical, jnp.int32(0), wall)
    ref_canon = int(ref_canon)

    for guards in ("exact", "fast"):
        sst, sres = pallas_fanin_stream(
            split_store(store), split_changeset(cs), canonical,
            jnp.int32(0), wall, n_chunks=n_chunks, guards=guards)
        assert_lanes_equal(ref_store, join_store(sst),
                           f"stream[{guards}] seed={seed}")
        assert int(sres.new_canonical) == ref_canon, guards
        assert not bool(sres.any_dup) and not bool(sres.any_drift)

    # Positive guard path ON HARDWARE: a local-node (ordinal 0) record
    # ahead of the canonical clock must set any_dup in BOTH guard
    # modes (the exact in-kernel cummax chain and the closed-form
    # bound) — an all-clear-only check would miss a compiled-kernel
    # flag bug.
    dup_cs = cs._replace(
        node=cs.node.at[0, 0].set(0),
        valid=cs.valid.at[0, 0].set(True))
    for guards in ("exact", "fast"):
        _, dres = pallas_fanin_stream(
            split_store(store), split_changeset(dup_cs),
            jnp.int64(0), jnp.int32(0), wall, n_chunks=n_chunks,
            guards=guards)
        assert bool(dres.any_dup), f"dup flag missed [{guards}]"
    print(f"PASS stream n_chunks={n_chunks} seed={seed} "
          f"(exact+fast bit-identical to XLA fold; dup flag fires)")


def validate_batch(n_keys, seed):
    cs = make_changeset(16, n_keys, seed=seed + 50, fill=0.6)
    canonical = jnp.int64(_MILLIS << SHIFT)
    wall = jnp.int64(_MILLIS + 10_000)
    store = empty_dense_store(n_keys)

    ref_store, ref_res = fanin_step(store, cs, canonical, jnp.int32(0),
                                    wall)
    sst, sres = pallas_fanin_batch(
        split_store(store), split_changeset(cs), canonical, jnp.int32(0),
        wall, chunk_rows=8)
    assert_lanes_equal(ref_store, join_store(sst), f"batch seed={seed}")
    assert int(sres.new_canonical) == int(ref_res.new_canonical)
    np.testing.assert_array_equal(np.asarray(sres.win),
                                  np.asarray(ref_res.win))
    # value-ref (int32 val lane) mode, incl. negative payloads: the
    # sign-extension must land bit-identical on hardware too.
    from crdt_tpu.ops.pallas_merge import split_changeset_narrow
    ncs_src = cs._replace(
        val=((cs.val & 0xFFFFFFFF).astype(jnp.int32)).astype(jnp.int64))
    nref_store, nref_res = fanin_step(store, ncs_src, canonical,
                                      jnp.int32(0), wall)
    ncs, overflow = split_changeset_narrow(ncs_src)
    assert not bool(overflow)
    nst, nres = pallas_fanin_batch(
        split_store(store), ncs, canonical, jnp.int32(0), wall,
        chunk_rows=8)
    assert_lanes_equal(nref_store, join_store(nst),
                       f"narrow batch seed={seed}")
    assert int(nres.new_canonical) == int(nref_res.new_canonical)
    print(f"PASS batch seed={seed} (16 rows, chunked 8, == fanin_step; "
          "narrow valref32 incl. negatives)")


def validate_model(n_keys):
    from crdt_tpu import DenseCrdt, DuplicateNodeException
    from crdt_tpu.testing import FakeClock
    BASE = _MILLIS
    pal = DenseCrdt("ns", n_keys, wall_clock=FakeClock(start=BASE),
                    executor="pallas")
    xla = DenseCrdt("ns", n_keys, wall_clock=FakeClock(start=BASE),
                    executor="xla")
    peers = []
    for i, name in enumerate(["p1", "p2", "p3"]):
        p = DenseCrdt(name, n_keys, wall_clock=FakeClock(start=BASE + i))
        p.put_batch(jnp.arange(i * 100, i * 100 + 500),
                    jnp.arange(500, dtype=jnp.int64) + 1000 * i)
        p.delete_batch(jnp.arange(i * 100, i * 100 + 7))
        peers.append(p.export_delta())
    pal.merge_many(peers)
    xla.merge_many(peers)
    assert_lanes_equal(pal.store, xla.store, "model")
    assert pal.canonical_time == xla.canonical_time

    bad = DenseCrdt("ns", n_keys, wall_clock=FakeClock(start=BASE + 900))
    bad.put_batch([1], [1])
    payloads = []
    for c in (pal, xla):
        try:
            c.merge_many([bad.export_delta()])
            raise AssertionError("guard did not trip")
        except DuplicateNodeException as e:
            payloads.append((str(e), c.canonical_time.logical_time))
    assert payloads[0] == payloads[1], payloads
    print("PASS model (pallas executor == xla executor on chip, "
          "guard payloads identical)")


def validate_model_soak(n_keys, rounds, seed=0):
    """Randomized op-sequence soak: a pallas-executor replica and an
    xla-executor replica apply IDENTICAL random local writes, deletes,
    multi-peer merges, and clears; lane equality is asserted after
    every round."""
    import random
    from crdt_tpu import DenseCrdt
    from crdt_tpu.testing import FakeClock
    rng = random.Random(seed)
    BASE = _MILLIS
    pal_clk = FakeClock(start=BASE)
    xla_clk = FakeClock(start=BASE)
    pal = DenseCrdt("ns", n_keys, wall_clock=pal_clk, executor="pallas")
    xla = DenseCrdt("ns", n_keys, wall_clock=xla_clk, executor="xla")
    peer_clk = BASE
    for rnd in range(rounds):
        # Keep the replicas' wall clocks tracking the peer clock:
        # unbounded divergence would eventually trip the (correct)
        # drift guard as a harness artifact, not a finding.
        for clk in (pal_clk, xla_clk):
            clk.advance(max(0, peer_clk - clk.millis))
        op = rng.random()
        if op < 0.35:
            k = rng.randrange(1, 200)
            slots = rng.sample(range(n_keys), k)
            vals = [rng.randrange(1 << 40) for _ in slots]
            pal.put_batch(slots, vals)
            xla.put_batch(slots, vals)
        elif op < 0.5:
            slots = rng.sample(range(n_keys), rng.randrange(1, 50))
            pal.delete_batch(slots)
            xla.delete_batch(slots)
        elif op < 0.9:
            deltas = []
            for p in range(rng.randrange(1, 5)):
                peer_clk += rng.randrange(1, 4)
                peer = DenseCrdt(f"p{rng.randrange(6)}", n_keys,
                                 wall_clock=FakeClock(start=peer_clk))
                slots = rng.sample(range(n_keys), rng.randrange(1, 300))
                peer.put_batch(slots, [rng.randrange(1 << 40)
                                       for _ in slots])
                if rng.random() < 0.4:
                    peer.delete_batch(rng.sample(slots,
                                                 max(1, len(slots) // 4)))
                deltas.append(peer.export_delta())
            pal.merge_many(deltas)
            xla.merge_many(deltas)
        else:
            # clear(): tombstone every live slot via one batch
            pal.clear()
            xla.clear()
        assert_lanes_equal(pal.store, xla.store, f"soak round {rnd}")
        assert pal.canonical_time.logical_time == \
            xla.canonical_time.logical_time, rnd
    print(f"PASS soak ({rounds} rounds, seed={seed}: pallas == xla "
          "after every round)")


def validate_merge_split(n_keys, seed=0):
    """`merge_split` (pre-split interchange, tiled and untiled, wide
    and value-ref lanes) vs the wide `merge` path ON THE CHIP — the
    interpret-mode differentials re-run against real Mosaic output."""
    import random
    from crdt_tpu import DenseCrdt
    from crdt_tpu.ops.pallas_merge import split_changeset
    from crdt_tpu.testing import FakeClock
    rng = random.Random(seed)
    BASE = _MILLIS
    for value_width, tiled in ((64, True), (64, False), (32, True)):
        writers = []
        for i, nid in enumerate(("wa", "wb", "wc")):
            w = DenseCrdt(nid, n_keys, value_width=value_width,
                          wall_clock=FakeClock(start=BASE + 1 + i))
            slots = rng.sample(range(n_keys), 200)
            w.put_batch(slots, [rng.randrange(1 << 30) for _ in slots])
            w.delete_batch(rng.sample(slots, 40))
            writers.append(w)
        via_split = DenseCrdt("hub", n_keys, value_width=value_width,
                              wall_clock=FakeClock(start=BASE + 50))
        via_wide = DenseCrdt("hub", n_keys, value_width=value_width,
                             wall_clock=FakeClock(start=BASE + 50))
        for w in writers:
            scs, ids = w.export_split_delta(tiled=tiled)
            via_split.merge_split(scs, ids)
            via_wide.merge(*w.export_delta())
        assert_lanes_equal(via_split.store, via_wide.store,
                           f"merge_split vw={value_width} tiled={tiled}")
        assert (via_split.canonical_time.logical_time
                == via_wide.canonical_time.logical_time)
    # multi-row (r > chunk) padded form through the real kernel
    rows = 12
    lt = ((_MILLIS + np.random.default_rng(seed).integers(
        0, 50, (rows, n_keys))) << SHIFT)
    from crdt_tpu.ops.dense import DenseChangeset
    rng2 = np.random.default_rng(seed + 1)
    cs = DenseChangeset(
        lt=jnp.asarray(lt, jnp.int64),
        node=jnp.asarray(rng2.integers(0, 3, (rows, n_keys)), jnp.int32),
        val=jnp.asarray(rng2.integers(0, 1 << 40, (rows, n_keys)),
                        jnp.int64),
        tomb=jnp.asarray(rng2.random((rows, n_keys)) < 0.3),
        valid=jnp.asarray(rng2.random((rows, n_keys)) < 0.7))
    ids = ["pa", "pb", "pc"]
    a = DenseCrdt("hub", n_keys, wall_clock=FakeClock(start=_MILLIS + 99))
    b = DenseCrdt("hub", n_keys, wall_clock=FakeClock(start=_MILLIS + 99))
    a.merge_split(split_changeset(cs), ids)
    b.merge(cs, ids)
    assert_lanes_equal(a.store, b.store, "merge_split multirow")
    # the one condition unique to this section: padding rows must not
    # leak their sentinels into the canonical either
    assert (a.canonical_time.logical_time
            == b.canonical_time.logical_time)
    print("PASS merge_split (tiled/untiled, wide/valref, multirow "
          "padded == wide merge on chip)")


def main():
    from crdt_tpu.ops.pallas_merge import TILE
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=4 * 8192)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--soak", type=int, default=0, metavar="ROUNDS",
                    help="additionally run a randomized op-sequence "
                         "soak of this many rounds")
    ap.add_argument("--soak-seed", type=int, default=0)
    args = ap.parse_args()
    if args.keys % TILE:
        ap.error(f"--keys must be a multiple of the Pallas tile "
                 f"({TILE}); got {args.keys}")
    print(f"platform: {jax.devices()[0].platform} ({jax.devices()[0]})")
    for seed in range(args.seeds):
        validate_stream(args.keys, n_chunks=4, seed=seed)
        validate_batch(args.keys, seed)
    validate_model(args.keys)
    validate_merge_split(args.keys)
    if args.soak:
        validate_model_soak(args.keys, args.soak, seed=args.soak_seed)
    print("ALL PASS")


if __name__ == "__main__":
    main()
