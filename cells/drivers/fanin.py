"""Window driver for the fan-in: back-to-back `DenseCrdt.merge_many`
passes of the replicas' changesets into the same base store.

Set-up makes, on the device and from the seed, the base store and one
``[replicas, keys]`` changeset (each row one replica's, valid on a
share of the keys, some rows tombstones, clocks spread over the last
``clock_spread_ms`` so that winners come from every replica), then runs
two passes to compile and warm every program. Each pass of the window
copies the base store, builds a replica on the copy and merges the
changeset into it, so every pass does the same work. Every replica
knows every member's id (the steady state of a group: the replica's
node table and the changeset's are the same sorted list), so the merge
reads the changeset's node ordinals as they are. After the window the
last pass's store is compared with the plain fold
(`cells.reference.fanin_fold`).
"""

from __future__ import annotations

import time

import numpy as np

from cells import harness
from cells.reference import fanin_fold

BASE = "base"
PASS_SPAN = "fanin.pass"


def before_jax(cell, seed: int, seconds: float) -> dict:
    return {}


def after(pre: dict) -> None:
    pass


def _draws(seed, lane: int, shape):
    """Independent uint64 draws per element and lane, from the seed."""
    import jax.numpy as jnp
    idx = jnp.arange(int(np.prod(shape)), dtype=jnp.uint64).reshape(shape)
    mix = harness.splitmix64
    return mix(idx ^ mix(seed + jnp.uint64(lane)))


def _share(bits, p: float):
    import jax.numpy as jnp
    return (bits >> jnp.uint64(11)) < jnp.uint64(int(p * (1 << 53)))


def _inputs(seed, now_ms, *, replicas: int, keys: int, valid: float,
            tomb: float, spread_ms: int, base_occupied: float):
    import jax.numpy as jnp
    from crdt_tpu.ops.dense import DenseChangeset, DenseStore

    def stamps(lane, shape):
        ms = now_ms - 1 - (_draws(seed, lane, shape)
                           % jnp.uint64(spread_ms)).astype(jnp.int64)
        ctr = (_draws(seed, lane + 1, shape) % jnp.uint64(16)
               ).astype(jnp.int64)
        return (ms << 16) | ctr

    def values(lane, shape):
        import jax
        return jax.lax.bitcast_convert_type(_draws(seed, lane, shape),
                                            jnp.int64)

    n, r = (keys,), (replicas, keys)
    occ = _share(_draws(seed, 0, n), base_occupied)
    b_lt = jnp.where(occ, stamps(1, n), 0)
    base = DenseStore(
        lt=b_lt, node=jnp.zeros(n, jnp.int32), val=values(3, n),
        mod_lt=b_lt, mod_node=jnp.zeros(n, jnp.int32), occupied=occ,
        tomb=occ & _share(_draws(seed, 4, n), tomb))
    # Row i is replica i's, whose id sorts at i + 1 after the base's.
    cs = DenseChangeset(
        lt=stamps(5, r),
        node=jnp.broadcast_to(
            jnp.arange(1, replicas + 1, dtype=jnp.int32)[:, None], r),
        val=values(7, r), tomb=_share(_draws(seed, 8, r), tomb),
        valid=_share(_draws(seed, 9, r), valid))
    return base, cs


def _copy(store):
    import jax
    return jax.tree_util.tree_map(lambda x: x.copy(), store)


def run(cell, seed: int, seconds: float, pre: dict, meter, tracer,
        t_start: float, devices, hooks) -> harness.Outcome:
    import jax
    import jax.numpy as jnp
    from functools import partial
    from crdt_tpu import DenseCrdt

    cfg = cell.sizes("config")
    r, n = int(cfg["replicas"]), int(cfg["keys"])
    names = [BASE] + [f"p{i:04d}" for i in range(r)]
    if names != sorted(names):
        raise ValueError("node names must sort base first, then by row")
    make = jax.jit(partial(
        _inputs, replicas=r, keys=n, valid=float(cfg["valid_share"]),
        tomb=float(cfg["tomb_share"]), spread_ms=int(cfg["clock_spread_ms"]),
        base_occupied=float(cfg["base_occupied_share"])))
    base, cs = make(jnp.uint64(seed % (1 << 64)),
                    jnp.int64(int(time.time() * 1000)))
    plant = hooks.get("plant")

    def one_pass():
        crdt = DenseCrdt(BASE, n, store=_copy(base), node_ids=names)
        if plant is not None:
            plant(crdt)
        crdt.merge_many([(cs, names)])
        return crdt

    for _ in range(2):
        crdt = one_pass()
    jax.block_until_ready(crdt.store)
    setup_meter = meter.snapshot()
    compiles0 = meter.compiles
    passes = 0
    tracer.start()
    t0 = time.monotonic()
    setup_s = t0 - t_start
    # The traced part (all of an untraced window) opens the window; the
    # passes run back to back through it and on to the window's end.
    with harness.span(harness.WINDOW_SPAN):
        while time.monotonic() < t0 + tracer.part(seconds):
            with harness.span(PASS_SPAN):
                crdt = one_pass()
            passes += 1
        jax.block_until_ready(crdt.store)
    tracer.stop()
    while time.monotonic() < t0 + seconds:
        crdt = one_pass()
        passes += 1
    jax.block_until_ready(crdt.store)
    t1 = time.monotonic()
    tracer.reduce(span_names=(PASS_SPAN,))
    in_window_compiles = meter.compiles - compiles0
    peak = harness.memory_peak(devices)

    # What the window produced, as host lanes with authors by name.
    out, out_ids = crdt.export_delta()
    got = jax.device_get({"lt": out.lt[0], "node": out.node[0],
                          "val": out.val[0], "tomb": out.tomb[0],
                          "occupied": out.valid[0]})
    del crdt, out
    rank = {name: i for i, name in enumerate(sorted(names))}
    got["rank"] = np.asarray([rank[x] for x in out_ids],
                             np.int32)[got["node"]]
    if "observe" in hooks:
        hooks["observe"](got)
    want = fanin_fold.fold(
        {"lt": base.lt, "val": base.val, "tomb": base.tomb,
         "occupied": base.occupied},
        {"lt": cs.lt, "node": cs.node, "val": cs.val, "tomb": cs.tomb,
         "valid": cs.valid},
        [rank[x] for x in names], rank[BASE])
    wrong = fanin_fold.mismatches(got, want)
    harness.log(phase="window", passes=passes, window_s=t1 - t0,
                compiles_in_window=int(in_window_compiles),
                setup=setup_meter)
    merges = passes * r * n
    record = {"passes": passes, "window_s": t1 - t0, "replicas": r,
              "keys": n}
    return harness.Outcome(
        metrics={"setup_s": setup_s, "merges_s": merges / (t1 - t0)},
        attempted=passes, failed=0,
        checks=[harness.Check("keys_wrong", float(wrong), 0.0)],
        memory_peak_bytes=peak, record=record)
