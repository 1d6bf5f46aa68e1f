"""Full benchmark suite — the five BASELINE.json configs.

Prints one JSON line per config (the driver's single-line contract is
`bench.py` at the repo root; this suite is the detailed harness).

Configs (BASELINE.json / BASELINE.md):
1. 2-replica LWW merge, 1k keys, int values — the ported
   example/crdt_example.dart shape, measured on the scalar oracle
   (the stand-in for the reference's single-thread Dart merge loop,
   crdt.dart:77-94) AND on the device path.
2. N-replica fan-in, 1M keys × {8, 64, 1024} replicas.
3. Tombstone-heavy merge (50% deletes, record.dart:17).
4. HLC tie-break stress (colliding logicalTimes; node-ordinal
   disambiguation, hlc.dart:158-161).
5. String/JSON payloads: variable-length values live in a host-side
   table; the device reduction carries table indices (SURVEY.md §7
   hard part 4). Measures the full wire path: JSON decode → merge.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root (bench.py helpers)

from bench import (_MILLIS, bench, bench_distinct, bench_e2e_1024,
                   bench_e2e_generator_only, result_dict)
from crdt_tpu import Hlc, MapCrdt, Record, TpuMapCrdt
from crdt_tpu.compile_cache import enable_compile_cache
from crdt_tpu.testing import FakeClock


def scalar_records(n_keys, node, value=None):
    h = lambda i: Hlc(_MILLIS + i % 997, i % 3, node)
    return {f"k{i}": Record(h(i), value(i) if value else i, h(i))
            for i in range(n_keys)}


def bench_example_oracle(n_keys=1000, repeats=5):
    """Config 1 on the scalar oracle — the single-thread comparison
    point (the reference publishes no numbers; this is its moral
    equivalent in-process)."""
    remote = scalar_records(n_keys, "remote")
    best = float("inf")
    for _ in range(repeats):
        crdt = MapCrdt("local", wall_clock=FakeClock(start=_MILLIS + 10_000))
        t0 = time.perf_counter()
        crdt.merge(dict(remote))
        best = min(best, time.perf_counter() - t0)
    return result_dict(
        f"oracle_2replica_{n_keys}key_int_merges_per_sec", n_keys, best,
        path="oracle-scalar-host")


def bench_example_device(n_keys=1000, repeats=5):
    """Config 1 on the drop-in TpuMapCrdt (host-shadow vectorized merge;
    reads are fetch-free, the device mirror syncs lazily)."""
    remote = scalar_records(n_keys, "remote")
    best = float("inf")
    for _ in range(repeats):
        crdt = TpuMapCrdt("local",
                          wall_clock=FakeClock(start=_MILLIS + 10_000))
        t0 = time.perf_counter()
        crdt.merge(dict(remote))
        crdt.get_record("k0")
        best = min(best, time.perf_counter() - t0)
    return result_dict(
        f"tpu_backend_2replica_{n_keys}key_int_merges_per_sec", n_keys,
        best, path="tpu_map_crdt-host-shadow")


def _bench_wire(dst_factory, metric: str, path: str, n_keys: int,
                repeats: int, sync_key=None):
    """Config 5 body: varlen-payload wire JSON decode + merge into the
    backend ``dst_factory`` builds; ``sync_key`` forces a device sync
    after the merge (device backends only)."""
    src = MapCrdt("remote", wall_clock=FakeClock(start=_MILLIS))
    src.put_all({f"key-{i}": {"s": "x" * (8 + i % 57), "i": i}
                 for i in range(n_keys)})
    wire = src.to_json()
    best = float("inf")
    for _ in range(repeats):
        dst = dst_factory()
        t0 = time.perf_counter()
        dst.merge_json(wire)
        if sync_key is not None:
            dst.get_record(sync_key)
        best = min(best, time.perf_counter() - t0)
    return result_dict(metric, n_keys, best, path=path)


def bench_payload_wire(n_keys=10_000, repeats=3):
    """Config 5: wire ingest into TpuMapCrdt — columnar decode (C batch
    HLC parse) + vectorized shadow-lane join, no Record/Hlc objects."""
    return _bench_wire(
        lambda: TpuMapCrdt("local", wall_clock=FakeClock(start=_MILLIS + 10)),
        f"wire_json_{n_keys}key_varlen_payload_merges_per_sec",
        "wire-json-columnar", n_keys, repeats, sync_key="key-0")


def bench_payload_wire_sqlite(n_keys=10_000, repeats=3):
    """Config 5 on the durable backend — what persistence costs: the
    same decode feeds per-record SQL upserts (plugin-pattern backend,
    README.md:39)."""
    from crdt_tpu import SqliteCrdt
    return _bench_wire(
        lambda: SqliteCrdt("local", wall_clock=FakeClock(start=_MILLIS + 10)),
        f"wire_json_sqlite_{n_keys}key_varlen_payload_merges_per_sec",
        "wire-json-sqlite-durable", n_keys, repeats)


def bench_payload_wire_watched(n_keys=1 << 20, repeats=1):
    """The watch contract under bulk merge (VERDICT r3 item 4): one
    active subscriber must not de-vectorize the wire ingest. Reports
    the watched/unwatched slowdown for (a) a key-filtered subscriber
    (the realistic watch shape — answered O(1) from the batch) and
    (b) a whole-store recording subscriber (buffer extended in one
    C-level pass)."""
    src = MapCrdt("remote", wall_clock=FakeClock(start=_MILLIS))
    src.put_all({f"key-{i}": {"s": "x" * (8 + i % 57), "i": i}
                 for i in range(n_keys)})
    wire = src.to_json()

    def run(subscribe):
        best = float("inf")
        for _ in range(repeats + 1):
            dst = TpuMapCrdt("local",
                             wall_clock=FakeClock(start=_MILLIS + 10))
            stream = subscribe(dst) if subscribe else None
            t0 = time.perf_counter()
            dst.merge_json(wire)
            best = min(best, time.perf_counter() - t0)
            if stream is not None:
                assert stream.events, "subscriber saw no events"
        return best

    base = run(None)
    keyed = run(lambda d: d.watch(key="key-7").record())
    recording = run(lambda d: d.watch().record())
    out = result_dict(
        f"wire_json_{n_keys}key_watched_keyed_merges_per_sec",
        n_keys, keyed, path="wire-json-columnar-watched")
    out["slowdown_vs_unwatched"] = round(keyed / base, 3)
    out2 = result_dict(
        f"wire_json_{n_keys}key_watched_recording_merges_per_sec",
        n_keys, recording, path="wire-json-columnar-watched")
    out2["slowdown_vs_unwatched"] = round(recording / base, 3)
    return out, out2


def bench_sqlite_upsert_floor(n_keys=10_000, repeats=5):
    """VERDICT r4 item 5: bare ``executemany(_UPSERT)`` of PRE-ENCODED
    rows into a fresh store — the durable-ingest floor. The full wire
    row can't beat this by construction; if (wire row) ≈ (floor) +
    (oracle-measured codec work), the residue really is sqlite's
    upsert, with a number attached."""
    from crdt_tpu import SqliteCrdt
    src = MapCrdt("remote", wall_clock=FakeClock(start=_MILLIS))
    src.put_all({f"key-{i}": {"s": "x" * (8 + i % 57), "i": i}
                 for i in range(n_keys)})
    wire = src.to_json()
    # The exact rows one real ingest upserts, pre-encoded once.
    probe = SqliteCrdt("local", wall_clock=FakeClock(start=_MILLIS + 10))
    probe.merge_json(wire)
    rows = probe._conn.execute(
        "SELECT * FROM records ORDER BY rowid").fetchall()
    probe.close()
    best = float("inf")
    for _ in range(repeats):
        dst = SqliteCrdt("local2", wall_clock=FakeClock(start=_MILLIS + 10))
        t0 = time.perf_counter()
        with dst._conn:
            dst._conn.executemany(dst._UPSERT, rows)
        best = min(best, time.perf_counter() - t0)
        dst.close()
    return result_dict(
        f"sqlite_upsert_floor_{n_keys}key_rows_per_sec", n_keys, best,
        path="sqlite-bare-executemany")


def _int_wire(n_keys):
    """Int-value wire payload over int keys — the shape every backend
    (including the dense models, whose payload lane is int64) can
    ingest, so the dense/TpuMap rows compare apples to apples."""
    import numpy as np
    from crdt_tpu import DenseCrdt
    src = DenseCrdt("remote", n_keys, wall_clock=FakeClock(start=_MILLIS))
    src.put_batch(np.arange(n_keys), np.arange(n_keys, dtype=np.int64) * 3)
    src.delete_batch(np.arange(0, n_keys, 11))
    return src.to_json()


def bench_payload_wire_dense(n_keys=1 << 20, repeats=1):
    """VERDICT r4 item 3: wire ingest into the dense flagship model —
    decode_columns → shared recv fold → O(k) sparse scatter, no
    Record/Hlc objects (models/dense_crdt.py `_merge_columns`)."""
    from crdt_tpu import DenseCrdt
    wire = _int_wire(n_keys)
    best = float("inf")
    for _ in range(repeats + 1):
        dst = DenseCrdt("local", n_keys,
                        wall_clock=FakeClock(start=_MILLIS + 10))
        t0 = time.perf_counter()
        dst.merge_json(wire)
        dst.get(0)    # device sync
        best = min(best, time.perf_counter() - t0)
    return result_dict(
        f"wire_json_dense_{n_keys}key_int_merges_per_sec", n_keys,
        best, path="wire-json-columnar-dense")


def bench_payload_wire_int_tpu_map(n_keys=1 << 20, repeats=1):
    """The same int wire payload into TpuMapCrdt — the comparator for
    the dense row (same decode, shadow-lane join instead of the dense
    scatter)."""
    wire = _int_wire(n_keys)
    best = float("inf")
    for _ in range(repeats + 1):
        dst = TpuMapCrdt("local", wall_clock=FakeClock(start=_MILLIS + 10))
        t0 = time.perf_counter()
        dst.merge_json(wire, key_decoder=int)
        best = min(best, time.perf_counter() - t0)
    return result_dict(
        f"wire_json_tpu_map_{n_keys}key_int_merges_per_sec", n_keys,
        best, path="wire-json-columnar")


def bench_gossip_interchange(n_keys=1 << 20, loops=12):
    """Round-5 interchange claim: single-row gossip merges through the
    pre-split kernel wire form (`merge_split`) vs wide-lane `merge` —
    the split path skips the per-merge int64 split and tile relayout.
    Run in ONE process back-to-back so proxy variance hits both."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from bench import make_changeset
    from crdt_tpu import DenseCrdt
    ids = [f"n{i}" for i in range(9)]
    w = DenseCrdt("w", n_keys, node_ids=ids,
                  wall_clock=FakeClock(start=_MILLIS))
    w.merge(make_changeset(128, n_keys, seed=0), ids)
    scs, sids = w.export_split_delta()
    wcs, wids = w.export_delta()
    jax.block_until_ready((scs, wcs))
    merges = int(jnp.sum(wcs.valid))
    peers = sorted(set(ids + ["rcv", "w"]))

    def run_once(fn):
        rcv = DenseCrdt("rcv", n_keys, node_ids=peers)
        t0 = _time.perf_counter()
        with rcv.pipelined():
            for _ in range(loops):
                fn(rcv)
        return (_time.perf_counter() - t0) / loops

    wide_fn = lambda r: r.merge(wcs, wids)          # noqa: E731
    split_fn = lambda r: r.merge_split(scs, sids)   # noqa: E731
    for fn in (wide_fn, split_fn):                  # warm both paths
        rcv = DenseCrdt("rcv", n_keys, node_ids=peers)
        with rcv.pipelined():
            fn(rcv)
            fn(rcv)
    # INTERLEAVED best-of: these windows are host-enqueue-bound and
    # single-shot timings swing with host jitter — alternating reps
    # hit both paths with the same weather.
    wide_s = split_s = float("inf")
    for _ in range(3):
        wide_s = min(wide_s, run_once(wide_fn))
        split_s = min(split_s, run_once(split_fn))
    out = result_dict(
        f"gossip_split_interchange_{n_keys}key_merges_per_sec", merges,
        split_s, path="merge_split-pre-tiled")
    out["wide_merge_per_sec"] = round(merges / wide_s, 1)
    out["speedup_vs_wide"] = round(wide_s / split_s, 2)
    return out


def bench_dense_to_json(n_slots=1 << 20, repeats=3):
    """1M-slot full wire export on the dense model (the interop contract
    crdt.dart:124-135 at dense scale): lane-direct C-codec formatting."""
    import numpy as np
    from crdt_tpu import DenseCrdt
    c = DenseCrdt("na", n_slots, wall_clock=FakeClock(start=_MILLIS))
    c.put_batch(np.arange(n_slots), np.arange(n_slots, dtype=np.int64))
    c.delete_batch(np.arange(0, n_slots, 7))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = c.to_json()
        best = min(best, time.perf_counter() - t0)
    assert out.startswith('{"0":')
    return result_dict(
        f"dense_to_json_{n_slots // 1000}k_records_per_sec", n_slots,
        best, path="lane-direct-c-codec")


def bench_tpu_map_to_json(n_keys=1 << 20, repeats=3):
    """1M-key full wire export on the drop-in general-key backend:
    lane-direct shadow-lane formatting (crdt.dart:124-135 interop at
    the scale the round-2 review called effectively unusable)."""
    c = TpuMapCrdt("na", wall_clock=FakeClock(start=_MILLIS))
    c.put_all({f"k{i}": i for i in range(n_keys)})
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = c.to_json()
        best = min(best, time.perf_counter() - t0)
    assert out.startswith('{"k0":')
    return result_dict(
        f"tpu_map_to_json_{n_keys // 1000}k_records_per_sec", n_keys,
        best, path="lane-direct-c-codec")


def bench_payload_wire_oracle(n_keys=10_000, repeats=5):
    """Config 5 on the host-only oracle — isolates the wire codec
    (native batch HLC parse + merge loop) from device round-trip
    latency, which dominates and jitters the TpuMapCrdt row."""
    return _bench_wire(
        lambda: MapCrdt("local", wall_clock=FakeClock(start=_MILLIS + 10)),
        f"wire_json_oracle_{n_keys}key_varlen_payload_merges_per_sec",
        "wire-json-oracle-host", n_keys, repeats)


def main():
    enable_compile_cache()
    # Each config prints as it completes (a late failure must not lose
    # earlier results); forced-executor rows tag the metric name so the
    # xla/pallas pair never collides for consumers keyed on metric.
    def emit(make_result, tag=None):
        """Run one config, print its row(s), return the first row (or
        None on failure) so the e2e decomposition can reuse it."""
        try:
            r = make_result()
        except Exception as e:
            print(f"suite config failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            return None
        rows = r if isinstance(r, tuple) else (r,)
        for row in rows:
            if tag:
                row["metric"] += f"_{tag}"
            print(json.dumps(row), flush=True)
        return rows[0]

    emit(bench_example_oracle)
    emit(bench_example_device)
    # Small fan-ins chain more repeats so the one-off dispatch round
    # trip doesn't dominate (see bench.py protocol note).
    emit(lambda: bench(1 << 20, 8, 8, repeats=256))
    emit(lambda: bench(1 << 20, 64, 8, repeats=64))
    # Write-stream headline config on BOTH executors, side by side.
    emit(lambda: bench(1 << 20, 1024, 8, path="xla", repeats=64), tag="xla")
    emit(lambda: bench(1 << 20, 1024, 8, path="pallas", repeats=64),
         tag="pallas")
    # GENUINELY DISTINCT replica rows resident in HBM (the
    # BASELINE.md:26 north-star workload; every counted merge pays its
    # full HBM read — see bench.bench_distinct).
    emit(lambda: bench_distinct(1 << 20, 128, loops=48))
    # value-ref mode: int32 payloads/table indices (15 B vs 19 B per
    # merge) — the recommended shape for variable-length values.
    emit(lambda: bench_distinct(1 << 20, 128, loops=48,
                                value_width=32))
    # THE north-star workload end to end: 1M × 1024 DISTINCT replica
    # rows as 8 freshly device-generated batches (generation cost
    # included, disclosed in the protocol fields) — once through the
    # model API (pipelined window), once through the raw kernel; the
    # pair isolates model-API overhead at the headline scale.
    # Three-row protocol (VERDICT r4 item 6): model e2e, raw-kernel
    # e2e, generator-only — the last isolates input manufacture so the
    # e2e rows decompose; a derived merge-only row reports the
    # subtraction.
    e2e_rows = {
        "model": emit(lambda: bench_e2e_1024(1 << 20, through_model=True)),
        "kernel": emit(lambda: bench_e2e_1024(1 << 20,
                                              through_model=False)),
        "gen": emit(lambda: bench_e2e_generator_only(1 << 20)),
    }
    if e2e_rows["gen"] is not None:
        for which in ("model", "kernel"):
            if e2e_rows[which] is None:
                continue
            v_e2e = e2e_rows[which]["value"]
            v_gen = e2e_rows["gen"]["value"]
            if v_gen <= v_e2e:
                continue   # generation slower than e2e: noise, skip
            derived = result_dict(
                f"record_merges_per_sec_1048k_keys_x1024_distinct_"
                f"replicas_e2e_{which}_minus_generation",
                1, 1 / v_e2e - 1 / v_gen,
                # The generator row spends extra time in its consumer
                # reduces (which the e2e rows don't run), so the
                # subtraction slightly UNDERSTATES framework time —
                # treat as an upper bound on merge-side throughput.
                path="derived: 1/(1/e2e - 1/generator_only), upper bound")
            print(json.dumps(derived), flush=True)
    emit(lambda: bench(1 << 20, 1024, 8, config="tombstone", repeats=64))
    emit(lambda: bench(1 << 20, 1024, 8, config="tiebreak", repeats=64))
    emit(bench_payload_wire)
    emit(bench_payload_wire_oracle)
    emit(bench_payload_wire_sqlite)
    emit(bench_sqlite_upsert_floor)
    # 1M-key wire ingest: the drop-in backend vs the oracle at the
    # scale DenseCrdt stores actually run at.
    emit(lambda: bench_payload_wire(n_keys=1 << 20, repeats=1))
    emit(lambda: bench_payload_wire_oracle(n_keys=1 << 20, repeats=1))
    emit(bench_payload_wire_watched)
    # 1M-key INT wire ingest: dense flagship vs the drop-in backend on
    # the identical payload (VERDICT r4 item 3's "≥ TpuMapCrdt" bar).
    emit(bench_payload_wire_dense)
    emit(bench_payload_wire_int_tpu_map)
    emit(bench_gossip_interchange)
    emit(bench_dense_to_json)
    emit(bench_tpu_map_to_json)


if __name__ == "__main__":
    main()
