"""Window driver for the served store: socket clients against one
`ServeTier` over one `DenseCrdt`, through the binary op lane.

Set-up loads every slot's record on the device from the seed (or, for
a mix with ``"preload": false``, starts from the empty store), starts
the tier with its defaults, warms the ingest commit for every batch
size a tick can reach (writes that leave every value as it was) and
the point read, and connects the load generator's sessions. The window
is the mix's open-loop schedule; after it, each written slot is read
back from the store and the window is checked against the LWW
reference (`cells.reference.lww_register`).
"""

from __future__ import annotations

import multiprocessing
import resource
import time
from functools import partial

import numpy as np

from cells import harness, loadgen, ycsb
from cells.reference import lww_register

NODE = "kv"
SAMPLE_SLOTS = 4096


def before_jax(cell, seed: int, seconds: float) -> dict:
    """Draw the schedule and start the client processes: all before
    this process touches JAX, so no child ever shares the chip."""
    cfg, mix = cell.sizes("config"), cell.sizes("traffic")
    sched = ycsb.schedule(mix, int(cfg["recordcount"]), seed, seconds)
    ctx = multiprocessing.get_context("spawn")
    kids = []
    for _ in range(int(mix["client_procs"])):
        mine, theirs = ctx.Pipe()
        proc = ctx.Process(target=loadgen.child_main, args=(theirs,),
                           daemon=True)
        proc.start()
        theirs.close()
        kids.append((proc, mine))
    return {"sched": sched, "kids": kids}


def after(pre: dict) -> None:
    for proc, conn in pre["kids"]:
        try:
            if proc.is_alive():
                conn.send(None)
        except (BrokenPipeError, OSError):
            pass
        proc.join(timeout=10)
        if proc.is_alive():
            proc.terminate()
            proc.join(timeout=10)
        conn.close()


def _loaded_store(n: int, seed, lt0):
    """Every slot holds a live record written by this node at ``lt0``
    (a packed HLC), valued by `lww_register.loaded_value`."""
    import jax
    import jax.numpy as jnp
    from crdt_tpu.ops.dense import DenseStore
    key = harness.splitmix64(seed)
    val = jax.lax.bitcast_convert_type(
        harness.splitmix64(jnp.arange(n, dtype=jnp.uint64) ^ key),
        jnp.int64)
    lt = jnp.full((n,), lt0, jnp.int64)
    zeros = jnp.zeros((n,), jnp.int32)
    return DenseStore(lt=lt, node=zeros, val=val, mod_lt=lt,
                      mod_node=zeros, occupied=jnp.ones((n,), bool),
                      tomb=jnp.zeros((n,), bool))


def _gather(val, occupied, tomb, idx):
    return val[idx], occupied[idx], tomb[idx]


def _read_store(crdt, slots: np.ndarray) -> dict:
    """``slot -> value`` (None where no live record) straight from the
    store's lanes after the window, in one padded gather."""
    import jax
    import jax.numpy as jnp
    if not len(slots):
        return {}
    pad = 1 << max(len(slots) - 1, 1).bit_length()
    idx = np.zeros(pad, np.int64)
    idx[:len(slots)] = slots
    st = crdt.store
    val, occ, tomb = jax.device_get(jax.jit(_gather)(
        st.val, st.occupied, st.tomb, jnp.asarray(idx)))
    live = occ & ~tomb
    return {int(s): (int(val[i]) if live[i] else None)
            for i, s in enumerate(slots.tolist())}


def _warm(tier, crdt, n: int, seed: int, preload: bool,
          max_rows: int) -> None:
    """Commit one batch of each power-of-two size up to ``max_rows``,
    each row in its own tile, writing back what the slot already
    holds, and serve a few point reads."""
    k = 1
    while k <= max_rows:
        slots = np.arange(k, dtype=np.int64) * (n // k)
        with tier.lock:
            if preload:
                crdt.put_batch(slots, lww_register.loaded_value(slots, seed))
            else:
                crdt.delete_batch(slots)
            crdt.drain_ingest()
        k *= 2
    with tier.lock:
        for s in (0, n // 3, n - 1):
            crdt.get(s)


def _registry_totals() -> dict:
    from crdt_tpu.obs.registry import default_registry
    reg = default_registry()
    flushes = sum(s["value"] for s in reg.counter(
        "crdt_tpu_ingest_flush_total").samples())
    qw = [s for s in reg.histogram(
        "crdt_tpu_serve_ack_phase_seconds").samples()
        if s["labels"].get("phase") == "queue_wait"]
    return {"flushes": flushes,
            "queue_wait_sum_s": sum(s["sum"] for s in qw),
            "queue_wait_count": sum(s["count"] for s in qw)}


def _pct_ms(lat_s: np.ndarray, q: float) -> float:
    """The ``q`` quantile (nearest rank) of all the latencies, in ms."""
    if not len(lat_s):
        return float("nan")
    xs = np.sort(lat_s)
    return float(xs[max(0, int(np.ceil(q * len(xs))) - 1)] * 1e3)


def run(cell, seed: int, seconds: float, pre: dict, meter, tracer,
        t_start: float, devices, hooks) -> harness.Outcome:
    import jax
    from crdt_tpu import DenseCrdt, ServeTier

    cfg, mix = cell.sizes("config"), cell.sizes("traffic")
    n = int(cfg["recordcount"])
    preload = bool(mix["preload"])
    sched = pre["sched"]
    _, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))

    if preload:
        # Stamped a second in the past, so every write of the window
        # is stamped later than the load.
        lt0 = (int(time.time() * 1000) - 1000) << 16
        store = jax.jit(partial(_loaded_store, n))(
            jax.numpy.uint64(seed % (1 << 64)), jax.numpy.int64(lt0))
        crdt = DenseCrdt(NODE, n, store=store)
        del store
    else:
        crdt = DenseCrdt(NODE, n)
    if "plant" in hooks:
        hooks["plant"](crdt)
    tier = ServeTier(crdt).start()
    stopped = False
    try:
        _warm(tier, crdt, n, seed, preload, int(mix["sessions"]))
        kids = pre["kids"]
        for p, (_, conn) in enumerate(kids):
            mine = np.flatnonzero(sched["session"] % len(kids) == p)
            conn.send({"host": tier.host, "port": tier.port,
                       "sessions": list(range(p, int(mix["sessions"]),
                                              len(kids))),
                       "ops": {k: v[mine] for k, v in sched.items()}})
        for _, conn in kids:
            if not conn.poll(300) or conn.recv() != "ready":
                raise RuntimeError("a client process did not connect")
        setup_meter = meter.snapshot()
        part = tracer.part(seconds)
        reg0 = _registry_totals()
        tracer.start()
        t0 = time.monotonic() + 0.5
        for _, conn in kids:
            conn.send(t0)
        setup_s = t0 - t_start
        compiles0 = meter.compiles
        harness.sleep_until(t0)
        # The per-layer counts cover the traced part (all of an
        # untraced window), the same stretch as the trace.
        with harness.span(harness.WINDOW_SPAN):
            harness.sleep_until(t0 + part)
        reg1 = _registry_totals()
        tracer.stop()
        k = len(sched["due"])
        res = {"sent": np.full(k, np.nan), "acked": np.full(k, np.nan),
               "status": np.full(k, 255, np.uint8),
               "got": np.zeros(k, np.int64)}
        errors = []
        for p, (_, conn) in enumerate(kids):
            if not conn.poll(seconds + 120):
                raise RuntimeError("a client process never reported")
            out = conn.recv()
            mine = np.flatnonzero(sched["session"] % len(kids) == p)
            for key in res:
                res[key][mine] = out[key]
            errors += out["errors"]
        in_window_compiles = meter.compiles - compiles0
        peak = harness.memory_peak(devices)
        tier.stop()
        stopped = True
    finally:
        if not stopped:
            tier.kill()

    writes = sched["op"] != ycsb.OP_GET
    written = np.unique(sched["slot"][writes])
    rng = np.random.default_rng([seed, 0x5A3])
    sample = np.setdiff1d(rng.integers(0, n, SAMPLE_SLOTS), written)
    seen = _read_store(crdt, np.concatenate([written, sample]))
    del crdt
    tracer.reduce(span_names=("ingest_flush",))
    if "observe" in hooks:
        hooks["observe"](res, seen)
    counts = lww_register.check_window(
        sched, res, {int(s): seen[int(s)] for s in written},
        {int(s): seen[int(s)] for s in sample}, seed, preload)

    due = t0 + sched["due"]
    lat = res["acked"] - due
    late = res["sent"] - due
    ok = np.isfinite(res["acked"]) & np.isin(
        res["status"], (lww_register.OK, lww_register.OK_NULL))
    reads = ~writes
    in_window = ok & (res["acked"] <= t0 + seconds)
    in_part = ok & (res["acked"] <= t0 + part)
    harness.log(phase="window", ops=int(k), acked=int(ok.sum()),
                compiles_in_window=int(in_window_compiles),
                generator_late_p99_ms=_pct_ms(late[np.isfinite(late)], 0.99),
                generator_late_max_ms=float(np.nanmax(late) * 1e3),
                client_errors=errors[:5], setup=setup_meter,
                latency_ms={f"{kind}_p{round(q * 100)}": _pct_ms(lat[sel], q)
                            for kind, sel in (("ack", ok & writes),
                                              ("read", ok & reads))
                            for q in (0.5, 0.9, 0.99)})
    record = {
        "writes_acked": int((in_part & writes).sum()),
        "reads_in_window": int((in_part & reads).sum()),
        "flushes": reg1["flushes"] - reg0["flushes"],
        "queue_wait_sum_s": reg1["queue_wait_sum_s"]
        - reg0["queue_wait_sum_s"],
        "queue_wait_count": reg1["queue_wait_count"]
        - reg0["queue_wait_count"],
        "ack_p99_ms": _pct_ms(lat[ok & writes], 0.99),
        "read_p99_ms": _pct_ms(lat[ok & reads], 0.99),
    }
    metrics = {"setup_s": setup_s,
               "ack_p50_ms": _pct_ms(lat[ok & writes], 0.5),
               "read_p50_ms": _pct_ms(lat[ok & reads], 0.5),
               "ops_s": float(in_window.sum()) / seconds}
    checks = [harness.Check(name, float(v), 0.0)
              for name, v in counts.items()]
    return harness.Outcome(metrics=metrics, attempted=int(k),
                           failed=int(k - ok.sum()), checks=checks,
                           memory_peak_bytes=peak, record=record)
