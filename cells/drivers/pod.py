"""Window driver for the pod: active-active replicas of the YCSB store,
one whole replica per chip, converged by `CollectiveGroup.join` after
every round of writes.

Set-up builds each member's loaded store on its own chip from the seed
(every slot a live record stamped a second in the past, valued by
`lww_register.loaded_value`, the same on every member; the stores are
never gathered onto one chip, which could not hold them), forms the
group over the chips and runs two rounds to compile and warm every
program. A round is, for each member in turn, one `put_batch` (the
reference's putAll) of the distinct keys of ``draws`` scrambled-Zipfian
draws, each valued with a fresh unique int64, then one ``join()``;
rounds run back to back through the window. Every batch is logged with
its member, its stamp (the member's clock after the `put_batch`), its
slots and values. After the window the members are compared with each
other over every slot, the written slots with the plain fold
(`cells.reference.pod_fold`) and a sample of untouched slots with
their loaded values.
"""

from __future__ import annotations

import os
import time
from functools import partial

import numpy as np

from cells import harness, ycsb
from cells.drivers.serve import _loaded_store
from cells.reference import lww_register, pod_fold

ROUND_SPAN = "pod.round"
SAMPLE_SLOTS = 4096
_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def before_jax(cell, seed: int, seconds: float) -> dict:
    """A rehearsal runs the members on virtual CPU devices, one each."""
    flags = os.environ.get("XLA_FLAGS", "")
    if cell.rehearse and _DEVICE_COUNT_FLAG not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} {_DEVICE_COUNT_FLAG}={cell.chips}").strip()
    return {}


def after(pre: dict) -> None:
    pass


def _join_counters() -> dict:
    """The program's join counters, summed over their labels (zero
    where the program keeps none)."""
    from crdt_tpu.obs.registry import default_registry
    reg = default_registry()
    return {key: sum(s["value"] for s in reg.counter(name).samples())
            for key, name in (
                ("joins", "crdt_tpu_collective_join_total"),
                ("fetch_bytes", "crdt_tpu_collective_fetch_bytes_total"))}


class _Writer:
    """Each member's batches, drawn from the seed: the distinct keys of
    ``draws`` scrambled-Zipfian draws, each valued with an int64 no
    other write of the run carries."""

    def __init__(self, seed: int, draws: int, n_slots: int):
        self.seed, self.draws, self.n_slots = seed, draws, n_slots
        self.key = lww_register.splitmix64(np.uint64(seed % (1 << 64))
                                           ^ np.uint64(0x90D5))
        self.written = 0

    def batch(self, rnd: int, member: int):
        rng = np.random.default_rng([self.seed, 0x90D, rnd, member])
        slots = np.unique(ycsb.scrambled_zipfian(rng, self.draws,
                                                 self.n_slots))
        # SplitMix64's output function is a bijection of uint64, so
        # distinct counters give distinct values.
        ctr = np.arange(self.written, self.written + len(slots),
                        dtype=np.uint64)
        self.written += len(slots)
        return slots, lww_register.splitmix64(ctr ^ self.key).view(np.int64)


def _differ(a, b):
    import jax.numpy as jnp
    return sum(jnp.sum(x != y, dtype=jnp.int64) for x, y in zip(a, b))


def _take(lanes, idx):
    return tuple(x[idx] for x in lanes)


def _record_lanes(m):
    st = m._store
    return (st.occupied, st.lt, st.node, st.val, st.tomb)


def _members_differ(members) -> int:
    """Slots where any member's ``(occupied, lt, node, val, tomb)``
    differs from member 0's, over every slot: member 0's lanes are
    copied to each other member's chip and compared there."""
    import jax
    ref = _record_lanes(members[0])
    count = 0
    for m in members[1:]:
        mine = _record_lanes(m)
        there = jax.device_put(ref, next(iter(mine[0].devices())))
        count += int(jax.jit(_differ)(there, mine))
        del there
    return count


def _gather(m, slots: np.ndarray) -> dict:
    """The member's record lanes at ``slots``, in one padded gather on
    the member's chip."""
    import jax
    pad = 1 << max(len(slots) - 1, 1).bit_length()
    idx = np.zeros(pad, np.int64)
    idx[:len(slots)] = slots
    lanes = _record_lanes(m)
    dev = next(iter(lanes[0].devices()))
    got = jax.device_get(jax.jit(_take)(lanes, jax.device_put(idx, dev)))
    names = ("occupied", "lt", "node", "val", "tomb")
    return {k: v[:len(slots)] for k, v in zip(names, got)}


def run(cell, seed: int, seconds: float, pre: dict, meter, tracer,
        t_start: float, devices, hooks) -> harness.Outcome:
    import jax
    from jax.sharding import SingleDeviceSharding
    from crdt_tpu import CollectiveGroup, DenseCrdt
    from crdt_tpu.parallel.collective import make_collective_mesh

    cfg, mix = cell.sizes("config"), cell.sizes("traffic")
    n, g = int(cfg["recordcount"]), int(cfg["members"])
    names = [f"m{i}" for i in range(g)]
    rank = {name: i for i, name in enumerate(sorted(names))}
    # Loaded a second in the past, so every write of the run is
    # stamped later than the load.
    lt0 = (int(time.time() * 1000) - 1000) << 16
    key = jax.numpy.uint64(seed % (1 << 64))
    members = []
    for name, dev in zip(names, devices[:g]):
        make = jax.jit(partial(_loaded_store, n),
                       out_shardings=SingleDeviceSharding(dev))
        members.append(DenseCrdt(name, n, store=make(
            key, jax.numpy.int64(lt0)), node_ids=names))
    group = CollectiveGroup(members, mesh=make_collective_mesh(g, devices))
    if "plant" in hooks:
        hooks["plant"](group)
    writer = _Writer(seed, int(mix["draws"]), n)
    log = []
    failed = []

    def writes(rnd: int) -> None:
        for i, m in enumerate(members):
            slots, vals = writer.batch(rnd, i)
            m.put_batch(slots, vals)
            log.append((rank[m.node_id], m.canonical_time.logical_time,
                        slots, vals))

    def one_round(rnd: int) -> bool:
        writes(rnd)
        try:
            group.join()
        except Exception as e:           # counted, and ends the window
            failed.append(repr(e)[:300])
            return False
        return True

    # The warm rounds: a join that fails here fails the run.
    rnd = 0
    for _ in range(2):
        writes(rnd)
        group.join()
        rnd += 1
    jax.block_until_ready([m._held for m in members])
    setup_meter = meter.snapshot()
    compiles0 = meter.compiles
    reg0 = _join_counters()
    tracer.start()
    t0 = time.monotonic()
    setup_s = t0 - t_start
    rounds = 0
    going = True
    # The traced part (all of an untraced window) opens the window; the
    # rounds run back to back through it and on to the window's end.
    with harness.span(harness.WINDOW_SPAN):
        while going and time.monotonic() < t0 + tracer.part(seconds):
            with harness.span(ROUND_SPAN):
                going = one_round(rnd)
            rnd += 1
            rounds += 1
        jax.block_until_ready([m._held for m in members])
    reg1 = _join_counters()
    tracer.stop()
    while going and time.monotonic() < t0 + seconds:
        going = one_round(rnd)
        rnd += 1
        rounds += 1
    jax.block_until_ready([m._held for m in members])
    t1 = time.monotonic()
    in_window_compiles = meter.compiles - compiles0
    peak = harness.memory_peak(devices)
    tracer.reduce(span_names=(ROUND_SPAN,))

    differ = _members_differ(members)
    want = pod_fold.fold(log, lt0, rank[names[0]], seed)
    got = _gather(members[0], want["slots"])
    rank_of = np.asarray([rank[x] for x in members[0]._table.ids()],
                         np.int64)
    got["rank"] = rank_of[got["node"]]
    if "observe" in hooks:
        hooks["observe"](got)
    final_wrong = pod_fold.mismatches(got, want)
    rng = np.random.default_rng([seed, 0x5A4])
    sample = np.setdiff1d(rng.integers(0, n, SAMPLE_SLOTS), want["slots"])
    loaded = lww_register.loaded_value(sample, seed)
    load_bad = np.zeros(len(sample), bool)
    for m in members:
        s = _gather(m, sample)
        load_bad |= ~s["occupied"] | s["tomb"] | (s["val"] != loaded)
    harness.log(phase="window", rounds=rounds, window_s=t1 - t0,
                batches=len(log), written_slots=len(want["slots"]),
                compiles_in_window=int(in_window_compiles),
                join_errors=failed[:3], setup=setup_meter)
    record = {"rounds": rounds, "window_s": t1 - t0, "members": g,
              "n_slots": n, "joins": reg1["joins"] - reg0["joins"],
              "fetch_bytes": reg1["fetch_bytes"] - reg0["fetch_bytes"]}
    return harness.Outcome(
        metrics={"setup_s": setup_s, "merges_s": rounds * g * n / (t1 - t0)},
        attempted=rounds, failed=len(failed),
        checks=[harness.Check("members_differ", float(differ), 0.0),
                harness.Check("final_wrong", float(final_wrong), 0.0),
                harness.Check("load_wrong", float(load_bad.sum()), 0.0),
                harness.Check("joins_failed", float(len(failed)), 0.0)],
        memory_peak_bytes=peak, record=record)
