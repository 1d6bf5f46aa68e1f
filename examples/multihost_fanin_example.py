"""TRUE multi-process replica fan-in: the sharded lattice join running
across two OS processes with cross-process collectives.

Everything else in this repo demonstrates multi-chip sharding inside
one process (the virtual 8-device mesh). This example is the missing
hop: two separate processes — the multi-HOST shape, each owning half
the mesh's devices — running `ShardedDenseCrdt.merge_many` as ONE
SPMD program whose replica-axis reduction crosses the process
boundary (gloo over TCP here; on real TPU pods the identical code
rides ICI/DCN — nothing in `crdt_tpu.parallel` is host-count-aware,
the mesh just spans `jax.devices()` after `jax.distributed`
initializes).

Each process validates its ADDRESSABLE key shards against a
single-process reference replica merged from the same changesets —
lane-exact — and the replicated canonical clock must agree.

Run: ``python examples/multihost_fanin_example.py`` (it spawns and
coordinates both processes itself).
"""

import os
import socket
import subprocess
import sys

BASE = 1_700_000_000_000
N = 4096          # key slots, sharded 2-way across the processes
ROWS = 8          # replica rows, fanned in across the replica axis


def worker(process_id: int) -> None:
    # 2 local devices × 2 procs, set before jax initializes a backend.
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{os.environ['MH_EXAMPLE_PORT']}",
        num_processes=2, process_id=process_id)

    import numpy as np

    from crdt_tpu import DenseCrdt, ShardedDenseCrdt
    from crdt_tpu.parallel import make_fanin_mesh
    from crdt_tpu.testing import FakeClock

    assert jax.device_count() == 4 and jax.local_device_count() == 2
    # (replica=2, key=2): the replica axis CROSSES the process
    # boundary, so the fan-in's lexicographic-max reduction is a real
    # cross-process collective.
    mesh = make_fanin_mesh(2, 2)

    def batches():
        out = []
        for i in range(3):     # identical on both processes (seeded)
            p = DenseCrdt(f"peer{i}", N,
                          wall_clock=FakeClock(start=BASE + i * 7))
            rng = np.random.default_rng(i)
            slots = rng.choice(N, ROWS * 64, replace=False)
            p.put_batch(slots, rng.integers(0, 1 << 30, slots.size))
            p.delete_batch(slots[:5])
            out.append(p.export_delta())
        return out

    sharded = ShardedDenseCrdt("local", N, mesh,
                               wall_clock=FakeClock(start=BASE + 500))
    sharded.merge_many(batches())

    # Reference: the same merges on a plain single-process replica.
    ref = DenseCrdt("local", N, executor="xla",
                    wall_clock=FakeClock(start=BASE + 500))
    ref.merge_many(batches())

    assert sharded.canonical_time == ref.canonical_time
    checked = 0
    for lane_name in ("lt", "node", "val", "mod_lt", "mod_node",
                      "occupied", "tomb"):
        lane = getattr(sharded.store, lane_name)
        ref_lane = np.asarray(getattr(ref.store, lane_name))
        for shard in lane.addressable_shards:
            (sl,) = shard.index
            np.testing.assert_array_equal(
                np.asarray(shard.data), ref_lane[sl],
                err_msg=f"{lane_name} shard {shard.index}")
            checked += 1
    print(f"[process {process_id}] {checked} addressable shards "
          "lane-exact vs single-process reference; canonical clocks "
          "agree ✓", flush=True)


def main() -> None:
    if "MH_EXAMPLE_RANK" in os.environ:
        worker(int(os.environ["MH_EXAMPLE_RANK"]))
        return
    # Fresh ephemeral coordinator port per run: concurrent suites on
    # one host must not collide. (The tiny bind/close race window is
    # acceptable for an example.)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {**os.environ, "MH_EXAMPLE_PORT": str(port)}
    p0 = subprocess.Popen([sys.executable, __file__],
                          env={**env, "MH_EXAMPLE_RANK": "0"})
    p1 = subprocess.Popen([sys.executable, __file__],
                          env={**env, "MH_EXAMPLE_RANK": "1"})
    try:
        # One shared deadline (not 300s each), and ALWAYS reap both:
        # an orphaned worker holding inherited pipes would hang the
        # example-CI harness past its own timeout.
        import time
        deadline = time.monotonic() + 240
        rc0 = p0.wait(timeout=max(1, deadline - time.monotonic()))
        rc1 = p1.wait(timeout=max(1, deadline - time.monotonic()))
    except Exception:
        p0.kill()
        p1.kill()
        raise
    if rc0 or rc1:
        p0.kill()
        p1.kill()
        raise SystemExit(f"worker exit codes: {rc0}, {rc1}")
    print("two processes, one SPMD fan-in, converged ✓")


if __name__ == "__main__":
    main()
