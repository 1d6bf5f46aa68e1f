"""Pipelined replication: merging a stream of peer changesets with
zero per-merge host synchronization.

The scenario: a dense replica ingesting deltas from many peers in a
tight loop — the steady-state of an anti-entropy mesh. Unpipelined,
every `merge` fetches its guard flags and canonical clock from the
device (a full host↔device round trip per call — the dominant cost of
a small merge). Inside a `DenseCrdt.pipelined()` window
the canonical clock threads as a device scalar, guard flags
accumulate, and ONE readback at the window's end settles everything.

Store lanes and the canonical clock are bit-identical to the same
merges issued unpipelined — this example proves it by running both.
"""

from crdt_tpu import DenseCrdt, PipelinedGuardError
from crdt_tpu.testing import FakeClock, assert_dense_stores_equal

BASE = 1_700_000_000_000
N = 4096


def make_peers(k: int):
    peers = []
    for i in range(k):
        p = DenseCrdt(f"peer{i}", N,
                      wall_clock=FakeClock(start=BASE + i * 13))
        p.put_batch(list(range(i, N, i + 3)),
                    [i * 1000 + s for s in range(i, N, i + 3)])
        p.delete_batch([i, i + 11])
        peers.append(p)
    return peers


def main() -> None:
    batches = [p.export_delta() for p in make_peers(6)]

    pipelined = DenseCrdt("local", N, wall_clock=FakeClock(start=BASE))
    with pipelined.pipelined():          # one readback, at exit
        for cs, ids in batches:
            pipelined.merge(cs, ids)

    plain = DenseCrdt("local", N, wall_clock=FakeClock(start=BASE))
    for cs, ids in batches:              # one readback PER merge
        plain.merge(cs, ids)

    assert_dense_stores_equal(pipelined.store, plain.store)
    assert pipelined.canonical_time == plain.canonical_time
    print(f"pipelined == unpipelined: {len(pipelined.record_map())} "
          "records, identical lanes and clock ✓")

    # The trade: a guard violation (here, a peer claiming OUR node id)
    # reports at the window's end, coarsely — and the merges have
    # already landed (the lattice join is monotone either way).
    rogue = DenseCrdt("local", N,        # duplicate node id!
                      wall_clock=FakeClock(start=BASE + 10_000))
    rogue.put_batch([0], [1])
    cs, ids = rogue.export_delta()
    try:
        with pipelined.pipelined():
            pipelined.merge(cs, ids)
    except PipelinedGuardError as e:
        print(f"deferred guard report: {e}")


if __name__ == "__main__":
    main()
