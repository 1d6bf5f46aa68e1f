"""Mean time an acknowledged write's tick commit waited for the tier's
one replica executor, from the tick's pickup until the commit ran there
with the replica lock held: sum over count of the program's
``crdt_tpu_serve_ack_phase_seconds{phase="replica_wait"}``, in ms. Read
from the in-process registry after the window, so it covers the served
traffic of the whole run, not only the traced seconds (as
``ack_p99_ms.ack`` covers the whole window). None where the program
observes no such phase."""

from cells.phase_means import mean_ms


def read(run):
    return mean_ms("crdt_tpu_serve_ack_phase_seconds", "replica_wait")
