"""Mean time of a served read frame's own fetch, its slices of the
store's lanes and their transfer to the host: sum over count of the
program's ``crdt_tpu_serve_read_phase_seconds{phase="fetch"}``, in ms.
Read from the in-process registry after the window, so it covers the
served traffic of the whole run, not only the traced seconds (as
``ack_p99_ms.ack`` covers the whole window). None where the program
observes no such phase."""

from cells.phase_means import mean_ms


def read(run):
    return mean_ms("crdt_tpu_serve_read_phase_seconds", "fetch")
