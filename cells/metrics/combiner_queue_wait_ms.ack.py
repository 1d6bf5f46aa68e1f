"""Mean time an acknowledged write waited in the write combiner's
queue before its flush tick picked it up: the delta of the program's
``crdt_tpu_serve_ack_phase_seconds{phase="queue_wait"}`` histogram
over the window (sum over count), in ms."""


def read(run):
    count = run.get("queue_wait_count", 0)
    if not count:
        return None
    return 1e3 * run["queue_wait_sum_s"] / count
