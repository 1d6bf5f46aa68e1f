"""Bytes each collective join moved from the devices to the host in
the traced part of the window: the delta of the program's
``crdt_tpu_collective_fetch_bytes_total`` over the delta of its
``crdt_tpu_collective_join_total``. A program that keeps no such
counters gives nothing to read."""


def read(run):
    joins, fetched = run.get("joins", 0), run.get("fetch_bytes", 0)
    if not joins or not fetched:
        return None
    return fetched / joins
