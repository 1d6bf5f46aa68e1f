"""Acknowledged writes per write-combiner flush over the window: the
writes the clients saw acknowledged over the delta of the program's
``crdt_tpu_ingest_flush_total`` counter."""


def read(run):
    flushes = run.get("flushes", 0)
    if not flushes:
        return None
    return run["writes_acked"] / flushes
