"""Device busy time per write-combiner flush in the traced window, in
microseconds: the union of device operation intervals over the count
of the program's ``ingest_flush`` host spans. It does not ask which
kernel does the scatter."""


def read(run):
    trace = run.get("trace")
    if trace is None:
        return None
    devs = trace["devices"]
    flushes = sum(d["span_counts"].get("ingest_flush", 0) for d in devs)
    if not flushes:
        return None
    return 1e-3 * sum(d["busy_ns"] for d in devs) / flushes
