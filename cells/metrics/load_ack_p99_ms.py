"""The 99th percentile write-ack latency of an above-capacity cell,
over every write due in the window, timed from its due time. Above the
knee it swings with the smallest change in capacity, so it is recorded
beside the cell's rate and never judged."""


def read(run):
    v = run.get("ack_p99_ms")
    return None if v is None or v != v else v
