"""The 99th percentile read latency of a cell below the knee, over
every read due in the window, timed from its due time. A stall of a
second or more in one run moves it by several times its spread, so it
is recorded beside the cell's median and never judged."""


def read(run):
    v = run.get("read_p99_ms")
    return None if v is None or v != v else v
