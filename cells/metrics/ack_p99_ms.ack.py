"""The 99th percentile write-ack latency of a cell below the knee,
over every write due in the window, timed from its due time. A stall
of a second or more in one run moves it by several times its spread,
so it is recorded beside the cell's median and never judged."""


def read(run):
    v = run.get("ack_p99_ms")
    return None if v is None or v != v else v
