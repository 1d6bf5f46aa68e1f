"""Device-to-host transfers per served read, in the traced window: the
count of the runtime's transfer events over the reads the clients saw
answered in the window. A read that fetches its slot in one transfer
reads 1; a change that answers reads without a fetch reads less."""

# The host-side event the TPU runtime records for each device-to-host
# transfer, as read from a v5e trace by hand
# (``python3 cells/trace_reduce.py <trace>``): one per array fetched.
D2H = "tpu::System::TransferFromDevice"


def read(run):
    trace = run.get("trace")
    reads = run.get("reads_in_window", 0)
    if trace is None or not reads:
        return None
    return trace["host_counts"].get(D2H, 0) / reads
