"""Share of the HBM roofline reached by the fan-in passes: the least
time the pass's bytes (`cells.work.fanin_bytes`) take at the device's
peak bandwidth, over the device busy time inside the harness's
``fanin.pass`` spans, in the traced window. The merge moves far more
bytes than it computes, so bandwidth bounds it."""

from cells import work


def read(run):
    trace = run.get("trace")
    if trace is None:
        return None
    peak = work.peaks(run["device_kind"])["hbm_bytes_s"]
    least = busy = 0.0
    for dev in trace["devices"]:
        passes = dev["span_counts"].get("fanin.pass", 0)
        least += passes * work.fanin_bytes(run["replicas"],
                                           run["keys"]) / peak
        busy += dev["in_spans"].get("fanin.pass", 0) * 1e-9
    if busy <= 0 or least <= 0:
        return None
    return 100.0 * least / busy
