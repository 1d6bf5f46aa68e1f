"""Share of device busy time spent in collective operations in the
traced window, in percent, averaged over the devices: device
nanoseconds of the ops whose recorded names hold all-reduce,
all-gather, reduce-scatter or collective-permute, over the union of
all device op intervals. Op totals, not intervals: a collective that
overlaps compute counts in full, so this is not the exposed share."""

KINDS = ("all-reduce", "all-gather", "reduce-scatter",
         "collective-permute")


def is_collective(name: str) -> bool:
    name = name.replace("_", "-")
    return any(kind in name for kind in KINDS)


def read(run):
    trace = run.get("trace")
    if trace is None:
        return None
    shares = []
    for dev in trace["devices"]:
        if not dev["busy_ns"]:
            continue
        ns = sum(t for name, (t, _) in dev["ops"].items()
                 if is_collective(name))
        shares.append(100.0 * ns / dev["busy_ns"])
    if not shares:
        return None
    return sum(shares) / len(shares)
