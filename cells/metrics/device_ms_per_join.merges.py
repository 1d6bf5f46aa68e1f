"""Device busy time per round (one collective join and the members'
writes before it), in milliseconds: the union of device operation
intervals inside the harness's ``pod.round`` spans over their count,
averaged over the devices. It does not ask which kernel runs."""


def read(run):
    trace = run.get("trace")
    if trace is None:
        return None
    per = [d["in_spans"].get("pod.round", 0)
           / d["span_counts"]["pod.round"] for d in trace["devices"]
           if d["span_counts"].get("pod.round")]
    if not per:
        return None
    return 1e-6 * sum(per) / len(per)
