"""Mean of one phase of a program's phase histogram, read from the
in-process metrics registry after the window.

The serving tier decomposes each served read frame
(``crdt_tpu_serve_read_phase_seconds``) and each acknowledged write
(``crdt_tpu_serve_ack_phase_seconds``) by phase. Only the tier's replica
executor paths observe them, and set-up calls the model directly, so the
mean covers the served traffic of the whole run, not only the traced
seconds. A program without the histogram or the phase reads None.
"""


def mean_ms(histogram: str, phase: str):
    from crdt_tpu.obs.registry import default_registry
    samples = [s for s in default_registry().histogram(histogram).samples()
               if s["labels"].get("phase") == phase]
    count = sum(s["count"] for s in samples)
    if not count:
        return None
    return 1e3 * sum(s["sum"] for s in samples) / count
