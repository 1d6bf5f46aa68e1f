"""Reduce a JAX profiler trace (``.xplane.pb``) to the cells' numbers.

The window is the host span the harness opens around the measured
window (``cells.window``). Inside it, for each device plane:

- ``busy_ns``: the union of the intervals in which an operation ran
  (events of the plane's op line), so overlapping ops count once;
- ``ops``: device nanoseconds and event counts by op name;
- ``in_spans``: device busy nanoseconds inside the host spans of a
  given name (the union of device intervals clipped to those spans);
- ``gaps``: the longest idle stretches, each labelled with the
  innermost host span open at its midpoint (``-`` where none was);

and ``host_counts``: how many host events of each name start inside
the window (spans, dispatches, transfers).

Every number is computed from event starts and durations alone, with
integer nanoseconds; `interval_union`, `clip_sum` and `label_gaps` are
the arithmetic, kept apart so that the self-test can check them on
hand-made intervals as well as on a recorded trace.
"""

from __future__ import annotations

import bisect
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]       # [start, end) in ns
#: Entries in each list of a breakdown (the result line allows ten).
TOP = 10

# Device-plane lines that aggregate other lines rather than record ops.
_NOT_OP_LINES = ("XLA Modules", "Steps", "Framework Ops",
                 "Framework Name Scope", "Source code", "XLA TraceMe")


def interval_union(iv: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[Interval] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip_sum(union: Sequence[Interval], spans: Iterable[Interval]) -> int:
    """Nanoseconds of ``union`` (disjoint, sorted) inside the union of
    ``spans``."""
    total = 0
    starts = [s for s, _ in union]
    for a, b in interval_union(spans):
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(union) and union[i][0] < b:
            s, e = union[i]
            total += max(0, min(e, b) - max(s, a))
            i += 1
    return total


def gaps_of(union: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle stretches of ``[lo, hi)`` that ``union`` leaves."""
    out, at = [], lo
    for s, e in union:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def label_gaps(gaps: Sequence[Interval],
               host: Sequence[Tuple[str, int, int]], top: int = 10
               ) -> List[Tuple[str, int]]:
    """The ``top`` longest gaps as ``(host span, ns)``; the span is the
    shortest host event that holds the gap's midpoint."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) // 2
        holding = [(he - hs, name) for name, hs, he in host
                   if hs <= mid < he]
        out.append((min(holding)[1] if holding else "-", e - s))
    return out


def _events(line) -> List[Tuple[str, int, int]]:
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def _is_device(plane) -> bool:
    # The chips' planes; "/device:CUSTOM:..." planes hold no chip ops.
    return plane.name.startswith("/device:TPU:")


def _op_lines(plane) -> list:
    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name == "XLA Ops"]
    return named or [ln for ln in lines if ln.name not in _NOT_OP_LINES]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce_profile(profile, window_span: str,
                   span_names: Sequence[str]) -> Optional[dict]:
    """The numbers of one traced window, or None where the trace holds
    no ``window_span`` or no device plane."""
    host: List[Tuple[str, int, int]] = []
    devices = []
    for plane in profile.planes:
        if _is_device(plane):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += _events(line)
    windows = [(s, e) for name, s, e in host if name == window_span]
    if not windows or not devices:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    host_in = [(n, s, e) for n, s, e in host if e > lo and s < hi]
    counts: Dict[str, int] = {}
    for n, s, _ in host_in:
        if lo <= s < hi:
            counts[n] = counts.get(n, 0) + 1
    out = {"window_ns": hi - lo, "host_counts": counts, "devices": []}
    for plane in devices:
        evs = []
        for line in _op_lines(plane):
            evs += [(n, max(s, lo), min(e, hi)) for n, s, e in _events(line)
                    if e > lo and s < hi]
        union = interval_union((s, e) for _, s, e in evs)
        ops: Dict[str, List[int]] = {}
        for n, s, e in evs:
            acc = ops.setdefault(n, [0, 0])
            acc[0] += e - s
            acc[1] += 1
        in_spans = {name: clip_sum(union, [(s, e) for n, s, e in host_in
                                           if n == name])
                    for name in span_names}
        out["devices"].append({
            "plane": plane.name,
            "busy_ns": sum(e - s for s, e in union),
            "ops": ops,
            "in_spans": in_spans,
            "span_counts": {name: sum(1 for n, _, _ in host_in
                                      if n == name)
                            for name in span_names},
            "gaps": label_gaps(gaps_of(union, lo, hi), host_in, TOP),
        })
    return out


def breakdown(reduced: dict) -> dict:
    """The result line's ``breakdown``: the device ops that took most
    time and the longest idle gaps, in seconds, over all devices."""
    ops: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for dev in reduced["devices"]:
        for name, (ns, _) in dev["ops"].items():
            ops[name] = ops.get(name, 0.0) + ns * 1e-9
        gaps += [(name, ns * 1e-9) for name, ns in dev["gaps"]]
    return {"device_ops": sorted(([k, v] for k, v in ops.items()),
                                 key=lambda kv: -kv[1])[:TOP],
            "idle_gaps": [list(g) for g in
                          sorted(gaps, key=lambda g: -g[1])[:TOP]]}


def describe(path: str) -> None:
    """Print what a trace holds: its planes, their lines with event
    counts, and each line's events that took most time. Read one trace
    this way before writing a reader against it."""
    for plane in load(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            tot: Dict[str, List[int]] = {}
            for name, s, e in _events(line):
                acc = tot.setdefault(name, [0, 0])
                acc[0] += e - s
                acc[1] += 1
            n = sum(c for _, c in tot.values())
            print(f"  line {line.name!r}: {n} events")
            for name, (ns, c) in sorted(tot.items(),
                                        key=lambda kv: -kv[1][0])[:TOP]:
                print(f"    {ns / 1e6:12.3f} ms {c:8d}x  {name[:100]}")


if __name__ == "__main__":
    import sys
    describe(sys.argv[1])
