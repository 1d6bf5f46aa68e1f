"""Unified telemetry for the CRDT runtime (SURVEY.md §5: the reference
has no tracing/metrics at all; ROADMAP's production north star needs
both).

Three cooperating layers, all dependency-free (stdlib + the jax the
package already requires):

- :mod:`~crdt_tpu.obs.registry` — a process-wide **metrics registry**
  (counters, gauges, log2-bucket histograms; thread-safe). The legacy
  counter dataclasses (`MergeStats`, `PeerSyncStats`, `WireTally`)
  attach to it as weak-referenced collectors, so every live backend,
  peer and wire endpoint shows up in one snapshot instead of three
  orphan objects.
- :mod:`~crdt_tpu.obs.trace` — **HLC-stamped structured trace events**
  (merge dispatch, gossip round, wire frame, checkpoint, breaker
  transition) in a bounded in-memory ring with an optional JSONL sink;
  `span()` threads `jax.profiler.TraceAnnotation` through the
  merge/pack/wire phases so TPU profiles show named kernels. Disabled
  by default — the hot path pays one attribute read.
- :mod:`~crdt_tpu.obs.lag` — the **convergence-lag monitor**: per-peer
  staleness (local HLC head minus peer watermark, in millis and
  pending records) derived from `GossipNode` watermarks; surfaced as
  ``node.health()`` and over the wire via the `SyncServer` ``metrics``
  op.

Fleet plane (PR 11): :mod:`~crdt_tpu.obs.probe` writes timestamped
canary beats into a reserved slot range through the ordinary write
path; :mod:`~crdt_tpu.obs.fleet` scrapes N replicas' ``metrics`` ops
into a per-(origin, observer) replication-lag matrix and a
machine-readable SLO verdict (``python -m crdt_tpu.obs fleet``); the
``trace`` hello capability (net.py) carries round ids across the wire
so initiator sync spans and responder merge spans correlate in one
JSONL sink.

Device plane (PR 12): :mod:`~crdt_tpu.obs.device` is the **dispatch
ledger** — every jit-cached device entry point (ops + parallel)
reports per-kernel dispatch counts, wall-time histograms, a
(kernel, pow2-bucket) compile census, donation-violation checks, and
a store-bytes gauge, turning the fast-path zero-dispatch invariants
into runtime-observable metrics.

Quantile plane (PR 18): :mod:`~crdt_tpu.obs.sketch` is a mergeable
DDSketch-style **relative-error quantile sketch** — the registry's
``sketch()`` instrument records latencies next to the log2 histograms
but answers quantiles within ~1% instead of bucket ceilings, merges
commutatively/associatively across replicas (``obs/fleet.py`` folds
per-replica sketches into fleet-true p99 for ``evaluate_slo`` and the
autoscaler's 14.6 ms gate), and ships on the ``metrics`` op behind
the negotiated ``sketch`` hello cap. :mod:`~crdt_tpu.obs.recorder` is
the **SLO flight recorder**: bounded debug bundles captured when the
SLO flips to failing, the lease fence trips, or the deadlock
sanitizer fires — fetched later via the ``debug_dump`` wire op /
``python -m crdt_tpu.obs dump``.

Exposition: :func:`~crdt_tpu.obs.render.render_prometheus` renders a
snapshot as Prometheus text; ``python -m crdt_tpu.obs`` polls a live
node's ``metrics`` op or summarizes a trace JSONL into a per-phase
latency table (see docs/OBSERVABILITY.md).
"""

from __future__ import annotations

from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       Sketch, default_registry)
from .sketch import QuantileSketch, merge_sketches, sketch_quantile
from .recorder import FlightRecorder, default_recorder
from .trace import TraceRing, round_id, span, tracer
from .lag import health_status, lag_entry, lag_millis
from .probe import CanaryProbe, canary_observed
from .fleet import (evaluate_slo, format_matrix, lag_matrix,
                    poll_fleet, render_federation)
from .device import DispatchLedger, default_ledger, pow2_bucket
from .render import (format_phase_table, render_prometheus,
                     render_summary, summarize_trace)


def metrics_snapshot() -> dict:
    """One self-describing snapshot of the process-wide registry — the
    payload the `SyncServer` ``metrics`` wire op returns."""
    return default_registry().snapshot()


__all__ = [
    "Counter", "Gauge", "Histogram", "Sketch", "MetricsRegistry",
    "default_registry", "metrics_snapshot",
    "QuantileSketch", "merge_sketches", "sketch_quantile",
    "FlightRecorder", "default_recorder",
    "TraceRing", "tracer", "span", "round_id",
    "lag_millis", "lag_entry", "health_status",
    "CanaryProbe", "canary_observed",
    "poll_fleet", "lag_matrix", "evaluate_slo", "render_federation",
    "format_matrix",
    "DispatchLedger", "default_ledger", "pow2_bucket",
    "render_prometheus", "render_summary", "summarize_trace",
    "format_phase_table",
]
