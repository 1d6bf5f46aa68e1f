"""Device-runtime dispatch ledger: what did the hardware actually do?

Every jit-cached device entry point in the tree — the ingest commit
scatters (XLA, Pallas and sharded routes), the merge joins
(sparse/wire/fused-repack/typed), the fan-in executors, the digest
reduction and the pack masks — reports each *dispatch* (one host call
that hands a program to the backend) to the process-wide
`DispatchLedger`. The ledger turns the fast-path invariants from
test-only assertions into runtime-observable facts
(docs/FASTPATH.md, docs/ANTIENTROPY.md):

- a pack-cache or digest-cache hit performs **zero** dispatches — the
  per-kernel counters do not move;
- a fused merge+repack (`merge_and_repack`) performs **exactly one**
  (`dense.merge_repack_step`);
- a write-combiner flush tick performs **exactly one** commit scatter.

Exposition (all on the default `MetricsRegistry`, so they ride the
``metrics`` wire op and the Prometheus renderer for free):

``crdt_tpu_device_dispatches_total{kernel}``
    dispatches per kernel entry point.
``crdt_tpu_device_dispatch_seconds{kernel}``
    wall time of the dispatching host call (log2 buckets). Dispatch
    is asynchronous on accelerators — this is enqueue + host prep
    time, not device execution time; fence-inclusive numbers live in
    the cells' device traces.
``crdt_tpu_device_compiles_total{kernel,bucket}``
    first-call events per (kernel, pow2 size bucket): callers pad
    batch dims to powers of two precisely so the jit cache sees O(log)
    distinct shapes, and the first call into a fresh bucket is the one
    that pays XLA compilation. Subsequent calls in the bucket are
    cache hits (``dispatches_total - compiles_total`` per kernel).
    Donation/sharding variants of one kernel can retrace within a
    bucket; the census counts the shape ladder, the dominant term.
``crdt_tpu_device_donation_violations_total{kernel}``
    donated input buffers still live after a donating dispatch —
    checked only on backends that honor donation (TPU/GPU; CPU ignores
    donation by design, jax warns and keeps the buffer).
``crdt_tpu_store_bytes{backend}``
    store-lane byte census at the last commit/merge that reported one.

The recording fast path is a class-based context manager (two
``perf_counter`` reads, one dict update under the ledger lock, one
counter inc, one histogram observe — single-digit microseconds against
dispatch costs of 100 µs+). It also opens a
``jax.profiler.TraceAnnotation`` named after the kernel, so every
ledger dispatch appears under its own name in a profile, beside the
device operations it enqueues. ``default_ledger().enabled = False``
short-circuits ``record()`` to a shared no-op so a caller can
measure the ledger's own overhead differentially (budget 5%).

Kernels *register* (by name) at module import of the instrumented
module, independent of ever dispatching — the crdtlint
``dispatch-ledger-unregistered`` gate imports the ops/parallel modules
and verifies the required set against ``registered_kernels()``.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import jax.profiler

from ..analysis.concurrency import make_lock
from .registry import MetricsRegistry, default_registry

# Resolved once per process: donation-violation checks only make sense
# on backends that honor donation, and the census gauge labels bytes by
# the backend that holds them.
_BACKEND: Optional[str] = None


def _backend() -> str:
    global _BACKEND
    if _BACKEND is None:
        try:
            import jax
            _BACKEND = jax.default_backend()
        except Exception:          # pragma: no cover - jax always here
            _BACKEND = "unknown"
    return _BACKEND


def pow2_bucket(dim: Optional[int]) -> str:
    """The compile-census bucket label for a leading batch dim: the
    pow2 ceiling (the shape ladder callers pad onto), or ``"scalar"``
    for kernels with no size-varying dim."""
    if dim is None:
        return "scalar"
    d = max(int(dim), 1)
    return str(1 << (d - 1).bit_length())


class DispatchLedger:
    """Per-kernel dispatch accounting over one `MetricsRegistry`.

    Thread-safe: merges, gossip rounds and serving-tier flushes
    dispatch from different threads into the same ledger.
    """

    # crdtlint lock-discipline contract (obs.registry module docstring).
    _CRDTLINT_GUARDED = {"_lock": ("_counts", "_compiled", "_registered")}
    # analysis/concurrency.py: the ledger lock releases before the
    # metric incs (`_dispatch`), so nothing ever nests inside it.
    _CRDTLINT_LOCK_ORDER = ("_lock",)

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._registry = registry if registry is not None \
            else default_registry()
        self._lock = make_lock("DispatchLedger._lock", 84)
        self._counts: Dict[str, int] = {}    # kernel -> dispatches
        self._compiled: set = set()          # (kernel, bucket) seen
        self._registered: set = set()        # kernel names declared
        self._metrics = None
        # Plain attribute, read unlocked on the hot path: toggling is
        # a coarse A/B switch (bench overhead measurement), not a
        # synchronization point.
        self.enabled = True

    def _instruments(self):
        m = self._metrics
        if m is None:
            reg = self._registry
            m = self._metrics = (
                reg.counter("crdt_tpu_device_dispatches_total",
                            "device dispatches by kernel entry point"),
                reg.histogram("crdt_tpu_device_dispatch_seconds",
                              "dispatching host-call wall time by "
                              "kernel (enqueue + host prep; async on "
                              "accelerators)"),
                reg.counter("crdt_tpu_device_compiles_total",
                            "first-call events per (kernel, pow2 size "
                            "bucket) — the compile census"),
                reg.counter("crdt_tpu_device_donation_violations_total",
                            "donated inputs still live after a "
                            "donating dispatch (TPU/GPU only)"),
                reg.gauge("crdt_tpu_store_bytes",
                          "store-lane bytes at the last reported "
                          "commit/merge, by backend"),
            )
        return m

    # --- registration (the crdtlint completeness surface) ---

    def register(self, *kernels: str) -> None:
        """Declare kernel entry points as ledger-instrumented. Called
        at module import of the instrumented module, so the
        `dispatch-ledger-unregistered` gate can verify coverage
        without dispatching anything."""
        with self._lock:
            self._registered.update(kernels)

    def registered_kernels(self) -> frozenset:
        with self._lock:
            return frozenset(self._registered)

    # --- reads (tests and invariant probes) ---

    def dispatches(self, kernel: Optional[str] = None) -> int:
        """Host-side dispatch count for one kernel, or the total over
        every kernel — the number a zero-dispatch invariant probe
        snapshots before and after the operation under test."""
        with self._lock:
            if kernel is not None:
                return self._counts.get(kernel, 0)
            return sum(self._counts.values())

    def as_dict(self) -> dict:
        with self._lock:
            return dict(self._counts)

    # --- recording ---

    def record(self, kernel: str, dim: Optional[int] = None,
               donated=None):
        """Context manager timing ONE dispatch of ``kernel``.

        ``dim`` is the compile-relevant leading batch dim (bucketed to
        its pow2 ceiling for the compile census); ``donated`` is a
        representative donated input array (one lane is enough — XLA
        donates the whole tree or none of it) checked post-call for
        donation violations on backends that honor donation."""
        if not self.enabled:
            return _NULL_RECORD
        return _Record(self, kernel, dim, donated)

    def _dispatch(self, kernel: str, seconds: float,
                  dim: Optional[int], donated) -> None:
        bucket = pow2_bucket(dim)
        disp_c, disp_h, comp_c, viol_c, _ = self._instruments()
        first = False
        with self._lock:
            self._counts[kernel] = self._counts.get(kernel, 0) + 1
            if (kernel, bucket) not in self._compiled:
                self._compiled.add((kernel, bucket))
                first = True
        disp_c.inc(kernel=kernel)
        disp_h.observe(seconds, kernel=kernel)
        if first:
            comp_c.inc(kernel=kernel, bucket=bucket)
        if donated is not None and _backend() in ("tpu", "gpu"):
            try:
                deleted = donated.is_deleted()
            except Exception:
                deleted = True     # can't tell — don't cry wolf
            if not deleted:
                viol_c.inc(kernel=kernel)

    # --- store census ---

    def census(self, store) -> int:
        """Report a store's lane bytes to the per-backend gauge.
        ``store`` is any NamedTuple of arrays (`DenseStore` & friends);
        ``nbytes`` is array metadata, so this costs no device work."""
        nbytes = 0
        for lane in store:
            nbytes += int(getattr(lane, "nbytes", 0) or 0)
        if self.enabled:
            self._instruments()[4].set(float(nbytes),
                                       backend=_backend())
        return nbytes


class _Record:
    __slots__ = ("_ledger", "_kernel", "_dim", "_donated", "_t0",
                 "_annotation")

    def __init__(self, ledger: DispatchLedger, kernel: str,
                 dim: Optional[int], donated):
        self._ledger = ledger
        self._kernel = kernel
        self._dim = dim
        self._donated = donated

    def __enter__(self):
        self._annotation = jax.profiler.TraceAnnotation(self._kernel)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is None:
            self._ledger._dispatch(self._kernel,
                                   time.perf_counter() - self._t0,
                                   self._dim, self._donated)
        return False


class _NullRecord:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_RECORD = _NullRecord()

_DEFAULT_LEDGER = DispatchLedger()


def kernel_label(kernel: str, interpret: bool) -> str:
    """Ledger name of one Pallas dispatch: interpret-mode runs (the
    CPU stand-in for Mosaic) carry an ``[interpret]`` suffix, so a
    chip run's ledger proves no kernel ran interpreted."""
    return kernel + "[interpret]" if interpret else kernel


def default_ledger() -> DispatchLedger:
    """The process-wide ledger every instrumented entry point reports
    to (same singleton discipline as `default_registry`)."""
    return _DEFAULT_LEDGER


def register(*kernels: str) -> None:
    _DEFAULT_LEDGER.register(*kernels)


def record(kernel: str, dim: Optional[int] = None, donated=None):
    """Module-level fast path for instrumented call sites: resolves
    the singleton once and short-circuits to a shared no-op context
    manager when the ledger is disabled."""
    led = _DEFAULT_LEDGER
    if not led.enabled:
        return _NULL_RECORD
    return led.record(kernel, dim, donated)


def census(store) -> int:
    return _DEFAULT_LEDGER.census(store)
