"""Device dispatch ledger (docs/OBSERVABILITY.md, device plane): the
fast-path invariants asserted through the RUNTIME metrics surface, not
test instrumentation — a pack-cache or digest-cache hit moves no
per-kernel dispatch counter, a fused merge+repack moves exactly one,
a combiner flush tick moves exactly one commit scatter. Plus the
compile census (first call per pow2 bucket), donation-violation
detection, the store-bytes census and the disable switch the bench
overhead probe leans on."""

import numpy as np
import pytest

from crdt_tpu import DenseCrdt
from crdt_tpu.obs import device as obs_device
from crdt_tpu.obs.device import DispatchLedger, default_ledger, \
    pow2_bucket
from crdt_tpu.obs.registry import MetricsRegistry, default_registry
from crdt_tpu.testing import FakeClock

pytestmark = pytest.mark.ledger

BASE = 1_700_000_000_000


def _make(node="n", n_slots=64, **kw):
    return DenseCrdt(node, n_slots=n_slots,
                     wall_clock=FakeClock(start=BASE), **kw)


def _delta(before, after):
    """Per-kernel dispatch-count movement between two snapshots."""
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(before) | set(after)
            if after.get(k, 0) != before.get(k, 0)}


def _cache_hits(name, node):
    return default_registry().counter(name).value(outcome="hit",
                                                  node=node)


# --- bucketing -------------------------------------------------------

def test_pow2_bucket():
    assert pow2_bucket(None) == "scalar"
    assert pow2_bucket(0) == "1"
    assert pow2_bucket(1) == "1"
    assert pow2_bucket(2) == "2"
    assert pow2_bucket(3) == "4"
    assert pow2_bucket(1024) == "1024"
    assert pow2_bucket(1025) == "2048"


# --- zero-dispatch invariants (default ledger = runtime metrics) ----

def test_pack_cache_hit_dispatches_nothing():
    led = default_ledger()
    a = _make("a")
    a.put_batch([1, 2], [10, 20])
    wm = a.canonical_time
    a.put_batch([3], [30])

    before = led.as_dict()
    first = a.pack_since(wm)
    moved = _delta(before, led.as_dict())
    # The miss computed the watermark delta mask on device.
    assert moved.get("dense.delta_mask") == 1

    hits = _cache_hits("crdt_tpu_pack_cache_total", "a")
    before = led.as_dict()
    again = a.pack_since(wm)
    assert _delta(before, led.as_dict()) == {}
    assert again is first
    assert _cache_hits("crdt_tpu_pack_cache_total", "a") == hits + 1


def test_digest_cache_hit_dispatches_nothing():
    led = default_ledger()
    c = _make("dig")
    c.put_batch([4, 9], [1, 2])

    before = led.as_dict()
    tree = c.digest_tree()
    assert _delta(before, led.as_dict()) == {
        "digest.digest_tree_device": 1}

    hits = _cache_hits("crdt_tpu_digest_cache_total", "dig")
    before = led.as_dict()
    assert c.digest_tree() is tree
    assert _delta(before, led.as_dict()) == {}
    assert _cache_hits("crdt_tpu_digest_cache_total", "dig") == hits + 1


def test_fused_merge_repack_is_one_dispatch_and_seeds_the_cache():
    led = default_ledger()
    a, b = _make("a"), _make("b")
    a.put_batch([5, 7], [50, 70])
    packed, ids = a.pack_since(None)

    before = led.as_dict()
    out = b.merge_and_repack(packed, ids)
    assert _delta(before, led.as_dict()) == {
        "dense.merge_repack_step": 1}

    # The fused kernel seeded b's pack cache under the post-merge key:
    # the watermark-aligned repack is a hit, zero dispatches, same
    # cached object.
    hits = _cache_hits("crdt_tpu_pack_cache_total", "b")
    before = led.as_dict()
    assert b.pack_since(None) is out
    assert _delta(before, led.as_dict()) == {}
    assert _cache_hits("crdt_tpu_pack_cache_total", "b") == hits + 1


def test_combiner_flush_tick_is_one_commit_scatter():
    led = default_ledger()
    c = _make("ing", n_slots=128)
    with c.ingest():
        c.put_batch([1, 2, 3, 4], [10, 20, 30, 40])
        c.put_batch([5, 6], [50, 60])
        staged = led.as_dict()
    c.drain_ingest()
    moved = _delta(staged, led.as_dict())
    # Staging dispatched nothing; the flush tick is exactly one
    # commit scatter regardless of how many puts it coalesced.
    assert moved == {"dense.ingest_scatter": 1}


# --- compile census --------------------------------------------------

def test_compile_census_counts_first_call_per_bucket_only():
    reg = default_registry()
    comp = reg.counter("crdt_tpu_device_compiles_total")
    disp = reg.counter("crdt_tpu_device_dispatches_total")

    def compiles(kernel):
        return sum(s["value"] for s in comp.samples()
                   if s["labels"].get("kernel") == kernel)

    c = _make("census", n_slots=128)
    c.put_batch([1], [1])
    c0 = compiles("dense.put_scatter")
    d0 = disp.value(kernel="dense.put_scatter")
    # Same batch shape -> same pow2 bucket -> jit cache hit: the
    # dispatch counter moves, the compile census does not.
    c.put_batch([2], [2])
    assert compiles("dense.put_scatter") == c0
    assert disp.value(kernel="dense.put_scatter") == d0 + 1


def test_compile_census_new_bucket_is_a_new_first_call():
    reg = MetricsRegistry()
    led = DispatchLedger(reg)
    with led.record("k", dim=4):
        pass
    with led.record("k", dim=4):
        pass
    with led.record("k", dim=9):   # pow2 ceiling 16: fresh bucket
        pass
    comp = reg.counter("crdt_tpu_device_compiles_total")
    assert led.dispatches("k") == 3
    assert comp.value(kernel="k", bucket="4") == 1
    assert comp.value(kernel="k", bucket="16") == 1


# --- donation checks -------------------------------------------------

class _LiveBuffer:
    def is_deleted(self):
        return False


class _DeletedBuffer:
    def is_deleted(self):
        return True


def test_donation_violation_counted_on_donating_backends(monkeypatch):
    monkeypatch.setattr(obs_device, "_BACKEND", "tpu")
    reg = MetricsRegistry()
    led = DispatchLedger(reg)
    with led.record("k", dim=2, donated=_LiveBuffer()):
        pass
    with led.record("k", dim=2, donated=_DeletedBuffer()):
        pass
    viol = reg.counter("crdt_tpu_device_donation_violations_total")
    assert viol.value(kernel="k") == 1


def test_donation_not_checked_on_cpu(monkeypatch):
    monkeypatch.setattr(obs_device, "_BACKEND", "cpu")
    reg = MetricsRegistry()
    led = DispatchLedger(reg)
    with led.record("k", dim=2, donated=_LiveBuffer()):
        pass
    viol = reg.counter("crdt_tpu_device_donation_violations_total")
    assert viol.value(kernel="k") == 0


# --- census + disable switch ----------------------------------------

def test_store_bytes_census(monkeypatch):
    monkeypatch.setattr(obs_device, "_BACKEND", "cpu")
    reg = MetricsRegistry()
    led = DispatchLedger(reg)
    store = (np.zeros(16, np.int64), np.zeros(16, np.int32),
             np.zeros(16, np.uint8))
    n = led.census(store)
    assert n == 16 * 8 + 16 * 4 + 16
    gauge = reg.gauge("crdt_tpu_store_bytes")
    assert gauge.value(backend="cpu") == float(n)


def test_disabled_ledger_records_nothing():
    led = DispatchLedger(MetricsRegistry())
    led.enabled = False
    with led.record("k", dim=8):
        pass
    assert led.dispatches() == 0
    # census still returns the byte total, it just skips the gauge
    assert led.census((np.zeros(4, np.int64),)) == 32


def test_failed_dispatch_is_not_counted():
    led = DispatchLedger(MetricsRegistry())
    with pytest.raises(RuntimeError):
        with led.record("k", dim=8):
            raise RuntimeError("backend rejected the program")
    assert led.dispatches("k") == 0


def test_register_is_import_time_not_dispatch_time():
    led = DispatchLedger(MetricsRegistry())
    led.register("mod.kernel_a", "mod.kernel_b")
    assert {"mod.kernel_a",
            "mod.kernel_b"} <= set(led.registered_kernels())
    assert led.dispatches() == 0


def test_dispatch_is_named_in_the_profile(tmp_path):
    """A recorded dispatch opens a profiler annotation named after its
    kernel: captured, it is an event of that name on the host plane,
    on the same clock as the device's operations."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData
    led = DispatchLedger(MetricsRegistry())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with led.record("mod.profiled_kernel", dim=8):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "mod.profiled_kernel" in names
    assert led.dispatches("mod.profiled_kernel") == 1
