"""crdtlint self-tests: suppressions, host linter, lattice law search,
jaxpr audit goldens, CLI gate, and the runtime sanitizer.

The CLI smoke tests run ``python -m crdt_tpu.analysis`` exactly as CI
does (subprocess, fresh interpreter) — the shipped tree must come back
clean, and both planted fixtures must fail loudly.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from crdt_tpu.analysis.findings import (
    Finding, apply_suppressions, parse_suppressions)
from crdt_tpu.analysis.host_lint import lint_file, lint_source
from crdt_tpu.analysis import sanitizer

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")


def _run_cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "crdt_tpu.analysis", *argv],
        cwd=REPO, capture_output=True, text=True, timeout=300)


# ---------------------------------------------------------------- findings


def test_suppression_parsing_covers_own_and_next_line():
    src = (
        "x = 1\n"
        "# crdtlint: disable=wall-clock-read -- build artifact reaping\n"
        "t = time.time()\n"
        "u = time.time()\n")
    supp = parse_suppressions(src)
    assert supp.covers("wall-clock-read", 2)
    assert supp.covers("wall-clock-read", 3)
    assert not supp.covers("wall-clock-read", 4)
    assert not supp.covers("record-mutation", 3)
    assert supp.unexplained == []


def test_suppression_without_reason_is_its_own_finding():
    src = "# crdtlint: disable=socket-no-timeout\nconnect()\n"
    supp = parse_suppressions(src)
    assert supp.unexplained == [1]
    kept = apply_suppressions(
        [Finding(rule="socket-no-timeout", path="f.py", line=2,
                 message="m")], supp, "f.py")
    rules = {f.rule for f in kept}
    # a reasonless suppression is inert: the original finding survives
    # AND the malformed comment is flagged
    assert rules == {"socket-no-timeout", "suppression-without-reason"}


# --------------------------------------------------------------- host lint


def test_racy_gossip_fixture_trips_every_planted_rule():
    findings = lint_file(os.path.join(FIXTURES, "racy_gossip.py"))
    rules = sorted({f.rule for f in findings})
    assert rules == [
        "add-batch-unique-keys",
        "hlc-wall-compare",
        "lock-discipline",
        "record-mutation",
        "socket-no-timeout",
        "wall-clock-read",
    ]
    # both undisciplined registry touches, not just one
    assert sum(f.rule == "lock-discipline" for f in findings) == 2


def test_donated_buffer_reuse_flagged():
    src = (
        "def f(store, cs):\n"
        "    out = put_scatter(store, cs, t, me, donate=True)\n"
        "    return store.lt + out.lt\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "donated-buffer-reuse" in rules


def test_donated_buffer_rebind_not_flagged():
    src = (
        "def f(store, cs):\n"
        "    store = put_scatter(store, cs, t, me, donate=True)\n"
        "    return store.lt\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "donated-buffer-reuse" not in rules


def test_peer_connection_idle_timeout_none_flagged():
    src = (
        "from crdt_tpu.net import PeerConnection\n"
        "conn = PeerConnection('h', 1, idle_timeout=None)\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "socket-no-timeout"]
    assert len(findings) == 1
    assert "idle_timeout" in findings[0].message


def test_peer_connection_with_idle_timeout_not_flagged():
    src = (
        "from crdt_tpu.net import PeerConnection\n"
        "a = PeerConnection('h', 1)\n"
        "b = PeerConnection('h', 1, idle_timeout=5.0)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "socket-no-timeout" not in rules


def test_combiner_bypass_flagged_without_gate():
    src = (
        "def commit(self, slots, vals, t, me):\n"
        "    self._store = put_scatter(self._store, slots, vals, t, me)\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "scatter-combiner-bypass"]
    assert len(findings) == 1
    assert "drain" in findings[0].message


def test_combiner_bypass_gate_must_precede_the_write():
    # Draining AFTER the scatter is the bug, not the fix: the staged
    # backlog still commits over the direct write.
    src = (
        "def commit(self, slots, vals, t, me):\n"
        "    self._store = delete_scatter(self._store, slots, t, me)\n"
        "    self.drain_ingest()\n")
    rules = [f.rule for f in lint_source(src, "snippet.py")]
    assert "scatter-combiner-bypass" in rules


def test_combiner_bypass_drain_gate_passes():
    src = (
        "def put_slot_records(self, recs):\n"
        "    self.drain_ingest()\n"
        "    self._store = record_scatter(self._store, recs)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "scatter-combiner-bypass" not in rules


def test_collective_fallback_silent_flagged():
    src = (
        "class Node:\n"
        "    def __init__(self, group):\n"
        "        self._group = group\n"
        "    def round(self):\n"
        "        try:\n"
        "            self._group.join()\n"
        "        except Exception:\n"
        "            return self.socket_round()\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "collective-socket-fallback-silent"]
    assert len(findings) == 1
    assert "crdt_tpu_collective_fallback_total" in findings[0].message


def test_collective_fallback_counted_passes():
    src = (
        "class Node:\n"
        "    def __init__(self, group):\n"
        "        self._group = group\n"
        "    def round(self):\n"
        "        try:\n"
        "            self._group.join()\n"
        "        except Exception:\n"
        "            self.counter('crdt_tpu_collective_fallback_total')"
        ".inc()\n"
        "            return self.socket_round()\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "collective-socket-fallback-silent" not in rules


def test_collective_fallback_reraise_passes():
    # Loud is fine: a handler that re-raises never hides the downgrade.
    src = (
        "class Node:\n"
        "    def __init__(self, group):\n"
        "        self._group = group\n"
        "    def round(self):\n"
        "        try:\n"
        "            self._group.join()\n"
        "        except Exception:\n"
        "            raise\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "collective-socket-fallback-silent" not in rules


def test_collective_fallback_outside_grouped_class_not_flagged():
    # Without a pod-local group on the class, a .join() in a try is
    # unrelated (thread.join, path join on an object named group_dir).
    src = (
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._threads = []\n"
        "    def stop(self):\n"
        "        try:\n"
        "            self.group_thread.join()\n"
        "        except Exception:\n"
        "            pass\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "collective-socket-fallback-silent" not in rules


def test_combiner_bypass_staging_branch_passes():
    # put_batch's shape: branch on the staging handle, fall through to
    # the direct scatter only when no window is open.
    src = (
        "def put_batch(self, slots, vals, t, me):\n"
        "    if self._ingest is not None:\n"
        "        return self._ingest.stage(slots, vals, None)\n"
        "    self._store = put_scatter(self._store, slots, vals, t, me)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "scatter-combiner-bypass" not in rules


def test_combiner_bypass_suppressible_with_reason():
    src = (
        "def flush(self, owner):\n"
        "    # crdtlint: disable=scatter-combiner-bypass -- the flush"
        " IS the barrier\n"
        "    owner._store = ingest_scatter(owner._store, s, lt, v)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "scatter-combiner-bypass" not in rules
    assert "suppression-without-reason" not in rules


def test_pack_path_copy_flagged():
    # All three copy shapes the zero-copy refactor removed: a bytes()
    # staging copy, an np.asarray re-materialization, a .tobytes().
    src = (
        "def pack_rows(delta):\n"
        "    blob = bytes(delta.lt)\n"
        "    a = np.asarray(delta.slots, np.int32)\n"
        "    return blob + a.tobytes()\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "pack-path-extra-copy"]
    assert len(findings) == 3
    assert all("crdt_tpu_pack_copy_bytes_total" in f.message
               for f in findings)


def test_pack_path_rule_skips_unpack_and_merge():
    # The wire-IN side legitimately materializes host arrays — the
    # rule covers only the device→wire direction.
    src = (
        "def unpack_rows(meta, blob):\n"
        "    return bytes(blob)\n"
        "def merge_packed(self, packed, ids):\n"
        "    lanes = np.asarray(packed.lt)\n"
        "    return lanes.tobytes()\n"
        "def scatter_rows(x):\n"
        "    return bytes(x)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "pack-path-extra-copy" not in rules


def test_pack_path_rule_covers_frame_layer_names():
    # `encode` / `send_bytes_frame` don't contain "pack" but ARE the
    # pack path's last hop — covered by exact name.
    src = (
        "def send_bytes_frame(sock, bufs):\n"
        "    sock.sendall(bytes(bufs[0]))\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "pack-path-extra-copy" in rules


def test_pack_path_copy_suppressible_with_reason():
    src = (
        "def pack_rows(delta):\n"
        "    # crdtlint: disable=pack-path-extra-copy -- foreign-lane"
        " normalization, counted in the copy-bytes counter\n"
        "    a = np.ascontiguousarray(delta.slots, np.int32)\n"
        "    return a\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "pack-path-extra-copy" not in rules
    assert "suppression-without-reason" not in rules


def test_async_blocking_call_flags_all_three_families():
    # The three blocking families the serving tier must never touch
    # from a coroutine: time.sleep, a raw socket ctor, and the sync
    # frame helpers (which block on sendall/recv under the hood).
    src = (
        "async def handle(reader, writer):\n"
        "    time.sleep(0.01)\n"
        "    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)\n"
        "    send_frame(s, {'op': 'hello'}, None)\n"
        "    reply = recv_frame(s, None)\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "async-blocking-call"]
    assert len(findings) == 4
    assert all("coroutine handle()" in f.message for f in findings)


def test_async_blocking_call_ignores_sync_functions():
    # The exact same calls in a plain def are the NORMAL sync path
    # (net.py is built from them) — only coroutines are in scope.
    src = (
        "def handle(conn):\n"
        "    time.sleep(0.01)\n"
        "    send_frame(conn, {'op': 'hello'}, None)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "async-blocking-call" not in rules


def test_async_blocking_call_skips_nested_sync_def_and_executor_refs():
    # A sync helper DEFINED inside the coroutine is executor bait —
    # its body runs off-loop. Passing a frame helper by reference to
    # run_in_executor never calls it on the loop either.
    src = (
        "async def serve(loop, conn, frame):\n"
        "    def _pump():\n"
        "        send_frame(conn, frame, None)\n"
        "        time.sleep(0)\n"
        "    await loop.run_in_executor(None, _pump)\n"
        "    await loop.run_in_executor(None, recv_frame, conn, None)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "async-blocking-call" not in rules


def test_async_blocking_call_flags_blocking_socket_methods():
    src = (
        "async def relay(sock, blob):\n"
        "    sock.sendall(blob)\n"
        "    return sock.recv(4)\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "async-blocking-call"]
    assert len(findings) == 2


def test_async_blocking_call_awaited_calls_pass():
    # Directly-awaited calls are async APIs whatever their name —
    # asyncio's own loop.sock_connect / connect coroutines must pass.
    src = (
        "async def dial(loop, sock, addr, conn):\n"
        "    await loop.sock_connect(sock, addr)\n"
        "    await conn.connect()\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "async-blocking-call" not in rules


def test_async_blocking_call_suppressible_with_reason():
    src = (
        "async def shutdown(self, sock):\n"
        "    # crdtlint: disable=async-blocking-call -- teardown path,"
        " loop already draining\n"
        "    sock.sendall(b'bye')\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "async-blocking-call" not in rules
    assert "suppression-without-reason" not in rules


def test_metric_name_unprefixed_flagged():
    src = (
        "def setup(reg):\n"
        "    reg.counter('requests_total', 'h')\n"
        "    reg.gauge('depth')\n"
        "    reg.histogram('lat_seconds', 'h')\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "metric-name-unprefixed"]
    assert len(findings) == 3
    assert {f.line for f in findings} == {2, 3, 4}
    assert "namespace" in findings[0].message


def test_metric_name_prefixed_passes():
    src = (
        "def setup(reg):\n"
        "    c = reg.counter('crdt_tpu_requests_total', 'h')\n"
        "    c.inc(op='put', node=node)\n"
        "    reg.histogram('crdt_tpu_lat_seconds').observe(\n"
        "        0.5, trigger=trigger, peer=name)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "metric-name-unprefixed" not in rules


def test_metric_label_from_user_key_flagged():
    src = (
        "def record(c, h, key, slot):\n"
        "    c.inc(key=str(key))\n"
        "    h.observe(0.1, shard=slot % 4)\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "metric-name-unprefixed"]
    assert {f.line for f in findings} == {2, 3}
    assert "cardinality" in findings[0].message


def test_metric_label_rule_skips_jax_at_set():
    # jax's .at[slots].set(values, mode='drop') is not a metric sink:
    # the cardinality scan only inspects keyword values, and mode= is
    # a constant
    src = (
        "def commit(store, slots, values):\n"
        "    return store.at[slots].set(values, mode='drop')\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "metric-name-unprefixed" not in rules


def test_metric_name_suppressible_with_reason():
    src = (
        "def bridge(reg):\n"
        "    # crdtlint: disable=metric-name-unprefixed --"
        " exporting a foreign exporter's series verbatim\n"
        "    reg.counter('up', 'h')\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "metric-name-unprefixed" not in rules
    assert "suppression-without-reason" not in rules


def test_router_bypass_ungated_enqueue_flagged():
    src = (
        "class Tier:\n"
        "    def __init__(self, router=None):\n"
        "        self.router = router\n"
        "        self._q = []\n"
        "    def handle(self, msg, slot, value):\n"
        "        self._q.append((slot, value))\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "router-epoch-bypass"]
    assert len(findings) == 1
    assert findings[0].line == 6
    assert "handle()" in findings[0].message


def test_router_bypass_enqueue_before_gate_flagged():
    src = (
        "class Tier:\n"
        "    def __init__(self, router=None):\n"
        "        self.router = router\n"
        "        self._q = []\n"
        "    def handle(self, msg, slot, value):\n"
        "        self._q.append((slot, value))\n"
        "        verdict = self.router.check(slot, msg.get('epoch'),"
        " True)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "router-epoch-bypass" in rules


def test_router_bypass_gated_enqueue_clean():
    src = (
        "class Tier:\n"
        "    def __init__(self, router=None):\n"
        "        self.router = router\n"
        "        self._q = []\n"
        "    async def handle(self, msg, slot, value):\n"
        "        routed = await self._route_verdict(msg, slot, True)\n"
        "        if routed is not None:\n"
        "            return routed\n"
        "        self._q.append((slot, value))\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "router-epoch-bypass" not in rules


def test_router_bypass_ignores_routerless_classes():
    # a queue-owning class with no router carries no partition
    # ownership contract — nothing to gate
    src = (
        "class Combiner:\n"
        "    def __init__(self):\n"
        "        self._q = []\n"
        "    def push(self, item):\n"
        "        self._q.append(item)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "router-epoch-bypass" not in rules


def test_router_bypass_covers_mpsc_push_spelling():
    # the MPSC-era enqueue (self._q.push) carries the same routing
    # contract as the list-era append, and the batched admission call
    # (check_batch) gates it
    src = (
        "class Tier:\n"
        "    def __init__(self, router=None):\n"
        "        self.router = router\n"
        "        self._q = MpscQueue()\n"
        "    def ungated(self, slot, value):\n"
        "        self._q.push(('j', slot, value))\n"
        "    def gated(self, slots, epoch):\n"
        "        admit = self.router.check_batch(slots, epoch, True)\n"
        "        self._q.push(('b', slots))\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "router-epoch-bypass"]
    assert len(findings) == 1
    assert findings[0].line == 6
    assert "ungated()" in findings[0].message


def test_combiner_enqueue_bare_append_flagged():
    src = (
        "class Tier:\n"
        "    def __init__(self, crdt):\n"
        "        self._q = MpscQueue()\n"
        "        self._wc = None\n"
        "    def handle(self, slot, value, fut):\n"
        "        self._q.append((slot, value, fut))\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "combiner-enqueue-unsafe"]
    assert len(findings) == 1
    assert findings[0].line == 6
    assert "handle()" in findings[0].message
    assert ".push" in findings[0].message


def test_combiner_enqueue_mpsc_push_clean():
    src = (
        "class Tier:\n"
        "    def __init__(self, crdt):\n"
        "        self._q = MpscQueue()\n"
        "        self._wc = None\n"
        "    def handle(self, slot, value, fut):\n"
        "        self._q.push((slot, value, fut))\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "combiner-enqueue-unsafe" not in rules


def test_combiner_enqueue_ignores_non_combiner_classes():
    # no self._wc in __init__ -> not a combiner owner; a plain list
    # queue drained on the same thread carries no MPSC contract
    src = (
        "class Hub:\n"
        "    def __init__(self):\n"
        "        self._q = []\n"
        "    def handle(self, item):\n"
        "        self._q.append(item)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "combiner-enqueue-unsafe" not in rules


def test_combiner_enqueue_init_exempt_and_inner_targets_flagged():
    # __init__ is construction (happens-before publication); any
    # deeper self._q... target (a stripe's raw list) is still a
    # bypass of the MPSC gate
    src = (
        "class Tier:\n"
        "    def __init__(self, crdt):\n"
        "        self._q = MpscQueue()\n"
        "        self._q.append = None\n"
        "        self._wc = None\n"
        "    def sneak(self, entry):\n"
        "        self._q._stripes[0].items.append(entry)\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "combiner-enqueue-unsafe"]
    assert len(findings) == 1
    assert findings[0].line == 7
    assert "sneak()" in findings[0].message


def test_combiner_enqueue_shipped_serve_tier_clean():
    # pin: the real serving tier routes every producer through the
    # MPSC gate — this is the tree-level guarantee the rule exists for
    import crdt_tpu.serve as serve_mod
    findings = [f for f in lint_file(serve_mod.__file__)
                if f.rule == "combiner-enqueue-unsafe"]
    assert findings == []


def test_ack_before_replicate_ungated_ack_flagged():
    src = (
        "class Tier:\n"
        "    def __init__(self, replicator=None):\n"
        "        self.replicator = replicator\n"
        "    def tick(self, futs):\n"
        "        for fut in futs:\n"
        "            fut.set_result('acked')\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "ack-before-replicate"]
    assert len(findings) == 1
    assert findings[0].line == 6
    assert "write-concern barrier" in findings[0].message


def test_ack_before_replicate_ack_before_barrier_flagged():
    # barrier is consulted, but only AFTER the ack already resolved
    src = (
        "class Tier:\n"
        "    def __init__(self, replicator=None):\n"
        "        self.replicator = replicator\n"
        "    def tick(self, fut):\n"
        "        fut.set_result('acked')\n"
        "        ok, why = self.replicator.barrier()\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "ack-before-replicate" in rules


def test_ack_before_replicate_barrier_first_clean():
    src = (
        "class Tier:\n"
        "    def __init__(self, replicator=None):\n"
        "        self.replicator = replicator\n"
        "    def tick(self, fut):\n"
        "        rep = self.replicator\n"
        "        if rep is not None:\n"
        "            ok, why = rep.barrier()\n"
        "        fut.set_result('acked')\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "ack-before-replicate" not in rules


def test_ack_before_replicate_ignores_replicatorless_classes():
    # a future-resolving class with no replicator carries no write-
    # concern contract — nothing to gate
    src = (
        "class Combiner:\n"
        "    def __init__(self):\n"
        "        self._q = []\n"
        "    def flush(self, fut):\n"
        "        fut.set_result(len(self._q))\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "ack-before-replicate" not in rules


def test_scale_fence_missing_epoch_check_flagged():
    src = (
        "class Scaler:\n"
        "    def __init__(self, fed):\n"
        "        self.fed = fed\n"
        "        self._inflight = None\n"
        "    def act(self, dec):\n"
        "        if self._inflight is not None:\n"
        "            return False\n"
        "        self.fed.split_hot(src=dec['src'])\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "scale-decision-unfenced"]
    assert len(findings) == 1
    assert findings[0].line == 8
    assert "table-epoch fence" in findings[0].message


def test_scale_fence_missing_inflight_guard_flagged():
    src = (
        "class Scaler:\n"
        "    def __init__(self, fed):\n"
        "        self.fed = fed\n"
        "    def act(self, dec):\n"
        "        if self.fed.table.epoch != dec['epoch']:\n"
        "            return False\n"
        "        self.fed.merge_cold(src=dec['src'])\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "scale-decision-unfenced"]
    assert len(findings) == 1
    assert "in-flight guard" in findings[0].message


def test_scale_fence_both_fences_first_clean():
    src = (
        "class Scaler:\n"
        "    def __init__(self, fed):\n"
        "        self.fed = fed\n"
        "        self._inflight = None\n"
        "    def act(self, dec):\n"
        "        if self._inflight is not None:\n"
        "            return False\n"
        "        if self.fed.table.epoch != dec['epoch']:\n"
        "            return False\n"
        "        self._inflight = dec['action']\n"
        "        self.fed.merge_cold(src=dec['src'])\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "scale-decision-unfenced" not in rules


def test_scale_fence_ignores_fedless_classes():
    # a test harness poking split_hot directly owns no federation
    # handle — no controller contract to enforce
    src = (
        "class Driver:\n"
        "    def __init__(self):\n"
        "        self.runs = 0\n"
        "    def kick(self, fed):\n"
        "        fed.split_hot(src=0)\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "scale-decision-unfenced" not in rules


def test_shipped_tree_lints_clean():
    from crdt_tpu.analysis.host_lint import lint_package
    import crdt_tpu
    pkg_root = os.path.dirname(os.path.abspath(crdt_tpu.__file__))
    findings = lint_package(pkg_root)
    assert findings == [], "\n".join(f.format() for f in findings)


# -------------------------------------------------------------- law search


def test_broken_mean_join_fixture_fails_all_three_laws():
    from crdt_tpu.analysis.lattice_laws import run_laws
    from tests.fixtures.broken_merge import LAW_TARGETS
    findings = run_laws(LAW_TARGETS, seeds=(0, 1, 2))
    rules = {f.rule for f in findings}
    assert rules == {"law-idempotence", "law-commutativity",
                     "law-associativity"}
    # every counterexample must carry the reproducible input
    for f in findings:
        assert "violating input (seed=" in (f.detail or "")


def test_builtin_law_targets_hold():
    from crdt_tpu.analysis.lattice_laws import builtin_targets, run_laws
    findings = run_laws(builtin_targets(), seeds=(0,))
    assert findings == [], "\n".join(f.format() for f in findings)


# ------------------------------------------------------------- jaxpr audit


def test_jaxpr_audit_builtin_targets_clean():
    from crdt_tpu.analysis.jaxpr_audit import audit_all, builtin_targets
    reports, findings = audit_all(builtin_targets())
    assert findings == [], "\n".join(f.format() for f in findings)
    assert len(reports) >= 11


def test_pallas_fanin_block_matches_golden():
    from crdt_tpu.analysis.jaxpr_audit import audit_all, builtin_targets
    targets = [t for t in builtin_targets()
               if t.name == "parallel.pallas_fanin_block[per-shard]"]
    assert targets, "per-shard Pallas fan-in audit target missing"
    reports, findings = audit_all(targets)
    assert findings == []
    with open(os.path.join(REPO, "tests", "goldens",
                           "fanin_pallas_audit.json")) as fh:
        golden = json.load(fh)
    assert reports[0].golden() == golden


# --------------------------------------------------------------- sanitizer


def test_sanitizer_enabled_reads_env_live(monkeypatch):
    monkeypatch.delenv("CRDT_TPU_SANITIZE", raising=False)
    assert not sanitizer.enabled()
    monkeypatch.setenv("CRDT_TPU_SANITIZE", "0")
    assert not sanitizer.enabled()
    monkeypatch.setenv("CRDT_TPU_SANITIZE", "1")
    assert sanitizer.enabled()


def test_sanitizer_sparse_join_accepts_dominating_store():
    store = types.SimpleNamespace(
        lt=np.array([10, 20, 30], np.int64),
        node=np.array([2, 1, 3], np.int32))
    sanitizer.check_dense_sparse_join(
        store, slots=np.array([0, 2]), lt=np.array([10, 5]),
        node=np.array([1, 9]))


def test_sanitizer_sparse_join_raises_on_lost_update():
    store = types.SimpleNamespace(
        lt=np.array([10, 20], np.int64),
        node=np.array([2, 1], np.int32))
    with pytest.raises(sanitizer.LatticeViolation, match="slot 1"):
        sanitizer.check_dense_sparse_join(
            store, slots=np.array([0, 1]), lt=np.array([10, 20]),
            node=np.array([1, 4]))


def test_sanitizer_dense_join_raises_on_dropped_row():
    store = types.SimpleNamespace(
        lt=np.array([5, 5], np.int64), node=np.array([0, 0], np.int32))
    cs = types.SimpleNamespace(
        lt=np.array([[5, 9]], np.int64),
        node=np.array([[0, 1]], np.int32),
        valid=np.array([[True, True]]))
    with pytest.raises(sanitizer.LatticeViolation, match="slot 1"):
        sanitizer.check_dense_join(store, cs)


def test_sanitizer_catches_merge_that_drops_writes(monkeypatch):
    """End-to-end: a scalar CRDT whose merge silently drops remote
    winners trips check_scalar_join under CRDT_TPU_SANITIZE=1."""
    monkeypatch.setenv("CRDT_TPU_SANITIZE", "1")
    from crdt_tpu.models.map_crdt import MapCrdt
    a = MapCrdt("a")
    b = MapCrdt("b")
    b.put("k", 1)
    payload = b.record_map()
    # sanity: an honest merge passes with the sanitizer armed
    honest = MapCrdt("c")
    honest.merge(dict(payload))
    # now drop the winner write on its way to storage
    monkeypatch.setattr(a, "put_records", lambda record_map: None)
    with pytest.raises(sanitizer.LatticeViolation):
        a.merge(payload)


# --------------------------------------------------------------------- CLI


def test_cli_json_clean_on_shipped_tree():
    proc = _run_cli("--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    assert payload["findings"] == []
    names = {r["target"] for r in payload["jaxpr_reports"]}
    assert "parallel.pallas_fanin_block[per-shard]" in names
    # The fast-path completeness gate's required kernels are present
    # (their absence would have failed the run above).
    assert "dense.merge_repack_step" in names
    assert "pallas.ingest_scatter_planes[interpret]" in names


def test_fastpath_completeness_gate_fails_on_missing_kernel():
    from crdt_tpu.analysis.cli import _fastpath_completeness
    findings = _fastpath_completeness(
        ["dense.merge_repack_step",
         "parallel.collective_join[member2]"])
    assert [f.rule for f in findings] == ["fastpath-kernel-unregistered"]
    assert "ingest_scatter_planes" in findings[0].message
    assert _fastpath_completeness(
        ["dense.merge_repack_step",
         "pallas.ingest_scatter_planes[interpret]",
         "parallel.collective_join[member2]"]) == []


def test_fastpath_completeness_requires_collective_on_multidevice():
    # The collective-join audit target only exists on >= 2 devices
    # (the shard_map needs a member mesh); under the 8-virtual-device
    # test platform its absence must be a finding like any other
    # required kernel's.
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("single-device host: requirement is exempt")
    from crdt_tpu.analysis.cli import _fastpath_completeness
    findings = _fastpath_completeness(
        ["dense.merge_repack_step",
         "pallas.ingest_scatter_planes[interpret]"])
    assert [f.rule for f in findings] == ["fastpath-kernel-unregistered"]
    assert "collective_join" in findings[0].message


def test_ledger_completeness_gate_fails_on_missing_kernel():
    from crdt_tpu.analysis.cli import (_LEDGER_REQUIRED,
                                       _ledger_completeness)
    missing = set(_LEDGER_REQUIRED) - {"dense.merge_repack_step"}
    findings = _ledger_completeness(registered=missing)
    assert [f.rule for f in findings] == ["dispatch-ledger-unregistered"]
    assert "dense.merge_repack_step" in findings[0].message
    # an unregistered extra never trips the gate; the full set is clean
    assert _ledger_completeness(
        registered=set(_LEDGER_REQUIRED) | {"extra.kernel"}) == []


def test_ledger_completeness_gate_clean_on_shipped_tree():
    # no `registered=`: the gate imports the instrumented modules and
    # reads the live default ledger — exactly what the default run does
    from crdt_tpu.analysis.cli import _ledger_completeness
    assert _ledger_completeness() == []


def test_cli_nonzero_with_counterexample_on_broken_fixture():
    proc = _run_cli("--law-fixture",
                    os.path.join(FIXTURES, "broken_merge.py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "law-idempotence" in proc.stdout
    assert "violating input (seed=" in proc.stdout


def test_cli_nonzero_on_racy_fixture():
    proc = _run_cli("--lint", os.path.join(FIXTURES, "racy_gossip.py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "lock-discipline" in proc.stdout
    assert "socket-no-timeout" in proc.stdout


# ---------------------------------------------------- concurrency analyzer


def test_deadlock_cycle_fixture_exact_findings():
    from crdt_tpu.analysis.concurrency import analyze_paths
    findings = analyze_paths(
        [os.path.join(FIXTURES, "deadlock_cycle.py")])
    assert [f.rule for f in findings] == [
        "lock-order-cycle", "lock-order-undeclared"], findings
    cycle, undeclared = findings
    # the cycle is pinned at the offending (inverted) acquisition and
    # the witness path walks through the helper the edge hides in
    assert "PairStore._a" in cycle.message
    assert "PairStore._b" in cycle.message
    assert "_grab_a" in cycle.detail
    assert "Indexer._idx" in undeclared.message
    assert "Journal._j" in undeclared.message


def test_blocking_hold_fixture_exact_findings():
    from crdt_tpu.analysis.concurrency import analyze_paths
    findings = analyze_paths(
        [os.path.join(FIXTURES, "blocking_hold.py")])
    assert [f.rule for f in findings] == [
        "blocking-under-lock", "blocking-under-lock"], findings
    socket_f, sleep_f = findings
    assert "sendall" in socket_f.message
    assert "Shipper._lock" in socket_f.message
    assert "time.sleep" in sleep_f.message
    # the sleep lives in a helper: interprocedural witness required
    assert "_backoff" in sleep_f.detail


def test_cli_nonzero_on_deadlock_fixture():
    proc = _run_cli("--lint",
                    os.path.join(FIXTURES, "deadlock_cycle.py"),
                    "--json")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert [f["rule"] for f in payload["findings"]] == [
        "lock-order-cycle", "lock-order-undeclared"]


def test_cli_nonzero_on_blocking_hold_fixture():
    proc = _run_cli("--lint",
                    os.path.join(FIXTURES, "blocking_hold.py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert proc.stdout.count("blocking-under-lock") >= 2


def test_shipped_tree_concurrency_clean():
    from crdt_tpu.analysis.concurrency import analyze_package
    import crdt_tpu
    pkg_root = os.path.dirname(os.path.abspath(crdt_tpu.__file__))
    assert analyze_package(pkg_root) == []


def test_concurrency_suppression_honored():
    from crdt_tpu.analysis.concurrency import analyze_source
    src = (
        "import threading, time\n"
        "class C:\n"
        "    _CRDTLINT_LOCK_ORDER = ('_l',)\n"
        "    def f(self):\n"
        "        with self._l:\n"
        "            # crdtlint: disable=blocking-under-lock -- bounded\n"
        "            time.sleep(0.01)\n")
    assert analyze_source(src, "c.py") == []
    # without the comment the finding is real
    assert [f.rule for f in analyze_source(
        src.replace("            # crdtlint: disable="
                    "blocking-under-lock -- bounded\n", ""),
        "c.py")] == ["blocking-under-lock"]


def test_contract_only_cycle_reported_at_declaration():
    from crdt_tpu.analysis.concurrency import analyze_source
    # two contracts that admit a cycle with no witnessing site
    src = (
        "class A:\n"
        "    _CRDTLINT_LOCK_ORDER = ('_x', ('peer_y', 'B._y'))\n"
        "class B:\n"
        "    _CRDTLINT_LOCK_ORDER = ('_y', ('peer_x', 'A._x'))\n")
    findings = analyze_source(src, "c.py")
    assert [f.rule for f in findings] == ["lock-order-cycle"]
    assert "mutually inconsistent" in findings[0].message


def test_acquire_call_counts_as_hold():
    from crdt_tpu.analysis.concurrency import analyze_source
    src = (
        "import threading, time\n"
        "class C:\n"
        "    _CRDTLINT_LOCK_ORDER = ('_l',)\n"
        "    def f(self):\n"
        "        self._l.acquire()\n"
        "        try:\n"
        "            time.sleep(0.01)\n"
        "        finally:\n"
        "            self._l.release()\n")
    assert [f.rule for f in analyze_source(src, "c.py")] == [
        "blocking-under-lock"]


def test_async_with_is_not_a_thread_lock_acquisition():
    from crdt_tpu.analysis.concurrency import analyze_source
    src = (
        "class C:\n"
        "    _CRDTLINT_LOCK_ORDER = ('_l',)\n"
        "    async def f(self):\n"
        "        async with self._l:\n"
        "            import time\n"
        "            time.sleep(0.01)\n")
    # the asyncio lock orders the event loop, not threads — the
    # concurrency pass must not treat it as a held thread lock
    assert analyze_source(src, "c.py") == []


def test_thread_unnamed_flagged_and_named_clean():
    flagged = lint_source(
        "import threading\n"
        "t = threading.Thread(target=f, daemon=True)\n", "t.py")
    assert [f.rule for f in flagged] == ["thread-unnamed"]
    named = lint_source(
        "import threading\n"
        "t = threading.Thread(target=f, daemon=True, name='worker')\n",
        "t.py")
    assert named == []


def test_async_sync_with_contract_lock_flagged():
    src = (
        "class C:\n"
        "    _CRDTLINT_LOCK_ORDER = ('_l',)\n"
        "    async def f(self):\n"
        "        with self._l:\n"
        "            return 1\n")
    findings = lint_source(src, "c.py")
    assert [f.rule for f in findings] == ["async-blocking-call"]
    assert "_l" in findings[0].message
    # a non-contract with block stays exempt (ordinary context
    # managers are not locks) ...
    assert lint_source(src.replace("('_l',)", "()"), "c.py") == []
    # ... and so does `async with` on the same attribute
    assert lint_source(src.replace("with self._l:",
                                   "pass\n"
                                   "    async def g(self):\n"
                                   "        async with self._l:"),
                       "c.py") == []


# ---------------------------------------------------- runtime lock sanitizer


def test_make_lock_is_plain_lock_when_disabled(monkeypatch):
    import threading
    monkeypatch.delenv("CRDT_TPU_SANITIZE", raising=False)
    from crdt_tpu.analysis.concurrency import OrderedLock, make_lock
    plain = make_lock("T.l", 10)
    assert isinstance(plain, type(threading.Lock()))
    reentrant = make_lock("T.r", 10, rlock=True)
    assert not isinstance(reentrant, OrderedLock)
    with reentrant:
        with reentrant:  # RLock semantics preserved
            pass


def test_runtime_sanitizer_catches_inversion_without_hang(monkeypatch):
    import threading
    monkeypatch.setenv("CRDT_TPU_SANITIZE", "1")
    from crdt_tpu.analysis.concurrency import OrderedLock, make_lock
    from crdt_tpu.obs.registry import default_registry
    from crdt_tpu.obs.trace import tracer

    a = make_lock("InvA.a", 10)
    b = make_lock("InvB.b", 20)
    assert isinstance(a, OrderedLock)

    ring = tracer()
    was_enabled = ring.enabled
    ring.enabled = True
    try:
        ok = threading.Event()

        def conforming():
            with a:
                with b:
                    ok.set()

        t1 = threading.Thread(target=conforming, name="inv-good")
        t1.start()
        t1.join(timeout=10)
        assert ok.is_set() and not t1.is_alive()

        def inverted():
            with b:
                with a:   # rank 10 while holding rank 20
                    pass

        t2 = threading.Thread(target=inverted, name="inv-bad")
        t2.start()
        t2.join(timeout=10)
        # the sanitizer reports, it never blocks differently — the
        # inverted thread must COMPLETE
        assert not t2.is_alive()

        counter = default_registry().counter(
            "crdt_tpu_lock_order_violations_total")
        assert counter.value(held="InvB.b", acquiring="InvA.a") == 1
        # the conforming order produced no count
        assert counter.value(held="InvA.a", acquiring="InvB.b") == 0

        events = [e for e in ring.events()
                  if e.get("kind") == "lock_order_violation"]
        assert events, "no trace event emitted"
        assert events[-1]["held"] == "InvB.b"
        assert events[-1]["acquiring"] == "InvA.a"
        assert events[-1]["thread"] == "inv-bad"
    finally:
        ring.enabled = was_enabled


def test_ordered_lock_rlock_reentry_is_not_a_violation(monkeypatch):
    monkeypatch.setenv("CRDT_TPU_SANITIZE", "1")
    from crdt_tpu.analysis.concurrency import OrderedLock, make_lock
    from crdt_tpu.obs.registry import default_registry

    r = make_lock("Reent.r", 30, rlock=True)
    assert isinstance(r, OrderedLock)
    with r:
        with r:
            pass
    counter = default_registry().counter(
        "crdt_tpu_lock_order_violations_total")
    assert counter.value(held="Reent.r", acquiring="Reent.r") == 0


# --- histogram-ceiling-gate (PR 18) ---

def test_histogram_ceiling_gate_direct_compare_flagged():
    src = (
        "def decide(snap, budget_s):\n"
        "    if histogram_quantile(snap, 0.99) > budget_s:\n"
        "        split()\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "histogram-ceiling-gate" in rules


def test_histogram_ceiling_gate_taint_through_max_fold_flagged():
    # The realistic controller shape: quantile folded through an
    # assignment and a max() before the gate — the taint must follow.
    src = (
        "def decide(samples, ack_p99_budget_s):\n"
        "    ceil = None\n"
        "    for s in samples:\n"
        "        v = histogram_quantile(s, 0.99)\n"
        "        ceil = v if ceil is None else max(ceil, v)\n"
        "    if ceil is not None and ceil > ack_p99_budget_s:\n"
        "        return 'split'\n")
    findings = [f for f in lint_source(src, "snippet.py")
                if f.rule == "histogram-ceiling-gate"]
    assert findings
    # pinned to the gate line, not the fold
    assert findings[0].line == 6


def test_histogram_ceiling_gate_display_only_not_flagged():
    # Rendering the ceiling (no budget in sight) is fine — ceilings
    # are display-only; so are non-budget compares like the None /
    # inf guards.
    src = (
        "def render(samples):\n"
        "    rows = []\n"
        "    for s in samples:\n"
        "        v = histogram_quantile(s, 0.99)\n"
        "        if v is not None and v != float('inf'):\n"
        "            rows.append(v * 1e3)\n"
        "    return rows\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "histogram-ceiling-gate" not in rules


def test_histogram_ceiling_gate_sketch_gate_not_flagged():
    # The migration target: gating the same budget on the sketch
    # quantile must stay clean even with a ceiling computed alongside
    # for display.
    src = (
        "def decide(snapshots, ack_p99_budget_s):\n"
        "    ceil = histogram_quantile(snapshots[0], 0.99)\n"
        "    sk = fleet_sketch(snapshots)\n"
        "    p99 = sk.quantile(0.99)\n"
        "    show(ceil)\n"
        "    return p99 is not None and p99 <= ack_p99_budget_s\n")
    rules = {f.rule for f in lint_source(src, "snippet.py")}
    assert "histogram-ceiling-gate" not in rules


def test_histogram_ceiling_gate_shipped_fleet_fallback_suppressed():
    # fleet.py's pre-sketch fallback compares the ceiling against the
    # budget on purpose (three-valued: pass / floor-breach / None) —
    # it must stay suppressed with a reason, not exempted silently.
    import crdt_tpu.obs.fleet as fleet
    findings = [f for f in lint_file(fleet.__file__)
                if f.rule in ("histogram-ceiling-gate",
                              "suppression-without-reason")]
    assert findings == []
