"""Jaxpr lattice auditor: order-sensitivity hazards in merge kernels.

Round-5 ADVICE found the motivating bug class shipped: an XLA scatter
with duplicate indices has UNSPECIFIED winner order, so a merge built
on one is backend-dependent — the exact property a CRDT join must not
have. This auditor traces every registered merge/join op to its jaxpr
(recursively, through pjit/scan/while/cond/shard_map/pallas_call) and
flags the hazard classes:

- ``scatter-duplicate-order`` — scatter family primitive with
  ``unique_indices=False``: duplicate indices pick an unspecified
  winner. Targets whose CALL CONTRACT guarantees unique slots (a
  dict-keyed delta cannot repeat a slot) declare ``unique_slots=True``
  and the hazard is downgraded to a recorded *assumption* — it stays
  in the golden report so a contract change is a visible diff, not a
  silent regression.
- ``nonassoc-float-reduce`` — reduction/contraction primitive over a
  floating dtype on the merge path: float addition is not associative,
  so the result depends on reduction order. All CRDT lanes are
  int64/int32/bool; any float reduce appearing here is a bug.
- ``prng-in-merge`` — PRNG primitive inside a merge: a join that draws
  randomness cannot be a function of its inputs, let alone a lattice
  join.
- ``donated-invar`` — donated input buffers recorded per target
  (donation aliases the input; safe only if the caller never touches
  the donated buffer again — the host linter's donated-buffer-reuse
  rule enforces that side).

Everything here is TRACE-ONLY: ``jax.make_jaxpr`` builds the IR
without executing a kernel, so the Pallas targets audit fine on CPU
(interpret mode) and the sharded targets on 8 virtual devices.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Sequence, Tuple

from .findings import Finding

_SCATTER_PRIMS_PREFIX = "scatter"
_REDUCE_PRIMS = {
    "reduce_sum", "reduce_prod", "cumsum", "cumprod", "cumlogsumexp",
    "dot_general", "add_any", "psum", "psum2",
}
_PRNG_PRIMS = {
    "threefry2x32", "rng_bit_generator", "rng_uniform", "random_bits",
    "random_seed", "random_wrap", "random_fold_in", "random_gamma",
}


@dataclass
class AuditTarget:
    """One kernel under audit. ``build()`` returns the ClosedJaxpr —
    it must hand concrete arrays to ``jax.make_jaxpr(fn)(*args)``
    itself (closing over arrays in a zero-arg thunk would execute
    eagerly instead of tracing)."""

    name: str
    build: Callable[[], object]
    unique_slots: bool = False
    notes: str = ""


@dataclass
class AuditReport:
    target: str
    hazards: List[dict] = field(default_factory=list)
    assumptions: List[str] = field(default_factory=list)
    prim_counts: Dict[str, int] = field(default_factory=dict)

    def golden(self) -> dict:
        """The stable subset pinned as a golden: hazards and relied-on
        contracts only — prim counts churn with jax versions."""
        return {"target": self.target, "hazards": self.hazards,
                "assumptions": sorted(self.assumptions)}


def _iter_jaxprs(params: dict):
    """Yield every jaxpr-valued param (pjit/scan 'jaxpr', cond
    'branches', while 'cond_jaxpr'/'body_jaxpr', pallas_call 'jaxpr',
    scatter 'update_jaxpr', ...) — generic, so new higher-order prims
    are walked without a registry."""
    import jax.extend.core as jex_core

    def as_jaxpr(v):
        if isinstance(v, jex_core.ClosedJaxpr):
            return v.jaxpr
        if isinstance(v, jex_core.Jaxpr):
            return v
        return None

    for v in params.values():
        j = as_jaxpr(v)
        if j is not None:
            yield j
        elif isinstance(v, (tuple, list)):
            for item in v:
                j = as_jaxpr(item)
                if j is not None:
                    yield j


def _walk(jaxpr, report: AuditReport, unique_slots: bool) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        report.prim_counts[name] = report.prim_counts.get(name, 0) + 1

        if name.startswith(_SCATTER_PRIMS_PREFIX):
            unique = bool(eqn.params.get("unique_indices", False))
            if not unique:
                if unique_slots:
                    note = (f"{name} with unique_indices=False is "
                            "order-safe only under the unique-slots "
                            "call contract")
                    if note not in report.assumptions:
                        report.assumptions.append(note)
                else:
                    report.hazards.append({
                        "rule": "scatter-duplicate-order",
                        "prim": name,
                        "detail": "unique_indices=False: duplicate "
                                  "indices pick an unspecified "
                                  "(backend-dependent) winner",
                    })

        if name in _REDUCE_PRIMS:
            floaty = any(
                hasattr(v, "aval") and hasattr(v.aval, "dtype")
                and str(v.aval.dtype).startswith(("float", "bfloat"))
                for v in eqn.invars)
            if floaty:
                report.hazards.append({
                    "rule": "nonassoc-float-reduce",
                    "prim": name,
                    "detail": "floating-point reduction on the merge "
                              "path: float addition is not "
                              "associative, result depends on "
                              "reduction order",
                })

        if name in _PRNG_PRIMS or "rng" in name:
            report.hazards.append({
                "rule": "prng-in-merge",
                "prim": name,
                "detail": "PRNG primitive inside a merge kernel: the "
                          "join is not a function of its inputs",
            })

        if name == "pjit":
            donated = eqn.params.get("donated_invars", ())
            if any(donated):
                note = (f"pjit with {sum(map(bool, donated))} donated "
                        "invar(s): input aliased, caller must not "
                        "reuse the donated buffer")
                if note not in report.assumptions:
                    report.assumptions.append(note)

        for sub in _iter_jaxprs(eqn.params):
            _walk(sub, report, unique_slots)


def audit_target(target: AuditTarget) -> AuditReport:
    closed = target.build()
    report = AuditReport(target=target.name)
    _walk(closed.jaxpr, report, target.unique_slots)
    return report


def audit_all(targets: Sequence[AuditTarget]
              ) -> Tuple[List[AuditReport], List[Finding]]:
    reports: List[AuditReport] = []
    findings: List[Finding] = []
    for target in targets:
        try:
            report = audit_target(target)
        except Exception as e:  # a target that fails to trace at all
            findings.append(Finding(
                rule="jaxpr-trace-error", path=f"<jaxpr:{target.name}>",
                line=0,
                message=f"target failed to trace: {type(e).__name__}",
                detail=str(e)))
            continue
        reports.append(report)
        for hz in report.hazards:
            findings.append(Finding(
                rule=hz["rule"], path=f"<jaxpr:{target.name}>", line=0,
                message=f"{hz['prim']}: {hz['detail']}"))
    return reports, findings


# --- builtin targets over the registered kernels ---

_N = 64      # store width for scalar/dense targets
_M = 8       # changeset rows


def builtin_targets(include_sharded: bool = True) -> List[AuditTarget]:
    """Audit targets over every registered merge/join op. Jax imports
    are local so the host linter can run without jax. Sharded targets
    require 8 devices (tests/conftest.py and the CLI __main__ force 8
    virtual CPU devices) and are skipped — with a report note — when
    fewer are available."""
    import jax
    import numpy as np
    from ..ops import dense as dense_ops
    from ..ops import merge as merge_ops

    i64 = lambda *s: np.zeros(s, np.int64)
    i32 = lambda *s: np.zeros(s, np.int32)
    b8 = lambda *s: np.zeros(s, bool)

    targets: List[AuditTarget] = []

    def scalar_store():
        return merge_ops.Store(lt=i64(_N), node=i32(_N), mod_lt=i64(_N),
                               mod_node=i32(_N), occupied=b8(_N),
                               tomb=b8(_N))

    def scalar_cs():
        return merge_ops.Changeset(slot=i32(_M), lt=i64(_M),
                                   node=i32(_M), tomb=b8(_M),
                                   valid=b8(_M))

    targets.append(AuditTarget(
        name="merge.merge_step", unique_slots=True,
        notes="host key->slot dict cannot repeat a slot",
        build=lambda: jax.make_jaxpr(merge_ops.merge_step)(
            scalar_store(), scalar_cs(), np.int64(0), np.int32(0),
            np.int64(0))))

    targets.append(AuditTarget(
        name="merge.scatter_put", unique_slots=True,
        notes="host key->slot dict cannot repeat a slot",
        build=lambda: jax.make_jaxpr(merge_ops.scatter_put)(
            scalar_store(), scalar_cs(), i64(_M), i32(_M))))

    def dense_store():
        return dense_ops.DenseStore(lt=i64(_N), node=i32(_N),
                                    val=i64(_N), mod_lt=i64(_N),
                                    mod_node=i32(_N), occupied=b8(_N),
                                    tomb=b8(_N))

    def dense_cs(rows=_M):
        return dense_ops.DenseChangeset(lt=i64(rows, _N),
                                        node=i32(rows, _N),
                                        val=i64(rows, _N),
                                        tomb=b8(rows, _N),
                                        valid=b8(rows, _N))

    targets.append(AuditTarget(
        name="dense.fanin_step",
        notes="elementwise fold; no scatter at all",
        build=lambda: jax.make_jaxpr(dense_ops.fanin_step)(
            dense_store(), dense_cs(), np.int64(0), np.int32(0),
            np.int64(0))))

    targets.append(AuditTarget(
        name="dense.fanin_stream",
        notes="lax.scan over chunked changesets; walked into the body",
        build=lambda: jax.make_jaxpr(dense_ops.fanin_stream)(
            dense_store(),
            dense_ops.DenseChangeset(lt=i64(2, _M, _N),
                                     node=i32(2, _M, _N),
                                     val=i64(2, _M, _N),
                                     tomb=b8(2, _M, _N),
                                     valid=b8(2, _M, _N)),
            np.int64(0), np.int32(0), np.int64(0))))

    targets.append(AuditTarget(
        name="dense.sparse_fanin_step", unique_slots=True,
        notes="dict-keyed delta cannot repeat a slot",
        build=lambda: jax.make_jaxpr(dense_ops.sparse_fanin_step)(
            dense_store(), i64(_M), i64(_M), i32(_M), i64(_M), b8(_M),
            b8(_M), np.int64(0), np.int32(0))))

    targets.append(AuditTarget(
        name="dense.wire_join_step",
        notes="elementwise slot-aligned join; no gather, no scatter",
        build=lambda: jax.make_jaxpr(dense_ops.wire_join_step)(
            dense_store(), i64(_N), i32(_N), i64(_N), b8(_N), b8(_N),
            np.int64(0), np.int32(0))))

    targets.append(AuditTarget(
        name="dense.put_scatter", unique_slots=True,
        notes="dict-keyed batch cannot repeat a slot; donate=False "
              "variant audited (donation is a host-linter concern)",
        build=lambda: jax.make_jaxpr(dense_ops._put_scatter(False))(
            dense_store(), i64(_M), i64(_M), b8(_M), np.int64(0),
            np.int32(0))))

    targets.append(AuditTarget(
        name="dense.record_scatter", unique_slots=True,
        notes="dict-keyed batch cannot repeat a slot",
        build=lambda: jax.make_jaxpr(dense_ops._record_scatter(False))(
            dense_store(), i64(_M), i64(_M), i32(_M), i64(_M), i64(_M),
            i32(_M), b8(_M))))

    targets.append(AuditTarget(
        name="dense.delete_scatter", unique_slots=True,
        notes="dict-keyed batch cannot repeat a slot",
        build=lambda: jax.make_jaxpr(dense_ops._delete_scatter(False))(
            dense_store(), i64(_M), np.int64(0), np.int32(0))))

    targets.append(AuditTarget(
        name="dense.merge_repack_step", unique_slots=True,
        notes="fused gossip-relay join + next-pack delta mask in one "
              "program; dict-keyed delta cannot repeat a slot",
        build=lambda: jax.make_jaxpr(dense_ops._merge_repack_jit(False))(
            dense_store(), i64(_M), i64(_M), i32(_M), i64(_M), b8(_M),
            b8(_M), np.int64(0), np.int32(0), np.int64(0))))

    # Merkle anti-entropy kernels (ops/digest.py, docs/ANTIENTROPY.md):
    # read-only reductions/masks over the store — no scatter, no lane
    # mutation — but registered so the CLI completeness gate proves
    # the hot anti-entropy path stays on device.
    from ..ops import digest as digest_ops

    targets.append(AuditTarget(
        name="digest.digest_tree_levels",
        notes="on-device segment-tree digest: per-slot mix + leaf "
              "fold + every interior combine in one program; "
              "read-only over the lanes",
        build=lambda: jax.make_jaxpr(digest_ops._digest_tree_jit(
            8, False))(i64(_N), i64(_N), b8(_N), b8(_N))))

    targets.append(AuditTarget(
        name="dense.range_delta_mask",
        notes="slot-span-restricted delta mask feeding "
              "pack_since(ranges=...); elementwise, no scatter",
        build=lambda: jax.make_jaxpr(dense_ops._range_mask_jit())(
            dense_store(), np.int64(0), i64(2), i64(2))))

    # Storage-plane kernels (docs/STORAGE.md): physically destructive
    # by design, so audit coverage is gated by the CLI
    # (_GC_REQUIRED) — an order hazard here destroys state no merge
    # can repair.
    targets.append(AuditTarget(
        name="dense.gc_purge",
        notes="epoch tombstone purge: elementwise lane masking under "
              "one stability-floor predicate — no gather, no "
              "scatter, order-insensitive by shape",
        build=lambda: jax.make_jaxpr(dense_ops._gc_purge_jit(False))(
            dense_store(), np.int64(0))))

    targets.append(AuditTarget(
        name="dense.compact_remap", unique_slots=True,
        notes="slot remap to span-dense prefixes: scatter targets are "
              "a masked per-span survivor-rank cumsum, bijective over "
              "occupied rows by construction (spans are host-validated "
              "non-overlapping, so each slot lands in at most one)",
        build=lambda: jax.make_jaxpr(
            dense_ops._compact_remap_jit(False, 8, False))(
            dense_store(), i64(2), i64(2))))

    # Typed lane kernels (crdt_tpu/semantics): the shared sparse
    # scatter and fan-in shapes here, plus one per-tag elementwise
    # wire-join target per registered semantics from the registry
    # itself — a new type gets audit coverage by registering.
    from ..semantics import audit_targets as _semantics_audit_targets
    from ..semantics import kernels as _sem_kernels

    i8 = lambda *s: np.zeros(s, np.int8)

    targets.append(AuditTarget(
        name="semantics.typed_sparse_join_step", unique_slots=True,
        notes="dict-keyed delta cannot repeat a slot; gather "
              "mode=fill + scatter mode=drop over typed rows",
        build=lambda: jax.make_jaxpr(
            _sem_kernels.typed_sparse_join_step)(
            dense_store(), i8(_M), i32(_M), i64(_M), i32(_M),
            i64(_M), b8(_M), b8(_M), np.int64(0), np.int32(0))))

    targets.append(AuditTarget(
        name="semantics.typed_fanin_step",
        notes="elementwise typed fold; no scatter at all",
        build=lambda: jax.make_jaxpr(_sem_kernels.typed_fanin_step)(
            dense_store(), i8(_N), dense_cs(), np.int64(0),
            np.int32(0), np.int64(0))))

    targets.extend(_semantics_audit_targets())

    targets.append(AuditTarget(
        name="pallas.pallas_fanin_step[interpret]",
        notes="Mosaic fan-in kernel at N=TILE, traced in interpret "
              "mode; walked into the pallas_call jaxpr",
        build=_build_pallas_step))

    targets.append(AuditTarget(
        name="pallas.ingest_scatter_planes[interpret]",
        unique_slots=True,
        notes="touched-tile ingest commit kernel on the resident 2-D "
              "planes (ops/pallas_scatter _planes_step), aliased in "
              "place; combiner dedups slots before host prep; "
              "interpret mode, trace-only",
        build=_build_pallas_planes_scatter))

    # The per-shard body of parallel/fanin.py's _pallas_fanin_block
    # (split -> pallas_fanin_batch -> join) audited at the per-device
    # shard shape. This is the golden-pinned target: it traces on one
    # device, whereas the full shard_map steps below need a mesh.
    targets.append(AuditTarget(
        name="parallel.pallas_fanin_block[per-shard]",
        notes="parallel/fanin.py _pallas_fanin_block per-device body: "
              "split_store -> pallas_fanin_batch(chunk_rows=8) -> "
              "join_store at one key shard (N=TILE, R=16), interpret "
              "mode, trace-only",
        build=_build_pallas_block_per_shard))

    if include_sharded and len(jax.devices()) >= 8:
        targets.append(AuditTarget(
            name="parallel.sharded_fanin[mesh2x4]",
            notes="shard_map + psum/pmax collective fan-in block",
            build=_build_sharded_fanin))
        targets.append(AuditTarget(
            name="parallel.sharded_pallas_fanin[mesh2x4]",
            notes="per-shard Mosaic batch kernel inside the "
                  "collective step (parallel/fanin.py "
                  "_pallas_fanin_block); trace-only",
            build=_build_sharded_pallas_fanin))

    # The pod-local collective join only needs a 2-member mesh, so it
    # audits on any multi-device (or virtual-device) host even where
    # the 8-device fan-in meshes cannot build.
    if include_sharded and len(jax.devices()) >= 2:
        targets.append(AuditTarget(
            name="parallel.collective_join[member2]",
            notes="pod-local group anti-entropy: shard_map lex-max "
                  "clock join + typed-semantics collectives "
                  "(gcounter/pncounter/orset pmax, mvreg "
                  "all_gather union) + in-program digest leaves "
                  "(parallel/collective.py, docs/COLLECTIVE.md)",
            build=_build_collective_join))

    return targets


def _build_pallas_step():
    import jax
    import numpy as np
    from ..ops import pallas_merge as pm
    from ..ops.dense import empty_dense_store, DenseChangeset

    n = pm.TILE
    store = pm.split_store(empty_dense_store(n))
    cs = pm.split_changeset(DenseChangeset(
        lt=np.zeros((2, n), np.int64), node=np.zeros((2, n), np.int32),
        val=np.zeros((2, n), np.int64), tomb=np.zeros((2, n), bool),
        valid=np.zeros((2, n), bool)))

    def step(store, cs, canon, local_node, wall):
        return pm.pallas_fanin_step(store, cs, canon, local_node, wall,
                                    interpret=True)

    return jax.make_jaxpr(step)(store, cs, np.int64(0), np.int32(0),
                                np.int64(0))


def _build_pallas_planes_scatter():
    import jax
    import numpy as np
    from ..ops import pallas_scatter as ps

    planes = ps.empty_planes(ps.TILE)  # one touched tile
    tile_ids = np.zeros((1,), np.int32)
    valid = np.zeros((ps._SB, ps._LANE), np.int32)
    lt_d = np.zeros((ps._SB, ps._LANE), np.int64)
    val_d = np.zeros((ps._SB, ps._LANE), np.int64)
    tomb_d = np.zeros((ps._SB, ps._LANE), np.int32)
    me = np.zeros((1,), np.int32)
    return jax.make_jaxpr(ps._planes_jit(False, True))(
        planes, tile_ids, valid, lt_d, val_d, tomb_d, me)


def _build_pallas_block_per_shard():
    # Mirrors parallel/fanin.py _pallas_fanin_block's per-device body
    # (the compute between the collectives): split -> batch kernel ->
    # join, at one key shard. Collectives (pmax/pmin/psum) only trace
    # inside shard_map, so they are exercised by the sharded targets
    # when `crdt_tpu.parallel` imports; the lattice-hazard surface
    # (scatters, reductions, RNG) lives entirely in this body.
    import jax
    import numpy as np
    from ..ops import pallas_merge as pm
    from ..ops.dense import empty_dense_store, DenseChangeset

    n = pm.TILE
    r = 16

    def _unwrap(fn):
        return getattr(fn, "__wrapped__", fn)

    store = _unwrap(pm.split_store)(empty_dense_store(n))
    cs = _unwrap(pm.split_changeset)(DenseChangeset(
        lt=np.zeros((r, n), np.int64), node=np.zeros((r, n), np.int32),
        val=np.zeros((r, n), np.int64), tomb=np.zeros((r, n), bool),
        valid=np.zeros((r, n), bool)))

    def block(store, cs, canon, local_node, wall):
        out, res = _unwrap(pm.pallas_fanin_batch)(
            store, cs, canon, local_node, wall,
            chunk_rows=8, interpret=True)
        return _unwrap(pm.join_store)(out), res

    return jax.make_jaxpr(block)(store, cs, np.int64(0), np.int32(0),
                                 np.int64(0))


def _sharded_args(n_per_shard: int):
    import numpy as np
    from ..parallel import fanin as pf
    from ..ops.dense import DenseStore, DenseChangeset

    mesh = pf.make_fanin_mesh(2, 4)
    r = pf.replica_extent(mesh) * 8
    n = 4 * n_per_shard
    store = DenseStore(lt=np.zeros(n, np.int64),
                       node=np.zeros(n, np.int32),
                       val=np.zeros(n, np.int64),
                       mod_lt=np.zeros(n, np.int64),
                       mod_node=np.zeros(n, np.int32),
                       occupied=np.zeros(n, bool),
                       tomb=np.zeros(n, bool))
    cs = DenseChangeset(lt=np.zeros((r, n), np.int64),
                        node=np.zeros((r, n), np.int32),
                        val=np.zeros((r, n), np.int64),
                        tomb=np.zeros((r, n), bool),
                        valid=np.zeros((r, n), bool))
    return mesh, store, cs


def _build_collective_join():
    import jax
    import numpy as np
    from ..ops.dense import DenseStore
    from ..parallel import collective as pc

    mesh = pc.make_collective_mesh(2)

    # The program's own input: both members' lanes, [2*N] over the
    # member axis.
    group = DenseStore(lt=np.zeros(2 * _N, np.int64),
                       node=np.zeros(2 * _N, np.int32),
                       val=np.zeros(2 * _N, np.int64),
                       mod_lt=np.zeros(2 * _N, np.int64),
                       mod_node=np.zeros(2 * _N, np.int32),
                       occupied=np.zeros(2 * _N, bool),
                       tomb=np.zeros(2 * _N, bool))

    # has_sem=True so the audit walks every typed join branch (the
    # untyped program is a strict subset). Trace the jitted program
    # itself — the host wrapper only places lanes and adds ledger
    # accounting.
    step = pc.make_collective_join(mesh, True, 8, donate=False)
    return jax.make_jaxpr(step.jitted)(
        group, np.zeros(_N, np.int8),
        np.zeros(2, np.int64), np.zeros(2, np.int32), np.int64(0))


def _build_sharded_fanin():
    import jax
    import numpy as np
    from ..parallel import fanin as pf

    mesh, store, cs = _sharded_args(2)
    step = pf.make_sharded_fanin(mesh)
    return jax.make_jaxpr(step)(store, cs, np.int64(0), np.int32(0),
                                np.int64(0))


def _build_sharded_pallas_fanin():
    import jax
    import numpy as np
    from ..ops.pallas_merge import TILE
    from ..parallel import fanin as pf

    mesh, store, cs = _sharded_args(TILE)
    step = pf.make_sharded_pallas_fanin(mesh, chunk_rows=8,
                                        interpret=True)
    return jax.make_jaxpr(step)(store, cs, np.int64(0), np.int32(0),
                                np.int64(0))
