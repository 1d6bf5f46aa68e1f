"""crdtlint CLI: ``python -m crdt_tpu.analysis``.

Default run (no explicit targets) audits the shipped tree — the CI
gate: host-lint every package file, run the semilattice law search
over the registered kernels, and audit every merge jaxpr for
order-sensitivity hazards. Exit 0 means no findings.

Explicit targets (``--lint PATH``, ``--law-fixture PATH``) run ONLY
what was named — how the self-test fixtures are exercised::

    python -m crdt_tpu.analysis --lint tests/fixtures/racy_gossip.py
    python -m crdt_tpu.analysis --law-fixture tests/fixtures/broken_merge.py

A law fixture is a Python file exposing ``LAW_TARGETS`` (a list of
``analysis.lattice_laws.LawTarget``); on a law violation the CLI
prints the violating input (seed + batches) and exits nonzero.

``--json`` emits machine-readable output; its ``jaxpr_reports`` key
carries each audited kernel's golden report (hazards + relied-on
contracts), which tests pin for the Pallas fan-in path.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import sys
from typing import List


def _load_law_fixture(path: str):
    spec = importlib.util.spec_from_file_location(
        "crdtlint_law_fixture_" + os.path.basename(path).replace(
            ".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    targets = getattr(module, "LAW_TARGETS", None)
    if not targets:
        raise SystemExit(
            f"law fixture {path} defines no LAW_TARGETS list")
    return list(targets)


def _registry_completeness() -> List:
    """The semantics-registry CI gate (docs/TYPES.md): every
    registered lane semantics must declare BOTH a law target and an
    audit target — registering is what puts a type under CI, so a spec
    missing either would ship an unverified kernel. One finding per
    missing factory; the default run fails on them like any other."""
    from .findings import Finding
    from ..semantics import all_semantics
    out = []
    for spec in all_semantics():
        where = f"semantics {spec.name!r} (tag {spec.tag})"
        if spec.law_target is None:
            out.append(Finding(
                rule="semantics-missing-law-target",
                path="crdt_tpu/semantics/types.py", line=0,
                message=f"{where} registers no law target",
                detail="declare law_target so the seeded semilattice "
                       "search covers this kernel (see "
                       "types._typed_law_target)"))
        if spec.audit_target is None:
            out.append(Finding(
                rule="semantics-missing-audit-target",
                path="crdt_tpu/semantics/types.py", line=0,
                message=f"{where} registers no audit target",
                detail="declare audit_target so the jaxpr audit "
                       "covers this kernel (see "
                       "types._typed_audit_target)"))
    return out


# Fast-path kernels the default run must find in the jaxpr-audit
# registry: shipping either without audit coverage would let an
# order-sensitivity hazard into the hottest dispatch (docs/FASTPATH.md).
_FASTPATH_REQUIRED = (
    "dense.merge_repack_step",
    "pallas.ingest_scatter_planes[interpret]",
    "parallel.collective_join[member2]",
)


def _fastpath_completeness(target_names) -> List:
    """The fast-path CI gate: the fused merge+repack program, the
    touched-tile ingest scatter and the pod-local collective join must
    be registered audit targets — an unregistered fast-path kernel
    fails the default run. The collective target needs a 2-device
    member mesh, so on a single-device host it is exempt rather than
    spuriously missing."""
    from .findings import Finding
    names = set(target_names)
    out = []
    for req in _FASTPATH_REQUIRED:
        if req.startswith("parallel.collective_join"):
            import jax
            if len(jax.devices()) < 2:
                continue
        if req not in names:
            out.append(Finding(
                rule="fastpath-kernel-unregistered",
                path="crdt_tpu/analysis/jaxpr_audit.py", line=0,
                message=f"fast-path kernel {req!r} is not a "
                        "registered jaxpr-audit target",
                detail="add it to builtin_targets() so the audit "
                       "covers the fused/zero-copy dispatch path "
                       "(docs/FASTPATH.md)"))
    return out


# Merkle anti-entropy kernels the default run must find in the
# jaxpr-audit registry: the digest reduction and the range-pack mask
# drive the cold-peer sync path (docs/ANTIENTROPY.md).
_MERKLE_REQUIRED = (
    "digest.digest_tree_levels",
    "dense.range_delta_mask",
)


def _merkle_completeness(target_names) -> List:
    """The merkle CI gate: the on-device digest-tree reduction and the
    slot-range delta mask must be registered audit targets — an
    unregistered anti-entropy kernel fails the default run."""
    from .findings import Finding
    names = set(target_names)
    out = []
    for req in _MERKLE_REQUIRED:
        if req not in names:
            out.append(Finding(
                rule="merkle-kernel-unregistered",
                path="crdt_tpu/analysis/jaxpr_audit.py", line=0,
                message=f"merkle anti-entropy kernel {req!r} is not a "
                        "registered jaxpr-audit target",
                detail="add it to builtin_targets() so the audit "
                       "covers the digest-reduction/range-pack "
                       "dispatch path (docs/ANTIENTROPY.md)"))
    return out


# Device entry points the default run must find registered with the
# dispatch ledger (obs.device): every jit/shard_map program a model or
# transport path can dispatch. An uninstrumented kernel is invisible to
# the compile census and breaks the zero-dispatch invariant probes
# (docs/OBSERVABILITY.md, device plane).
_LEDGER_REQUIRED = (
    # ops/dense.py — XLA executors, scatters, pack masks
    "dense.fanin_step", "dense.fanin_stream", "dense.sparse_fanin_step",
    "dense.wire_join_step", "dense.merge_repack_step",
    "dense.delta_mask", "dense.range_delta_mask",
    "dense.max_logical_time", "dense.put_scatter",
    "dense.record_scatter", "dense.delete_scatter",
    "dense.ingest_scatter",
    # ops/digest.py — the merkle reduction
    "digest.digest_tree_device",
    # ops/pallas_scatter.py — the resident planes: the in-place
    # flush, the one-gather point read, and the cold paths' view join
    # and split (either of those two on a served path is a miss)
    "pallas.ingest_scatter_planes", "dense.point_read",
    "dense.store_view", "dense.store_split",
    # ops/pallas_merge.py — Mosaic merge routes
    "pallas.model_fanin_batch", "pallas.model_fanin_split",
    "pallas.pipelined_model_step", "pallas.pipelined_model_step_split",
    # semantics/kernels.py — the typed fan-in family
    "semantics.typed_wire_join_step", "semantics.typed_sparse_join_step",
    "semantics.typed_fanin_step",
    # parallel/fanin.py — shard_map programs
    "parallel.sharded_fanin", "parallel.sharded_pallas_fanin",
    "parallel.sharded_ingest", "parallel.sharded_digest",
    "parallel.sharded_delta_mask", "parallel.sharded_max_logical_time",
    # parallel/collective.py — the pod-local group join
    "parallel.collective_join",
    # storage plane (docs/STORAGE.md) — epoch GC + online compaction
    "dense.gc_purge", "dense.compact_remap", "parallel.sharded_compact",
)


def _ledger_completeness(registered=None) -> List:
    """The dispatch-ledger CI gate: every device entry point must have
    declared itself to the ledger at module import — an uninstrumented
    kernel dispatches invisibly and fails the default run."""
    from .findings import Finding
    if registered is None:
        # Importing the instrumented modules runs their register()
        # calls; nothing is dispatched.
        from .. import parallel  # noqa: F401
        from ..obs.device import default_ledger
        from ..ops import (dense, digest, pallas_merge,  # noqa: F401
                           pallas_scatter)
        from ..semantics import kernels  # noqa: F401
        registered = default_ledger().registered_kernels()
    names = set(registered)
    out = []
    for req in _LEDGER_REQUIRED:
        if req not in names:
            out.append(Finding(
                rule="dispatch-ledger-unregistered",
                path="crdt_tpu/obs/device.py", line=0,
                message=f"device entry point {req!r} is not "
                        "registered with the dispatch ledger",
                detail="instrument its host wrapper with "
                       "obs.device.record(...) and register the name "
                       "at module import so dispatch counts, the "
                       "compile census and the zero-dispatch probes "
                       "cover it (docs/OBSERVABILITY.md)"))
    return out


# Storage-plane kernels (docs/STORAGE.md) the default run must find
# covered by BOTH verification surfaces: the jaxpr audit (an epoch-GC
# purge that reorders under donation corrupts silently) and the law
# search (purge composed with the merge-side resurrection fence must
# stay a semilattice — idempotent, commutative, associative — or
# replica states diverge permanently).
_GC_REQUIRED = (
    "dense.gc_purge",
    "dense.compact_remap",
)


def _gc_completeness(audit_names=None, law_names=None) -> List:
    """The storage-plane CI gate: epoch GC and online compaction must
    be registered with every verification surface that ran this
    invocation (pass ``None`` for one that did not run). A physically
    destructive kernel shipping without audit or law coverage is the
    one class of bug eventual consistency cannot repair."""
    from .findings import Finding
    out = []
    for req in _GC_REQUIRED:
        if audit_names is not None and req not in set(audit_names):
            out.append(Finding(
                rule="gc-kernel-unaudited",
                path="crdt_tpu/analysis/jaxpr_audit.py", line=0,
                message=f"storage-plane kernel {req!r} is not a "
                        "registered jaxpr-audit target",
                detail="add it to builtin_targets() — a donated "
                       "purge/remap with an order-sensitivity hazard "
                       "destroys state unrecoverably "
                       "(docs/STORAGE.md)"))
        if law_names is not None and req not in set(law_names):
            out.append(Finding(
                rule="gc-kernel-unlawed",
                path="crdt_tpu/analysis/lattice_laws.py", line=0,
                message=f"storage-plane kernel {req!r} is not a "
                        "registered law-search target",
                detail="add it to builtin_targets() — purge + fence "
                       "must provably stay a semilattice or replicas "
                       "diverge permanently (docs/STORAGE.md)"))
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m crdt_tpu.analysis",
        description="crdtlint: jaxpr lattice auditor + host-layer "
                    "race/discipline linter")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable JSON output")
    parser.add_argument("--lint", action="append", default=[],
                        metavar="PATH",
                        help="lint only this file/directory (repeat "
                             "to add more); disables the default "
                             "full-tree run")
    parser.add_argument("--law-fixture", action="append", default=[],
                        metavar="PATH",
                        help="run the law search over a fixture "
                             "module's LAW_TARGETS instead of the "
                             "builtin kernels")
    parser.add_argument("--seeds", default="0,1,2",
                        help="comma-separated seeds for the law "
                             "counterexample search (default 0,1,2)")
    parser.add_argument("--skip-lint", action="store_true",
                        help="skip the host linter in the default run")
    parser.add_argument("--skip-laws", action="store_true",
                        help="skip the law search in the default run")
    parser.add_argument("--skip-jaxpr", action="store_true",
                        help="skip the jaxpr audit in the default run")
    args = parser.parse_args(argv)

    from .findings import Finding, render_human, render_json
    findings: List[Finding] = []
    reports = []
    seeds = tuple(int(s) for s in args.seeds.split(",") if s.strip())
    explicit = bool(args.lint or args.law_fixture)

    if args.lint:
        from .concurrency import analyze_paths
        from .host_lint import lint_file, lint_package
        for path in args.lint:
            if os.path.isdir(path):
                findings.extend(lint_package(path))
            else:
                findings.extend(lint_file(path))
        # one global lock graph across every --lint path
        findings.extend(analyze_paths(args.lint))

    if args.law_fixture:
        from .lattice_laws import run_laws
        for path in args.law_fixture:
            findings.extend(run_laws(_load_law_fixture(path),
                                     seeds=seeds))

    if not explicit:
        if not args.skip_lint:
            from .concurrency import analyze_package
            from .host_lint import lint_package
            pkg_root = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            findings.extend(lint_package(pkg_root))
            findings.extend(analyze_package(pkg_root))
        if not args.skip_laws or not args.skip_jaxpr:
            # The registry gate guards exactly the law + jaxpr
            # coverage surfaces, so it runs whenever either does.
            findings.extend(_registry_completeness())
        law_names = audit_names = None
        if not args.skip_laws:
            from .lattice_laws import builtin_targets, run_laws
            law_targets = builtin_targets()
            law_names = tuple(t.name for t in law_targets)
            findings.extend(run_laws(law_targets, seeds=seeds))
        if not args.skip_jaxpr:
            from .jaxpr_audit import audit_all, builtin_targets as \
                audit_targets
            targets = audit_targets()
            names = tuple(t.name for t in targets)
            audit_names = names
            findings.extend(_fastpath_completeness(names))
            findings.extend(_merkle_completeness(names))
            findings.extend(_ledger_completeness())
            reports, audit_findings = audit_all(targets)
            findings.extend(audit_findings)
        if not args.skip_laws or not args.skip_jaxpr:
            findings.extend(_gc_completeness(audit_names, law_names))

    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    if args.json:
        print(render_json(
            findings,
            jaxpr_reports=[r.golden() for r in reports]))
    else:
        audited = (f" ({len(reports)} kernels audited)"
                   if reports else "")
        if findings:
            print(render_human(findings))
        else:
            print(f"crdtlint: clean{audited}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
