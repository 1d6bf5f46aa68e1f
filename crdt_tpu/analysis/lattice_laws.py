"""Seeded counterexample search for the semilattice laws.

The paper's convergence guarantee reduces to three properties of the
join the merge kernels implement (PAPERS.md, "Certified Mergeable
Replicated Data Types" frames them as checkable artifacts):

    idempotence     join(s, a) twice == once
    commutativity   join(join(s, a), b) == join(join(s, b), a)
    associativity   join over [a ++ b] == join over a, then over b

We check them on the DEVICE kernels, not a model: each
:class:`LawTarget` wraps a registered merge step and a way to combine
deltas, and ``run_laws`` drives randomized record batches through it,
reporting the violating input (seed, lanes, both results) when a law
fails.

Two scoping decisions keep the check honest rather than vacuous:

- **Compared lanes** are (lt, node, val, occupied, tomb) — the CRDT
  state. ``mod_lt``/``mod_node`` stamp local apply time and are
  order-dependent BY DESIGN (stamping is bookkeeping, not lattice
  state), so they are excluded.
- **Event uniqueness**: generated batches derive ``val``
  deterministically from ``(lt, node)``. Two replicas never emit
  different values for the same HLC stamp, so value disagreement under
  reordering is a real law violation, not generator noise. Without
  this, commutativity is unfalsifiable (ties broken either way are
  both "right").

Targets whose batch semantics forbid duplicate slots within one delta
(the scatter-based steps) set ``combine=None`` and are checked for
idempotence + commutativity only — associativity's concatenation
would manufacture exactly the duplicate-slot batches the call contract
excludes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

from .findings import Finding

# Lanes that ARE lattice state; mod_lt/mod_node are stamping
# bookkeeping and order-dependent by design.
_STATE_LANES = ("lt", "node", "val", "occupied", "tomb")

_LOCAL_NODE = 0          # generated events use nodes 1..4: never the
                         # local node, so recv-side self-echo guards
                         # cannot mask a law violation
_WALL = 1 << 30          # far future => drift guard never clamps


@dataclass
class LawTarget:
    """One merge step under law checking.

    ``apply(store, batch) -> store`` runs the kernel. ``fresh()``
    builds an empty store. ``gen(rng) -> batch`` draws one randomized
    delta. ``combine(a, b) -> batch`` concatenates two deltas for the
    associativity check; None skips that law (per-call uniqueness
    contracts). ``extract(store) -> dict[lane, ndarray]`` pulls the
    compared lanes."""

    name: str
    fresh: Callable[[], object]
    gen: Callable[[object], object]
    apply: Callable[[object, object], object]
    extract: Callable[[object], dict]
    combine: Optional[Callable[[object, object], object]] = None
    notes: str = ""


def _stores_equal(a: dict, b: dict) -> bool:
    import numpy as np
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
               for k in _STATE_LANES)


def _diff_detail(a: dict, b: dict, labels: Tuple[str, str]) -> str:
    import numpy as np
    lines: List[str] = []
    for k in _STATE_LANES:
        av, bv = np.asarray(a[k]), np.asarray(b[k])
        if np.array_equal(av, bv):
            continue
        idx = np.argwhere(av != bv)
        lines.append(f"lane '{k}' differs at {len(idx)} slot(s); "
                     f"first at {tuple(int(i) for i in idx[0])}: "
                     f"{labels[0]}={av[tuple(idx[0])]} "
                     f"{labels[1]}={bv[tuple(idx[0])]}")
    return "\n".join(lines)


def _batch_repr(batch: object) -> str:
    import numpy as np
    if isinstance(batch, dict):
        items = batch.items()
    elif hasattr(batch, "__dict__"):
        items = vars(batch).items()
    else:
        return repr(batch)
    lines = []
    for k, v in items:
        arr = np.asarray(v)
        with np.printoptions(threshold=64, linewidth=100):
            lines.append(f"{k} = {arr!r}")
    return "\n".join(lines)


def check_target(target: LawTarget, seed: int) -> List[Finding]:
    """Run all applicable laws on one target with one seed."""
    import numpy as np
    rng = np.random.default_rng(seed)
    findings: List[Finding] = []
    path = f"<law:{target.name}>"
    a = target.gen(rng)
    b = target.gen(rng)

    def fail(law: str, a_res: dict, b_res: dict,
             labels: Tuple[str, str], batches: Sequence) -> None:
        detail = _diff_detail(a_res, b_res, labels)
        detail += "\nviolating input (seed={}):\n".format(seed)
        for i, batch in enumerate(batches):
            detail += f"-- batch {i} --\n{_batch_repr(batch)}\n"
        findings.append(Finding(
            rule=f"law-{law}", path=path, line=0,
            message=f"{law} violated by {target.name} "
                    f"(seed={seed}); counterexample below",
            detail=detail.rstrip()))

    # idempotence: applying the same delta twice is a no-op
    once = target.apply(target.fresh(), a)
    twice = target.apply(once, a)
    e_once, e_twice = target.extract(once), target.extract(twice)
    if not _stores_equal(e_once, e_twice):
        fail("idempotence", e_once, e_twice, ("once", "twice"), [a])

    # commutativity: delta application order must not matter
    ab = target.apply(target.apply(target.fresh(), a), b)
    ba = target.apply(target.apply(target.fresh(), b), a)
    e_ab, e_ba = target.extract(ab), target.extract(ba)
    if not _stores_equal(e_ab, e_ba):
        fail("commutativity", e_ab, e_ba, ("a,b", "b,a"), [a, b])

    # associativity: one combined delta == two sequential deltas
    if target.combine is not None:
        joint = target.apply(target.fresh(), target.combine(a, b))
        e_joint = target.extract(joint)
        if not _stores_equal(e_ab, e_joint):
            fail("associativity", e_ab, e_joint,
                 ("sequential", "combined"), [a, b])

    return findings


def run_laws(targets: Sequence[LawTarget],
             seeds: Sequence[int] = (0, 1, 2)) -> List[Finding]:
    findings: List[Finding] = []
    for target in targets:
        for seed in seeds:
            hits = check_target(target, seed)
            findings.extend(hits)
            if hits:
                break   # one counterexample per target is enough
    return findings


# --- builtin targets over the registered kernels ---

_N = 64          # store width for law batches
_R = 8           # rows per delta


def _event_lanes(rng, size) -> tuple:
    """(lt, node, val, tomb) with the event-uniqueness invariant: val
    and tomb are deterministic functions of (lt, node), so identical
    stamps can never carry different payloads — otherwise ties broken
    either way are both 'right' and commutativity is unfalsifiable."""
    import numpy as np
    millis = rng.integers(1, 1 << 20, size=size)
    counter = rng.integers(0, 4, size=size)
    lt = ((millis << 16) | counter).astype(np.int64)
    node = rng.integers(1, 5, size=size).astype(np.int32)  # != local 0
    val = ((lt * 31 + node * 7) & 0x7FFF).astype(np.int64)
    tomb = ((lt ^ node) & 1).astype(bool)
    return lt, node, val, tomb


def _gen_sparse(rng, n: int, rows: int) -> dict:
    import numpy as np
    lt, node, val, tomb = _event_lanes(rng, rows)
    return {"slot": rng.integers(0, n, size=rows).astype(np.int64),
            "lt": lt, "node": node, "val": val, "tomb": tomb,
            "valid": np.ones(rows, dtype=bool)}


def _gen_dense(rng, n: int) -> dict:
    """Full-width wire delta (one lane value per slot, valid mask)."""
    import numpy as np
    lt, node, val, tomb = _event_lanes(rng, n)
    valid = rng.integers(0, 2, size=n).astype(bool)
    return {"lt": np.where(valid, lt, 0),
            "node": np.where(valid, node, 0).astype(np.int32),
            "val": np.where(valid, val, 0),
            "tomb": valid & tomb, "valid": valid}


def _extract_store(store) -> dict:
    import numpy as np
    return {k: np.asarray(getattr(store, k)) for k in _STATE_LANES}


def make_wire_join_target(step: Callable, name: str,
                          notes: str = "") -> LawTarget:
    """LawTarget over a wire_join_step-shaped callable
    ``step(store, lt, node, val, tomb, valid, stamp_lt, local_node)``.
    Public so the broken-merge fixture (and future kernels) reuse the
    harness instead of reimplementing it."""
    from ..ops.dense import empty_dense_store

    def fresh():
        return empty_dense_store(_N)

    def gen(rng):
        return _gen_dense(rng, _N)

    def apply(store, batch):
        import numpy as np
        new_store, _win = step(
            store, batch["lt"], batch["node"], batch["val"],
            batch["tomb"], batch["valid"],
            np.int64(_WALL << 16), np.int32(_LOCAL_NODE))
        return new_store

    def combine(a, b):
        # elementwise lattice max of two wire deltas: per slot keep
        # the (lt, node)-lex greater valid event (equal stamps carry
        # equal payloads by the uniqueness invariant, so >= is safe)
        import numpy as np
        a_newer = ((a["lt"] > b["lt"])
                   | ((a["lt"] == b["lt"]) & (a["node"] >= b["node"])))
        a_wins = a["valid"] & (~b["valid"] | a_newer)
        out = {}
        for k in ("lt", "node", "val", "tomb", "valid"):
            out[k] = np.where(a_wins, a[k], b[k])
        out["valid"] = a["valid"] | b["valid"]
        return out

    return LawTarget(name=name, fresh=fresh, gen=gen, apply=apply,
                     extract=_extract_store, combine=combine,
                     notes=notes)


def builtin_targets() -> List[LawTarget]:
    """Law targets over the registered merge kernels. Imports jax-side
    modules lazily so the host linter can run without jax."""
    import numpy as np
    from ..ops import dense as dense_ops

    targets: List[LawTarget] = [
        make_wire_join_target(
            dense_ops.wire_join_step, "dense.wire_join_step",
            notes="elementwise full-width join; all three laws"),
    ]

    # sparse_fanin_step: scatter-based; call contract requires unique
    # slots per delta => no associativity (concatenation would
    # manufacture exactly the duplicate-slot batches the contract
    # excludes).
    def sparse_fresh():
        return dense_ops.empty_dense_store(_N)

    def sparse_gen(rng):
        lanes = _gen_sparse(rng, _N, _R)
        # unique slots per delta (dict-keyed deltas guarantee it in
        # production); keep the first occurrence of each slot
        _, first = np.unique(lanes["slot"], return_index=True)
        keep = np.zeros(_R, dtype=bool)
        keep[first] = True
        lanes["valid"] = lanes["valid"] & keep
        return lanes

    def sparse_apply(store, batch):
        new_store, _win = dense_ops.sparse_fanin_step(
            store, batch["slot"], batch["lt"], batch["node"],
            batch["val"], batch["tomb"], batch["valid"],
            np.int64(_WALL << 16), np.int32(_LOCAL_NODE))
        return new_store

    targets.append(LawTarget(
        name="dense.sparse_fanin_step", fresh=sparse_fresh,
        gen=sparse_gen, apply=sparse_apply, extract=_extract_store,
        combine=None,
        notes="unique-slot contract: idempotence + commutativity "
              "only"))

    # fanin_step: R-row masked fold into the store; rows may collide,
    # the fold resolves them — all three laws, combine = row concat.
    def fanin_fresh():
        return dense_ops.empty_dense_store(_N)

    def fanin_gen(rng):
        lanes = _gen_sparse(rng, _N, _R)
        return dense_ops.DenseChangeset(
            lt=_rows_to_grid(lanes, "lt", np.int64),
            node=_rows_to_grid(lanes, "node", np.int32),
            val=_rows_to_grid(lanes, "val", np.int64),
            tomb=_rows_to_grid(lanes, "tomb", bool),
            valid=_rows_to_grid(lanes, "valid", bool))

    def fanin_apply(store, cs):
        new_store, _res = dense_ops.fanin_step(
            store, cs, canonical_lt=np.int64(0),
            local_node=np.int32(_LOCAL_NODE),
            wall_millis=np.int64(_WALL))
        return new_store

    def fanin_combine(a, b):
        import numpy as np
        return dense_ops.DenseChangeset(
            lt=np.concatenate([np.asarray(a.lt), np.asarray(b.lt)]),
            node=np.concatenate([np.asarray(a.node),
                                 np.asarray(b.node)]),
            val=np.concatenate([np.asarray(a.val), np.asarray(b.val)]),
            tomb=np.concatenate([np.asarray(a.tomb),
                                 np.asarray(b.tomb)]),
            valid=np.concatenate([np.asarray(a.valid),
                                  np.asarray(b.valid)]))

    targets.append(LawTarget(
        name="dense.fanin_step", fresh=fanin_fresh, gen=fanin_gen,
        apply=fanin_apply, extract=_extract_store,
        combine=fanin_combine,
        notes="R-row masked fold; all three laws, combine=row "
              "concatenation"))

    # The pod-local collective join, driven as a 2-member group where
    # one member plays the store and the other the incoming delta; the
    # laws read member 0's joined lanes. Needs >= 2 devices
    # (tests/conftest.py and the CLI force 8 virtual CPU devices);
    # skipped otherwise — the pairwise kernels above cover the same
    # join rules on one device, and the collective≡wire property test
    # pins the two paths bit-identical.
    import jax
    if len(jax.devices()) >= 2:
        from ..ops.dense import DenseStore
        from ..parallel import collective as _pc

        coll_mesh = _pc.make_collective_mesh(2)
        coll_step = _pc.make_collective_join(coll_mesh, False, 8)

        def coll_fresh():
            return dense_ops.empty_dense_store(_N)

        def coll_gen(rng):
            return _gen_dense(rng, _N)

        def coll_apply(store, batch):
            other = DenseStore(
                lt=batch["lt"], node=batch["node"],
                val=batch["val"],
                mod_lt=np.zeros(_N, np.int64),
                mod_node=np.zeros(_N, np.int32),
                occupied=batch["valid"], tomb=batch["tomb"])
            joined, _res = coll_step(
                (store, other), np.zeros(2, np.int64),
                np.asarray([0, 1], np.int32), np.int64(0))
            return joined[0]

        def coll_combine(a, b):
            # same elementwise lex-max of two wire deltas as
            # make_wire_join_target: a member store IS a
            # full-width delta to the group
            a_newer = ((a["lt"] > b["lt"])
                       | ((a["lt"] == b["lt"])
                          & (a["node"] >= b["node"])))
            a_wins = a["valid"] & (~b["valid"] | a_newer)
            out = {}
            for k in ("lt", "node", "val", "tomb", "valid"):
                out[k] = np.where(a_wins, a[k], b[k])
            out["valid"] = a["valid"] | b["valid"]
            return out

        targets.append(LawTarget(
            name="parallel.collective_join[member2]",
            fresh=coll_fresh, gen=coll_gen, apply=coll_apply,
            extract=_extract_store, combine=coll_combine,
            notes="group join as all-reduce over a 2-member mesh; "
                  "all three laws on member 0's lanes"))

    # --- storage plane (docs/STORAGE.md) ---
    #
    # dense.gc_purge: the COMPOSITE operator the deployed system runs
    # — floor-masked join (the merge-side resurrection fence, modeled
    # as its stability premise: nothing at or below the floor is
    # still in flight, so sub-floor inbound rows are masked) followed
    # by the purge kernel at the same fixed floor. The fresh store is
    # pre-seeded with sub-floor tombstones AND sub-floor live rows,
    # so the purge genuinely fires (tombs vanish, live rows survive)
    # on every law path. All three laws on the above-floor
    # sublattice.
    _FLOOR = np.int64(1) << 30

    def gc_fresh():
        lt = np.zeros(_N, np.int64)
        node = np.zeros(_N, np.int32)
        val = np.zeros(_N, np.int64)
        occ = np.zeros(_N, bool)
        tomb = np.zeros(_N, bool)
        for i in range(8):
            lt[i] = int(_FLOOR) - 1 - i
            node[i] = np.int32(1 + (i % 4))
            occ[i] = True
            tomb[i] = (i % 2 == 0)
            val[i] = 0 if tomb[i] else 100 + i
        return dense_ops.DenseStore(
            lt=lt, node=node, val=val,
            mod_lt=np.zeros(_N, np.int64),
            mod_node=np.zeros(_N, np.int32),
            occupied=occ, tomb=tomb)

    def gc_apply(store, batch):
        stability_floor = np.int64(_FLOOR)  # fixed modeled watermark
        fenced = np.asarray(batch["valid"]) \
            & (np.asarray(batch["lt"]) > stability_floor)
        joined, _win = dense_ops.wire_join_step(
            store, batch["lt"], batch["node"], batch["val"],
            batch["tomb"], fenced, np.int64(_WALL << 16),
            np.int32(_LOCAL_NODE))
        purged, _count, _mask = dense_ops.gc_purge(
            joined, stability_floor)
        return purged

    _wire = make_wire_join_target(dense_ops.wire_join_step,
                                  "dense.gc_purge")
    targets.append(LawTarget(
        name="dense.gc_purge", fresh=gc_fresh,
        gen=_wire.gen, apply=gc_apply, extract=_extract_store,
        combine=_wire.combine,
        notes="floor-masked join + purge at a fixed stability floor; "
              "all three laws on the above-floor sublattice, purge "
              "fires on the seeded sub-floor tombstones"))

    # dense.compact_remap: join laws preserved under the compaction
    # quotient — extract compares the REMAPPED lanes (full-span
    # compact), so law-equal stores must also compact identically:
    # the remap is a deterministic, slot-order-preserving function of
    # the store, never of the delivery order.
    def compact_extract(store):
        out = dense_ops.compact_remap(
            store, np.asarray([0], np.int64),
            np.asarray([_N], np.int64), None, leaf_width=8)
        new_store, _translation, _live, _levels = out
        return _extract_store(new_store)

    compacted = make_wire_join_target(
        dense_ops.wire_join_step, "dense.compact_remap",
        notes="wire join compared through the compaction quotient: "
              "the remap must be order-independent or replicas that "
              "compact diverge")
    compacted.extract = compact_extract
    targets.append(compacted)

    # The semantics registry contributes one typed wire-join target
    # per registered lane type (crdt_tpu/semantics/types.py) — a new
    # type gets law coverage by registering, zero hand-listed targets.
    from ..semantics import law_targets as _semantics_law_targets
    targets.extend(_semantics_law_targets())

    return targets


def _rows_to_grid(lanes: dict, key: str, dtype):
    """Scatter R sparse rows into an [R, N] one-event-per-row grid —
    the DenseChangeset layout fanin_step folds over."""
    import numpy as np
    rows = len(lanes["slot"])
    grid = np.zeros((rows, _N), dtype=dtype)
    r = np.arange(rows)
    grid[r, lanes["slot"]] = lanes[key] if key != "valid" \
        else lanes["valid"]
    mask = np.zeros((rows, _N), dtype=bool)
    mask[r, lanes["slot"]] = lanes["valid"]
    if key != "valid":
        grid = np.where(mask, grid, np.zeros_like(grid))
    return grid.astype(dtype)
