"""On-device segment-tree digests over dense store lanes.

Anti-entropy half of the delta-state design (docs/ANTIENTROPY.md):
watermark deltas (`pack_since`) assume a peer you've talked to before,
so a fresh or long-partitioned replica forces a full-store scan. A
Merkle-style digest tree lets two replicas localize divergence in
O(log n) round trips instead — exchange the root, walk only the
subtrees whose digests differ, ship the divergent slot ranges through
the zero-copy range pack.

The whole reduction runs ON DEVICE in one jit-cached program
(`_digest_tree_jit`): a per-slot 64-bit mix over the replicated lanes
(`lt`, `val`, `tomb`, optional sem tag — NOT `node`/`mod_lt`, which are
replica-local ordinals/bookkeeping and differ between converged
stores), a wrapping-sum fold into fixed-width leaves, then pairwise
order-sensitive combines up to the root. Leaves are padded to a power
of two with the all-empty digest (0) so equal stores always produce
equal trees regardless of slot-count rounding. The model layer caches
the fetched levels keyed on ``(clock, sem_version)`` exactly like the
pack cache, so an unchanged store recomputes (and dispatches) nothing.

The mix is splitmix64's finalizer — fast, avalanche-complete, and
expressible as u64 shifts/xors/multiplies the TPU vector units handle
natively. It is NOT cryptographic; anti-entropy digests defend against
divergence, not adversaries (same trust model as the wire itself).
Host code must never re-hash store lanes (crdtlint rule
``merkle-digest-host-hash``) — the digest is the device's job.
"""

from __future__ import annotations

import functools as _ft
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dense import DenseStore
from ..obs import device as _obs_device

_obs_device.register("digest.digest_tree_device")

#: Slots folded into one leaf digest. The width trades walk traffic
#: against re-ship amplification: a divergent slot re-ships its whole
#: leaf, and under UNIFORMLY scattered divergence at rate p the
#: expected fraction of leaves hit is ``1-(1-p)^W`` (~``W*p`` for
#: small p), so wide leaves ship most of the store at 1% scatter
#: (W=64 -> 47%) while narrow ones stay proportional (W=8 -> 7.7%).
#: 8 keeps the walk within depth log2(n)-2 rounds and the bottom-level
#: probes ride the binary frame at 8 bytes/digest, so the extra depth
#: costs little (measured in BENCH_r08).
DEFAULT_LEAF_WIDTH = 8

#: Tree levels probed per round trip when the fetch side supports
#: batched (multi-level) probes. Each round speculatively requests the
#: DESCENDANTS of the whole current frontier for the next
#: ``PREFETCH_LEVELS - 1`` levels — at most ``(2^P - 1)`` digests per
#: frontier node, 8 bytes each — so a walk costs
#: ``ceil(depth / PREFETCH_LEVELS)`` round trips instead of ``depth``.
#: 3 trades ~7x the (tiny) digest bytes for a 3x round-trip cut, the
#: right direction on the high-RTT links cold joins cross.
PREFETCH_LEVELS = 3

#: Speculative expansion stops growing a batch past this many indices
#: per level: a wide frontier (heavy divergence) already amortizes its
#: round trips, and an unbounded 2^P fan-out on a big tree could make
#: one probe frame rival the payload it is trying to localize.
PREFETCH_MAX_BATCH = 512

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_MIX_A = np.uint64(0xBF58476D1CE4E5B9)
_MIX_B = np.uint64(0x94D049BB133111EB)
_TOMB_SALT = np.uint64(0xD6E8FEB86659FD93)
_SEM_SALT = np.uint64(0xFF51AFD7ED558CCD)
_NODE_SALT = np.uint64(0xC2B2AE3D27D4EB4F)


def _mix64(x):
    """splitmix64 finalizer — u64 shifts/xors/multiplies only, so the
    same expression runs under jit and on host numpy scalars."""
    x = (x ^ (x >> np.uint64(30))) * _MIX_A
    x = (x ^ (x >> np.uint64(27))) * _MIX_B
    return x ^ (x >> np.uint64(31))


def slot_digests(lt, val, tomb, occupied, sem=None, idx_offset=None):
    """Per-slot 64-bit digests over the REPLICATED lanes, zero where
    unoccupied. ``idx_offset`` shifts the mixed-in slot index so a
    shard can digest its local window against global positions
    (parallel/fanin.py)."""
    n = lt.shape[0]
    idx = jnp.arange(n, dtype=jnp.uint64)
    if idx_offset is not None:
        idx = idx + idx_offset
    h = _mix64(lt.astype(jnp.uint64) + _GOLDEN * (idx + np.uint64(1)))
    h = h ^ _mix64(val.astype(jnp.uint64) ^ _MIX_B)
    h = h ^ jnp.where(tomb, _TOMB_SALT, np.uint64(0))
    if sem is not None:
        h = h ^ _mix64(sem.astype(jnp.uint64) + _SEM_SALT)
    return jnp.where(occupied, _mix64(h), np.uint64(0))


def _pow2_at_least(n: int) -> int:
    p2 = 1
    while p2 < max(1, n):
        p2 *= 2
    return p2


def fold_leaves(digests, leaf_width: int):
    """Wrapping-sum fold of per-slot digests into leaf digests (slot
    position is already mixed into each digest, so the commutative sum
    loses nothing), slot-padded with zeros so a ragged tail leaf and
    an all-empty leaf digest identically (0 contribution). Emits
    ``ceil(n / leaf_width)`` leaves — NO power-of-two padding here, so
    per-shard folds concatenate into the exact global leaf row
    (`parallel.make_sharded_digest`).

    A 1-D reduce window, not a sum over a ``[n_leaves, leaf_width]``
    reshape: the TPU pads that array's minor dimension of 8 to 128
    lanes, a 16x copy of the digests (16.5 GB at 2^27 slots, past the
    chip's memory)."""
    n = digests.shape[0]
    n_leaves = max(1, -(-n // leaf_width))
    pad = n_leaves * leaf_width - n
    if pad:
        digests = jnp.concatenate(
            [digests, jnp.zeros((pad,), jnp.uint64)])
    return jax.lax.reduce_window(digests, np.uint64(0), jax.lax.add,
                                 (leaf_width,), (leaf_width,), "VALID")


def combine_level(children):
    """One interior level: order-sensitive pairwise combine, over a
    level held TRANSPOSED. ``children`` is a level of ``R * C`` nodes
    viewed ``[R, C]`` in node order and transposed to ``[C, R]``, so
    node ``r*C + c`` sits at row ``c``, lane ``r``, and the pair
    ``(2j, 2j+1)`` under parent ``r*C/2 + j`` is rows ``2j`` and
    ``2j+1`` of lane ``r``. Returns the parent level the same way,
    ``[C/2, R]``.

    Not ``x[0::2]`` / ``x[1::2]`` on the node-order row: JAX lowers
    numpy strided indexing to a ``gather``, and the TPU runs each one
    as fusions over s32 index arrays (~290 ms for one level of 2^24
    leaves on a v5e). Row-strided slices of the transposed view read
    whole rows and lower to plain slices, no gather."""
    c, r = children.shape
    left = jax.lax.slice(children, (0, 0), (c, r), (2, 1))
    right = jax.lax.slice(children, (1, 0), (c, r), (2, 1))
    return _mix64(left + _GOLDEN * right + _MIX_A)


def tree_levels_from_leaves(leaves) -> Tuple[jax.Array, ...]:
    """Pad the leaf row to a power of two with the all-empty digest
    (so equal stores always produce equal trees regardless of
    slot-count rounding), then build every interior level. Returns
    levels ROOT-FIRST (``levels[0]`` shape (1,), ``levels[-1]`` the
    padded leaves).

    A level of ``2^k`` nodes is transposed once as ``[2^(k-c), 2^c]``
    with ``c = ceil(k/2)``, both sides at least 128 from 2^14 nodes
    up; `combine_level` folds ``c`` levels from it, each transposed
    back to node order as it is emitted; the last of them
    (``2^(k-c)`` nodes) is viewed and transposed the same way."""
    n_leaves = _pow2_at_least(int(leaves.shape[0]))
    pad = n_leaves - int(leaves.shape[0])
    if pad:
        leaves = jnp.concatenate(
            [leaves, jnp.zeros((pad,), jnp.uint64)])
    levels = [leaves]
    while levels[-1].shape[0] > 1:
        k = levels[-1].shape[0].bit_length() - 1
        t = levels[-1].reshape(-1, 1 << -(-k // 2)).T
        while t.shape[0] > 1:
            t = combine_level(t)
            levels.append(t.T.reshape(-1))
    return tuple(reversed(levels))


def digest_levels_from_lanes(lt, val, tomb, occupied, sem=None,
                             leaf_width: int = DEFAULT_LEAF_WIDTH,
                             idx_offset=None) -> Tuple[jax.Array, ...]:
    """The full traceable reduction — per-slot mix -> leaf fold ->
    every interior combine — straight from store lanes. This is the
    composition `_digest_tree_jit` runs standalone AND the piece
    `ops.dense.compact_remap` fuses after its slot remap, so a
    compacted store leaves the dispatch with its digest tree already
    rebuilt (one program, no second dispatch)."""
    h = slot_digests(lt, val, tomb, occupied, sem=sem,
                     idx_offset=idx_offset)
    return tree_levels_from_leaves(fold_leaves(h, leaf_width))


@_ft.lru_cache(maxsize=None)
def _digest_tree_jit(leaf_width: int, has_sem: bool):
    """jit-cached digest reduction: per-slot mix -> leaf fold -> all
    interior combines in ONE program. Inputs are live store lanes read
    in place (a digest must not consume the store, so nothing is
    donated); the cache key mirrors the other kernel factories."""

    def step(lt, val, tomb, occupied, *sem):
        return digest_levels_from_lanes(
            lt, val, tomb, occupied, sem=sem[0] if has_sem else None,
            leaf_width=leaf_width)

    return jax.jit(step)


def digest_tree_device(store: DenseStore, sem=None,
                       leaf_width: int = DEFAULT_LEAF_WIDTH
                       ) -> Tuple[jax.Array, ...]:
    """Digest-tree levels (root-first) for a dense store, computed on
    device. ``sem`` is the optional per-slot semantics tag column."""
    args = (store.lt, store.val, store.tomb, store.occupied)
    with _obs_device.record("digest.digest_tree_device",
                            dim=store.lt.shape[0]):
        if sem is not None:
            return _digest_tree_jit(leaf_width, True)(*args, sem)
        return _digest_tree_jit(leaf_width, False)(*args)


class DigestTree(NamedTuple):
    """Host-side view of the fetched levels + walk geometry. Two trees
    are comparable only when ``n_slots`` and ``leaf_width`` agree —
    the wire walk checks geometry before descending."""

    n_slots: int
    leaf_width: int
    levels: Tuple[np.ndarray, ...]  # root-first; levels[-1] = leaves

    @property
    def depth(self) -> int:
        return len(self.levels)

    @property
    def root(self) -> int:
        return int(self.levels[0][0])

    @property
    def n_leaves(self) -> int:
        return int(self.levels[-1].shape[0])

    def values(self, level: int, idxs: Sequence[int]) -> List[int]:
        if not 0 <= level < self.depth:
            raise ValueError(f"digest level {level} out of range "
                             f"[0, {self.depth})")
        row = self.levels[level]
        out = []
        for i in idxs:
            i = int(i)
            if not 0 <= i < row.shape[0]:
                raise ValueError(f"digest index {i} out of range for "
                                 f"level {level} (width {row.shape[0]})")
            out.append(int(row[i]))
        return out

    def values_levels(self, groups: Sequence[Tuple[int, Sequence[int]]]
                      ) -> List[List[int]]:
        """Batched :meth:`values`: one result row per ``(level, idxs)``
        group — the in-process mirror of the wire's multi-level
        ``digest`` probe (``more`` groups), so tests and local walks
        exercise the same prefetch shape the socket path ships."""
        return [self.values(level, idxs) for level, idxs in groups]

    def same_geometry(self, n_slots: int, leaf_width: int,
                      depth: int) -> bool:
        return (self.n_slots == n_slots
                and self.leaf_width == leaf_width
                and self.depth == depth)

    def leaf_range(self, leaf_idx: int) -> Tuple[int, int]:
        lo = leaf_idx * self.leaf_width
        return lo, min(lo + self.leaf_width, self.n_slots)


def build_digest_tree(n_slots: int, leaf_width: int,
                      levels: Sequence[jax.Array]) -> DigestTree:
    """One ``device_get`` over every level -> host DigestTree."""
    host = jax.device_get(tuple(levels))
    return DigestTree(n_slots=int(n_slots), leaf_width=int(leaf_width),
                      levels=tuple(np.asarray(a) for a in host))


class DeviceDigestLevels:
    """Digest levels a program left on the device (the collective
    join's): `tree` fetches every level in one ``device_get`` the first
    time a walk or ``digest_tree`` asks, and keeps the host tree, so a
    round that nobody walks moves no digest bytes to the host. Members
    of one group share one instance, so the group fetches once."""

    __slots__ = ("n_slots", "leaf_width", "_levels", "_tree")

    def __init__(self, n_slots: int, leaf_width: int,
                 levels: Sequence[jax.Array]):
        self.n_slots = n_slots
        self.leaf_width = leaf_width
        self._levels = tuple(levels)
        self._tree: Optional[DigestTree] = None

    def tree(self) -> DigestTree:
        if self._tree is None:
            self._tree = build_digest_tree(self.n_slots, self.leaf_width,
                                           self._levels)
            self._levels = ()
        return self._tree


def walk_divergent_leaves(
        tree: DigestTree,
        fetch: Optional[Callable[[int, List[int]], Sequence[int]]],
        fetch_levels: Optional[
            Callable[[List[Tuple[int, List[int]]]],
                     Sequence[Sequence[int]]]] = None,
        prefetch: int = PREFETCH_LEVELS,
) -> Tuple[List[int], int, int]:
    """Top-down walk against a remote tree reachable only through a
    fetch callback. Two fetch shapes:

    - ``fetch(level, idxs) -> values`` — one level per call (one wire
      round trip on the socket path), so the whole walk is <= depth =
      log2(n_leaves)+1 rounds. The original shape; any peer speaking
      the single-level ``digest`` op supports it.
    - ``fetch_levels(groups) -> [values, ...]`` with ``groups`` a list
      of ``(level, idxs)`` pairs — frontier PREFETCH: each call probes
      the current frontier plus the speculative descendants of the
      whole frontier for the next ``prefetch - 1`` levels (capped at
      `PREFETCH_MAX_BATCH` indices per level), cutting the walk to
      ``ceil(depth / prefetch)`` round trips. The walk then descends
      through the prefetched levels locally: every next frontier is by
      construction a subset of the speculative request, so no
      mid-batch fetch is ever needed.

    Returns ``(divergent_leaf_idxs, rounds, values_fetched)`` — an
    empty leaf list means the trees (and therefore the replicated
    lanes) agree. ``values_fetched`` counts every digest requested,
    speculative ones included (8 bytes each on the wire).
    """
    if fetch_levels is None:
        if fetch is None:
            raise ValueError("walk needs fetch or fetch_levels")
        frontier = [0]
        rounds = 0
        fetched = 0
        for level in range(tree.depth):
            remote = fetch(level, frontier)
            rounds += 1
            fetched += len(frontier)
            local = tree.levels[level]
            diff = [i for i, v in zip(frontier, remote)
                    if int(local[i]) != int(v)]
            if not diff:
                return [], rounds, fetched
            if level == tree.depth - 1:
                return diff, rounds, fetched
            frontier = [c for i in diff for c in (2 * i, 2 * i + 1)]
        return [], rounds, fetched  # pragma: no cover — loop returns

    if prefetch < 1:
        raise ValueError(f"prefetch must be >= 1; got {prefetch}")
    frontier = [0]
    level = 0
    rounds = 0
    fetched = 0
    while level < tree.depth:
        groups: List[Tuple[int, List[int]]] = []
        idxs = list(frontier)
        for lvl in range(level, min(level + prefetch, tree.depth)):
            if groups and len(idxs) > PREFETCH_MAX_BATCH:
                break
            groups.append((lvl, idxs))
            if lvl + 1 < tree.depth:
                idxs = [c for i in idxs for c in (2 * i, 2 * i + 1)]
        results = fetch_levels(groups)
        rounds += 1
        fetched += sum(len(ix) for _, ix in groups)
        if len(results) != len(groups):
            raise ValueError(
                f"fetch_levels returned {len(results)} groups for "
                f"{len(groups)} requested")
        for (lvl, g_idxs), vals in zip(groups, results):
            if len(vals) != len(g_idxs):
                raise ValueError(
                    f"fetch_levels group {lvl} returned {len(vals)} "
                    f"values for {len(g_idxs)} indices")
            remote = {i: int(v) for i, v in zip(g_idxs, vals)}
            local = tree.levels[lvl]
            diff = [i for i in frontier if int(local[i]) != remote[i]]
            if not diff:
                return [], rounds, fetched
            if lvl == tree.depth - 1:
                return diff, rounds, fetched
            frontier = [c for i in diff for c in (2 * i, 2 * i + 1)]
        level = groups[-1][0] + 1
    return [], rounds, fetched  # pragma: no cover — loop returns


def coalesce_leaf_ranges(leaf_idxs: Sequence[int], leaf_width: int,
                         n_slots: int) -> Tuple[Tuple[int, int], ...]:
    """Divergent leaves -> minimal sorted ``(lo, hi)`` slot spans for
    the range pack (adjacent leaves merge into one span; the tail span
    clips to ``n_slots`` so padding leaves never widen the pack)."""
    spans: List[Tuple[int, int]] = []
    for leaf in sorted(set(int(i) for i in leaf_idxs)):
        lo = leaf * leaf_width
        hi = min(lo + leaf_width, n_slots)
        if lo >= n_slots or hi <= lo:
            continue  # pure padding leaf
        if spans and spans[-1][1] == lo:
            spans[-1] = (spans[-1][0], hi)
        else:
            spans.append((lo, hi))
    return tuple(spans)
