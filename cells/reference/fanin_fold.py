"""Plain reference for the fan-in: a per-key fold of every replica row.

A key's record after the fan-in is the highest hybrid logical clock
among the base store's record and every valid replica row for that
key, compared as (logical time, node id); the base record keeps an
exact tie (the reference's ``Crdt.merge`` replaces only on a strictly
greater clock). Tombstones are records like any other.

Written in plain ``jax.numpy`` over blocks of keys, so that it runs on
the device beside the rows it folds. Nothing here imports the program;
node ids are compared by their rank in the sorted list of names.
"""

from __future__ import annotations

import numpy as np

NEG = -(1 << 63)


def _fold_block(b_lt, b_rank, b_val, b_tomb, b_occ, lt, rank, val, tomb,
                valid):
    import jax.numpy as jnp
    best_lt = jnp.max(jnp.where(valid, lt, NEG), axis=0)
    at_best = valid & (lt == best_lt)
    best_rank = jnp.max(jnp.where(at_best, rank, -1), axis=0)
    row = jnp.argmax(at_best & (rank == best_rank), axis=0)
    any_row = jnp.any(valid, axis=0)
    w_val = jnp.take_along_axis(val, row[None], axis=0)[0]
    w_tomb = jnp.take_along_axis(tomb, row[None], axis=0)[0]
    row_wins = any_row & (~b_occ | (best_lt > b_lt)
                          | ((best_lt == b_lt) & (best_rank > b_rank)))
    return (jnp.where(row_wins, best_lt, b_lt),
            jnp.where(row_wins, best_rank, b_rank),
            jnp.where(row_wins, w_val, b_val),
            jnp.where(row_wins, w_tomb, b_tomb),
            b_occ | any_row)


def fold(base: dict, rows: dict, row_rank, base_rank: int,
         block: int = 1 << 13) -> dict:
    """Host lanes ``lt, rank, val, tomb, occupied`` of the folded store.

    ``base``: the base store's ``lt, val, tomb, occupied`` lanes [N];
    ``rows``: the replica rows' ``lt, node, val, tomb, valid`` [R, N],
    where ``node`` indexes ``row_rank`` (each row author's rank);
    ``base_rank``: the rank of the base store's author.
    """
    import jax
    import jax.numpy as jnp
    n = base["lt"].shape[0]
    rank_of = jnp.asarray(np.asarray(row_rank, np.int32))
    step = jax.jit(_fold_block)
    parts = []
    for k in range(0, n, block):
        sl = slice(k, min(n, k + block))
        b_lt = base["lt"][sl]
        parts.append(jax.device_get(step(
            b_lt, jnp.full(b_lt.shape, base_rank, jnp.int32),
            base["val"][sl], base["tomb"][sl], base["occupied"][sl],
            rows["lt"][:, sl], rank_of[rows["node"][:, sl]],
            rows["val"][:, sl], rows["tomb"][:, sl], rows["valid"][:, sl])))
    names = ("lt", "rank", "val", "tomb", "occupied")
    return {name: np.concatenate([p[i] for p in parts])
            for i, name in enumerate(names)}


def mismatches(got: dict, want: dict) -> int:
    """Keys whose record differs: occupancy, or, where occupied, any of
    logical time, author, value and tombstone."""
    occ = want["occupied"]
    differ = got["occupied"] != occ
    for lane in ("lt", "rank", "val", "tomb"):
        differ |= occ & (got[lane] != want[lane])
    return int(differ.sum())
