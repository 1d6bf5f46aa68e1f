"""Plain reference for the pod: the last-writer-wins fold of every
member's logged batches over the loaded store.

Active-active replicas that converge by joining whole states end on
the same record at every slot whatever the order of their joins: the
write with the largest ``(stamp, rank)``, where the stamp is the
batch's packed hybrid logical clock and the rank is the position of
the writing member's id in the sorted id table (the tie-break of equal
stamps). A slot no write touched keeps its loaded record. Each batch
of the log writes its slots under one stamp (putAll), so no two rows
of one slot share both stamp and rank.

Nothing here imports the program: only NumPy and the batch log.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

from cells.reference.lww_register import loaded_value

#: One logged batch: the writer's rank, the batch's stamp, its slots
#: and their values.
Batch = Tuple[int, int, np.ndarray, np.ndarray]


def fold(log: Sequence[Batch], base_lt: int, base_rank: int,
         seed: int) -> Dict[str, np.ndarray]:
    """The winner at every written slot: ``slots`` (ascending) with its
    ``lt``, ``rank`` and ``val``. The loaded record of each written
    slot (``base_lt``, ``base_rank``, `loaded_value`) takes part."""
    if not log:
        empty = np.zeros(0, np.int64)
        return {"slots": empty, "lt": empty, "rank": empty, "val": empty}
    slots = np.concatenate([np.asarray(s, np.int64) for _, _, s, _ in log])
    written = np.unique(slots)
    sizes = [len(s) for _, _, s, _ in log]
    slots = np.concatenate([written, slots])
    lt = np.concatenate([np.full(len(written), base_lt, np.int64),
                         np.repeat([t for _, t, _, _ in log], sizes)])
    rank = np.concatenate([np.full(len(written), base_rank, np.int64),
                           np.repeat([r for r, _, _, _ in log], sizes)])
    val = np.concatenate([loaded_value(written, seed)]
                         + [np.asarray(v, np.int64) for _, _, _, v in log])
    order = np.lexsort((rank, lt, slots))
    s = slots[order]
    last = order[np.append(np.flatnonzero(s[1:] != s[:-1]), len(s) - 1)]
    return {"slots": slots[last], "lt": lt[last], "rank": rank[last],
            "val": val[last]}


def mismatches(got: Dict[str, np.ndarray],
               want: Dict[str, np.ndarray]) -> int:
    """Written slots whose record is not the fold's winner. ``got``
    holds the store's ``occupied``, ``tomb``, ``lt``, ``rank`` (the
    writer's rank in the sorted id table) and ``val`` at
    ``want["slots"]``."""
    bad = (~np.asarray(got["occupied"], bool) | np.asarray(got["tomb"], bool)
           | (got["lt"] != want["lt"]) | (got["rank"] != want["rank"])
           | (got["val"] != want["val"]))
    return int(bad.sum())
