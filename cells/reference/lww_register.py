"""Plain reference for the served store: an LWW register per slot.

The store under test orders writes by the hybrid logical clock it
stamps them with on one replica, so for each slot the record that
stands is the last write stamped. A client cannot see the stamps, but
it sees real time: a write sent after another write was acknowledged
is stamped later and wins. So, per slot, with every write known by its
unique value, its send time and its acknowledgement time:

- write W is *superseded* by time t when some acknowledged write W2 was
  sent after W was acknowledged and was itself acknowledged before t;
  ``sup[W]`` is the earliest such acknowledgement (``inf`` if none);
- a read sent at s and answered at a may return W only if W was sent
  before a and ``sup[W] >= s`` (the last acknowledged value, or a
  concurrent later one);
- the slot's final record must be a W with ``sup[W] = inf``.

The loaded records count as a write sent and acknowledged before the
window. Writes that were refused or never answered may or may not have
landed: they are allowed answers and supersede nothing.

Nothing here imports the program: only NumPy and the request log.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

INF = float("inf")
#: The binop lane's reply statuses: a value (or an acknowledged write),
#: and no live record. Any other status is a refusal.
OK, OK_NULL = 0, 1


def splitmix64(x: np.ndarray) -> np.ndarray:
    """SplitMix64's output function over uint64 lanes (wrapping)."""
    z = np.asarray(x, np.uint64)
    with np.errstate(over="ignore"):
        z = z + np.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def loaded_value(slots: np.ndarray, seed: int) -> np.ndarray:
    """The value the set-up loads at each slot: a function of
    ``(slot, seed)`` alone."""
    key = splitmix64(np.uint64(seed % (1 << 64)))
    return splitmix64(np.asarray(slots, np.uint64) ^ key).view(np.int64)


class History:
    """Every write of one window, indexed per slot for the checks."""

    def __init__(self, slot, value, sent, acked, ok):
        """Parallel arrays over the window's writes; ``ok`` marks the
        acknowledged ones (NaN ``acked`` = never answered)."""
        self.by_value: Dict[int, int] = {}
        self.slot = np.asarray(slot, np.int64)
        self.value = np.asarray(value, np.int64)
        self.sent = np.asarray(sent, float)
        acked = np.asarray(acked, float)
        self.ok = np.asarray(ok, bool)
        self.acked = np.where(self.ok, acked, INF)
        self.sup = np.full(len(self.slot), INF)
        self.first_ack: Dict[int, float] = {}
        order = np.argsort(self.slot, kind="stable")
        bounds = np.flatnonzero(np.diff(self.slot[order])) + 1
        for idx in np.split(order, bounds) if len(order) else []:
            self._index_slot(idx)
        for i, v in enumerate(self.value.tolist()):
            self.by_value[v] = i

    def _index_slot(self, idx: np.ndarray) -> None:
        # Acknowledged writes by send time; suffix minimum of their
        # acknowledgement times answers "earliest ack among writes sent
        # after t" with one search.
        good = idx[self.ok[idx]]
        by_sent = good[np.argsort(self.sent[good])]
        sent_sorted = self.sent[by_sent]
        suffix = np.minimum.accumulate(self.acked[by_sent][::-1])[::-1]
        suffix = np.append(suffix, INF)
        at = np.searchsorted(sent_sorted, self.acked[idx], side="right")
        self.sup[idx] = suffix[at]
        self.first_ack[int(self.slot[idx[0]])] = float(suffix[0])

    def legal(self, slot: int, value: Optional[int], sent: float,
              answered: float, initial: Optional[int]) -> bool:
        """May a read of ``slot`` sent at ``sent`` and answered at
        ``answered`` return ``value`` (None = no live record)?"""
        i = self.by_value.get(value) if value is not None else None
        if i is not None and self.slot[i] == slot:
            return self.sent[i] < answered and self.sup[i] >= sent
        # The loaded record: superseded once any write to the slot is
        # acknowledged.
        return value == initial and \
            self.first_ack.get(slot, INF) >= sent

    def final_legal(self, slot: int, value: Optional[int],
                    initial: Optional[int]) -> bool:
        return self.legal(slot, value, INF, INF, initial)


def check_window(sched: dict, res: dict, final: Dict[int, Optional[int]],
                 sample: Dict[int, Optional[int]], seed: int,
                 preloaded: bool) -> dict:
    """Count what the window got wrong.

    ``sched``: the window's operations (``op``, ``slot``, ``value``);
    ``res``: what the clients saw (``sent``, ``acked``, ``status``,
    ``got``); ``final``: each written slot's value after the window
    (None = no live record); ``sample``: slots no write touched, as the
    store holds them after the window.
    """
    op, slot, value = sched["op"], sched["slot"], sched["value"]
    status, got = res["status"], res["got"]
    writes = op != 2
    answered = np.isfinite(res["acked"])
    hist = History(slot[writes], value[writes], res["sent"][writes],
                   res["acked"][writes],
                   answered[writes] & (status[writes] == OK))

    def initial(s):
        return int(loaded_value(np.array([s]), seed)[0]) if preloaded \
            else None

    read_wrong = 0
    for i in np.flatnonzero(~writes & answered):
        if status[i] == OK_NULL:
            v = None
        elif status[i] == OK:
            v = int(got[i])
        else:
            continue                      # refused: counted as failed
        if not hist.legal(int(slot[i]), v, res["sent"][i], res["acked"][i],
                          initial(int(slot[i]))):
            read_wrong += 1
    final_wrong = sum(
        not hist.final_legal(s, v, initial(s)) for s, v in final.items())
    want = {s: initial(s) for s in sample}
    load_wrong = sum(sample[s] != want[s] for s in sample)
    return {"read_wrong": read_wrong, "final_wrong": final_wrong,
            "load_wrong": load_wrong,
            "unanswered": int((~answered).sum())}
