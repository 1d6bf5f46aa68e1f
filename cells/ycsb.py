"""YCSB request streams for the served cells, from a seed (NumPy only).

The arithmetic follows YCSB's core workload generators (Cooper et al.,
SoCC 2010; the `site.ycsb.generator` package):

- ``scrambled_zipfian``: `ScrambledZipfianGenerator` with the Zipfian
  constant 0.99. YCSB draws a rank from a Zipfian over 10^10 items with
  the precomputed zeta constant below and scatters it over the record
  space with FNV-1a 64 (`Utils.fnvhash64`), so the hot keys are spread
  and no zeta sum over the record count is ever taken.
- ``hashed_inserts``: the load phase's ``insertorder=hashed`` key order,
  ``fnvhash64(keynum)``.

Arrivals are open loop. ``schedule`` gives every seed the same multiset
of inter-arrival gaps (drawn once from a fixed generator) in a
seed-dependent order, so seeds differ in order and keys, not in the
amount or burstiness of the work. This module imports no JAX: the load
generator processes use it before the chip is taken.
"""

from __future__ import annotations

import numpy as np

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)
ZIPF_ITEM_COUNT = 10_000_000_000
ZIPF_ZETAN = 26.46902820178302      # zeta(10^10, 0.99), as YCSB ships it
ZIPF_THETA = 0.99
GAP_SEED = 0x59C5B                  # fixed: the gap multiset of every seed

OP_PUT, OP_GET = 0, 2               # the binop lane's opcodes


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB `Utils.fnvhash64` over int64 lanes: FNV-1a over the eight
    low-to-high octets, then ``Math.abs`` of the signed result."""
    v = np.asarray(v, np.int64).view(np.uint64).copy()
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            v >>= np.uint64(8)
            h *= FNV_PRIME_64
    return np.abs(h.view(np.int64))


def zipfian_ranks(u: np.ndarray) -> np.ndarray:
    """YCSB `ZipfianGenerator.nextLong` for uniform draws ``u``."""
    items, theta, zetan = ZIPF_ITEM_COUNT, ZIPF_THETA, ZIPF_ZETAN
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    ranks = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    ranks = np.where(uz < 1.0 + 0.5 ** theta, 1, ranks)
    return np.where(uz < 1.0, 0, ranks)


def scrambled_zipfian(rng: np.random.Generator, n: int,
                      records: int) -> np.ndarray:
    """``n`` keys in ``[0, records)`` from YCSB's scrambled Zipfian."""
    return fnvhash64(zipfian_ranks(rng.random(n))) % records


def hashed_inserts(first: int, n: int, records: int) -> np.ndarray:
    """Keys ``first .. first + n - 1`` in YCSB's hashed insert order,
    folded onto the record space (slots)."""
    return fnvhash64(np.arange(first, first + n, dtype=np.int64)) % records


def arrival_offsets(rate: float, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Poisson arrivals at ``rate``/s over ``seconds``: the same
    ``round(rate * seconds)`` exponential gaps for every seed, in the
    order ``rng`` draws, rescaled to end inside the window."""
    k = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(GAP_SEED).exponential(1.0 / rate, k)
    gaps *= seconds / gaps.sum() * (k / (k + 1))
    return np.cumsum(rng.permutation(gaps))


def schedule(mix: dict, records: int, seed: int, seconds: float) -> dict:
    """The whole window's operations, in due order: ``due`` (s from the
    window's start), ``op`` (binop opcode), ``slot``, ``value`` (the
    int64 written; unused for reads) and ``session``."""
    rng = np.random.default_rng([seed, 0x7C311])
    due = arrival_offsets(float(mix["rate_ops_s"]), seconds, rng)
    k = len(due)
    n_reads = int(round(float(mix["read_share"]) * k))
    op = np.full(k, OP_PUT, np.uint8)
    op[rng.permutation(k)[:n_reads]] = OP_GET
    keys = mix["keys"]
    if keys == "scrambled_zipfian":
        slot = scrambled_zipfian(rng, k, records)
    elif keys == "hashed_inserts":
        # Each seed inserts its own stretch of key numbers.
        first = int(rng.integers(0, 1 << 40))
        slot = hashed_inserts(first, k, records)
    else:
        raise ValueError(f"unknown key chooser {keys!r}")
    value = rng.integers(-(1 << 63), (1 << 63) - 1, size=k, dtype=np.int64)
    session = np.arange(k, dtype=np.int64) % int(mix["sessions"])
    return {"due": due, "op": op, "slot": slot.astype(np.int64),
            "value": value, "session": session}
