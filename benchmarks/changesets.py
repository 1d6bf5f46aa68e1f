"""Shared inputs of the hand-run kernel tools (`probe_kernel.py`,
`validate_on_chip.py`): a device-generated random changeset and the
sequential XLA fan-in fold that the Pallas kernels are checked against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from crdt_tpu.hlc import SHIFT
from crdt_tpu.ops.dense import DenseChangeset, fanin_step

_MILLIS = 1_700_000_000_000


def make_changeset(rc: int, n: int, seed: int, tomb_ratio: float = 0.3,
                   millis_spread: int = 1000, counter_spread: int = 4,
                   fill: float = 0.8) -> DenseChangeset:
    """Device-generated random changeset. Defaults model the realistic
    sparse-delta shape (mixed writers, 30% tombstones, 80% fill); the
    knobs produce the BASELINE.json stress configs:

    - ``tomb_ratio=0.5`` — tombstone-heavy merge (config 3).
    - ``millis_spread=1, counter_spread=2`` — HLC tie-break stress: most
      records collide on logicalTime and resolve via the node ordinal
      (config 4, hlc.dart:158-161).
    """
    k = jax.random.split(jax.random.key(seed), 5)
    lt = ((_MILLIS + jax.random.randint(k[0], (rc, n), 0, millis_spread,
                                        jnp.int64))
          << SHIFT) + jax.random.randint(k[1], (rc, n), 0, counter_spread,
                                         jnp.int64)
    return DenseChangeset(
        lt=lt,
        node=jax.random.randint(k[2], (rc, n), 1, 9, jnp.int32),
        val=lt,  # payload content doesn't affect the join cost
        tomb=jax.random.uniform(k[3], (rc, n)) < tomb_ratio,
        valid=jax.random.uniform(k[4], (rc, n)) < fill,
    )


def build_stream_fn(n_chunks: int):
    """fori_loop of XLA-fold fan-in steps; each chunk's clocks advance
    by 1ms so every round has genuine winners (steady-state write
    path)."""

    @jax.jit
    def run(store, cs, canonical, local_node, wall):
        def body(i, carry):
            st, canon = carry
            cs_i = cs._replace(lt=cs.lt + (i << SHIFT))
            st2, res = fanin_step(st, cs_i, canon, local_node, wall)
            return (st2, res.new_canonical)

        return jax.lax.fori_loop(0, n_chunks, body, (store, canonical))

    return run
