"""Compile-only sizing of the cells' device programs for a described v5e.

Nothing runs and no chip is needed: each program the cells' windows
drive is compiled for one chip of a described ``v5e:2x2`` topology and
its ``memory_analysis()`` is printed, one JSON line per program. The
configuration files under ``cells/configs/`` record these bytes beside
the sizes they justify.

    JAX_PLATFORMS=cpu python3 cells/memcheck.py
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _report(name: str, compiled, **shape) -> None:
    m = compiled.memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes",
              "generated_code_size_in_bytes")
    row = {k: int(getattr(m, k)) for k in fields}
    # What the program holds at once: its arguments, the outputs that
    # do not alias an argument, and its scratch.
    row["peak_bytes"] = (row["argument_size_in_bytes"]
                         + row["output_size_in_bytes"]
                         - row["alias_size_in_bytes"]
                         + row["temp_size_in_bytes"])
    print(json.dumps({"program": name, **shape, **row}), flush=True)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_enable_compilation_cache", False)
    from crdt_tpu.ops.dense import DenseChangeset, DenseStore
    from crdt_tpu.ops.pallas_merge import _model_fanin_batch_jit
    from crdt_tpu.ops.pallas_scatter import _scatter_jit

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def store_of(n):
        return DenseStore(lt=sds((n,), jnp.int64), node=sds((n,), jnp.int32),
                          val=sds((n,), jnp.int64),
                          mod_lt=sds((n,), jnp.int64),
                          mod_node=sds((n,), jnp.int32),
                          occupied=sds((n,), bool), tomb=sds((n,), bool))

    # The served store's ingest commit (touched-tile Mosaic scatter),
    # first commit (store handed in at construction, so not donated)
    # and every later one (donated), at the largest tile count a tick
    # reaches in the cells.
    sb, lane = 8, 1024
    for log2n in (28, 27):
        n = 1 << log2n
        for donate in (False, True):
            for tiles in (1, 64):
                args = (store_of(n), sds((tiles,), jnp.int32),
                        sds((tiles * sb, lane), jnp.int32),
                        sds((tiles * sb, lane), jnp.int64),
                        sds((tiles * sb, lane), jnp.int64),
                        sds((tiles * sb, lane), jnp.int32),
                        sds((1,), jnp.int32))
                try:
                    c = _scatter_jit(donate, False).lower(*args).compile()
                except Exception as e:   # the chip's compiler refused it
                    print(json.dumps({"program": "ingest_scatter_tiles",
                                      "n_slots": n, "donate": donate,
                                      "tiles": tiles,
                                      "refused": str(e)[:300]}))
                    continue
                _report("ingest_scatter_tiles", c, n_slots=n,
                        donate=donate, tiles=tiles)

    # The fan-in: one [1024, N] changeset through the fused Mosaic
    # merge, in chunks of 8 replica rows (the model's chunk size).
    for log2n in (19, 18):
        n = 1 << log2n
        r = 1024
        cs = DenseChangeset(lt=sds((r, n), jnp.int64),
                            node=sds((r, n), jnp.int32),
                            val=sds((r, n), jnp.int64),
                            tomb=sds((r, n), bool),
                            valid=sds((r, n), bool))
        try:
            c = _model_fanin_batch_jit.lower(
                store_of(n), cs, sds((), jnp.int64), sds((), jnp.int32),
                sds((), jnp.int64), chunk_rows=8, interpret=False,
                value_width=64).compile()
        except Exception as e:
            print(json.dumps({"program": "model_fanin_batch", "n_slots": n,
                              "replicas": r, "refused": str(e)[:300]}))
            continue
        _report("model_fanin_batch", c, n_slots=n, replicas=r,
                changeset_bytes=r * n * 22)
    return 0


if __name__ == "__main__":
    sys.exit(main())
