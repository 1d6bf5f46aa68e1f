"""Compile-only rehearsal of the served path's device programs for a
described (not attached) TPU v5e, at the widths `chip_smoke.py` runs:
a 16M-slot store, 8-row changesets, a 64M-slot store key-sharded over
a 2x2 mesh. Nothing executes; the chip's compiler refuses what it
would refuse on the chip (misaligned blocks, int64 block indices,
64-bit max all-reduces, programs past HBM).

The topology is described only inside the module fixture below —
never at import — so every xdist worker collects the same tests and
only the worker running this file loads the TPU compiler.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from crdt_tpu.ops import pallas_merge as pm
from crdt_tpu.ops.dense import (DenseChangeset, DenseStore,
                                _compact_remap_jit, _gc_purge_jit)

N = 1 << 24          # chip_smoke.py's store
N27 = 1 << 27        # the YCSB deployment's store (cells/configs)
R = 8                # its merge_many peer rows
TILES = 64           # touched tiles in one ingest flush
HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _store(n, sharding):
    return DenseStore(*(_sds((n,), d, sharding) for d in (
        jnp.int64, jnp.int32, jnp.int64, jnp.int64, jnp.int32,
        jnp.bool_, jnp.bool_)))


def _planes(n, sharding):
    return pm.SplitStore(*(_sds((n // pm._LANE, pm._LANE), d, sharding)
                           for d in (jnp.int32, jnp.uint32, jnp.int32,
                                     jnp.int32, jnp.uint32, jnp.int32,
                                     jnp.int32, jnp.uint32, jnp.int32)))


def _changeset(r, n, sharding):
    return DenseChangeset(*(_sds((r, n), d, sharding) for d in (
        jnp.int64, jnp.int32, jnp.int64, jnp.bool_, jnp.bool_)))


def _peak(compiled):
    """Bytes the program holds at once, its arguments included (an
    upper bound: every buffer counted live for the whole program)."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def _compile(fn, *args, kernel=True):
    # A jitted fn is lowered as it is, so its donation holds.
    fn = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = fn.lower(*args).compile()
    assert _peak(compiled) < HBM_BYTES
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _scalars(sharding):
    return (_sds((), jnp.int64, sharding), _sds((), jnp.int32, sharding),
            _sds((), jnp.int64, sharding))


@pytest.mark.parametrize("held", ["lanes", "planes"])
def test_model_fanin_batch_compiles(one_chip, held):
    # DenseCrdt._dispatch_pallas: the one fused merge dispatch, on the
    # store as either form holds it.
    store = _store(N, one_chip) if held == "lanes" else _planes(N, one_chip)
    _compile(lambda *a: pm.model_fanin_batch.__wrapped__(
        *a, chunk_rows=R), store,
        _changeset(R, N, one_chip), *_scalars(one_chip))


@pytest.mark.parametrize("kind", ["batch", "stream"])
def test_split_fanin_kernels_compile(one_chip, kind):
    sst = pm.SplitStore(*(_sds((N,), d, one_chip) for d in (
        jnp.int32, jnp.uint32, jnp.int32, jnp.int32, jnp.uint32,
        jnp.int32, jnp.int32, jnp.uint32, jnp.int32)))
    scs = pm.SplitChangeset(*(_sds((R, N), d, one_chip) for d in (
        jnp.int32, jnp.uint32, jnp.int32, jnp.int32, jnp.uint32,
        jnp.int32)))
    if kind == "batch":
        fn = lambda *a: pm.pallas_fanin_batch.__wrapped__(  # noqa: E731
            *a, chunk_rows=R)
    else:
        fn = lambda *a: pm.pallas_fanin_stream.__wrapped__(  # noqa: E731
            *a, n_chunks=4)
    _compile(fn, sst, scs, *_scalars(one_chip))


def test_ingest_scatter_tiles_compiles(one_chip):
    # The served flush: the touched-tile kernel on resident planes.
    from crdt_tpu.ops.pallas_scatter import _planes_jit
    rows = TILES * pm._SB
    lanes = [_sds((rows, pm._LANE), d, one_chip) for d in (
        jnp.int32, jnp.int64, jnp.int64, jnp.int32)]
    _compile(_planes_jit(True, False),
             _planes(N, one_chip), _sds((TILES,), jnp.int32, one_chip),
             *lanes, _sds((1,), jnp.int32, one_chip))


def test_resident_flush_compiles_in_place_at_2_27(one_chip):
    # The served store's flush on resident planes (the YCSB config's
    # 2^27 slots), donated: it must hold less than the donated 1-D
    # ingest scatter at 2^27 did (7,248,473,600 B).
    from crdt_tpu.ops.pallas_scatter import _planes_jit
    rows = TILES * pm._SB
    lanes = [_sds((rows, pm._LANE), d, one_chip) for d in (
        jnp.int32, jnp.int64, jnp.int64, jnp.int32)]
    compiled = _planes_jit(True, False).lower(
        _planes(N27, one_chip), _sds((TILES,), jnp.int32, one_chip),
        *lanes, _sds((1,), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 7_248_473_600
    assert mem.alias_size_in_bytes >= 9 * 4 << 27
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("to", ["lanes", "planes"])
def test_store_moves_between_forms_at_2_27(one_chip, to):
    # A cold path moves the resident planes to lanes, and the next
    # flush or point read moves them back. The model drops the old
    # form, so a move is the one time it holds the store twice: the
    # planes (36 B a slot) and the lanes (34 B a slot), plus the
    # move's own scratch, well inside HBM at the YCSB size.
    from crdt_tpu.ops import pallas_scatter as ps
    if to == "lanes":
        fn, arg = ps._from_planes_jit, _planes(N27, one_chip)
    else:
        fn, arg = ps._to_planes_jit, _store(N27, one_chip)
    peak = _peak(_compile(fn, arg, kernel=False))
    assert peak <= (36 + 34) * N27 + (1 << 30)


def test_point_read_compiles_at_2_27(one_chip):
    from crdt_tpu.ops.pallas_scatter import _gather_jit
    compiled = _gather_jit.lower(_planes(N27, one_chip),
                                 _sds((), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    # One slot's words out; nothing store-sized besides the planes.
    assert mem.output_size_in_bytes <= 4096
    assert mem.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("n", [N, N27])
def test_gc_purge_compiles(one_chip, n):
    # Donated, on lanes alone (a cold path drops the planes first).
    compiled = _compile(_gc_purge_jit(True, None), _store(n, one_chip),
                        _sds((), jnp.int64, one_chip), kernel=False)
    assert compiled.memory_analysis().alias_size_in_bytes >= 34 * n


def test_compact_remap_compiles(one_chip):
    from crdt_tpu.models.dense_crdt import DenseCrdt
    span = _sds((1,), jnp.int64, one_chip)
    _compile(_compact_remap_jit(True, DenseCrdt.DIGEST_LEAF_WIDTH, False,
                                None),
             _store(N, one_chip), span, span, kernel=False)


def test_sharded_pallas_fanin_compiles_on_2x2(topo):
    from crdt_tpu.parallel import (KEY_AXIS, REPLICA_AXIS,
                                   changeset_sharding,
                                   make_sharded_pallas_fanin,
                                   store_sharding)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4),
                (REPLICA_AXIS, KEY_AXIS))
    step = make_sharded_pallas_fanin(mesh, chunk_rows=R)
    compiled = _compile(step.__wrapped__,
                        _store(4 * N, store_sharding(mesh)),
                        _changeset(R, 4 * N, changeset_sharding(mesh)),
                        *_scalars(NamedSharding(mesh, P())))
    assert "all-reduce" in compiled.as_text()


@pytest.mark.parametrize("n", [N, N27])
def test_collective_join_compiles_on_2x2(topo, n):
    from crdt_tpu.parallel.collective import (MEMBER_AXIS,
                                              make_collective_join)
    from crdt_tpu.models.dense_crdt import DenseCrdt
    mesh = Mesh(np.asarray(topo.devices), (MEMBER_AXIS,))
    step = make_collective_join(mesh, False, DenseCrdt.DIGEST_LEAF_WIDTH,
                                donate=True)
    rep, mem = NamedSharding(mesh, P()), NamedSharding(mesh,
                                                       P(MEMBER_AXIS))
    # One whole member per chip: [4*n] lanes over the members; at 2^27
    # the pod4.join cell's members.
    compiled = _compile(step.jitted, _store(4 * n, mem),
                        _sds((4,), jnp.int64, mem),
                        _sds((4,), jnp.int32, mem),
                        _sds((), jnp.int64, rep), kernel=False)
    assert "all-reduce" in compiled.as_text()
    if n == N27:
        # pod4.join's chips are nearly full: the join may hold no
        # more than it did when its digest levels were built with
        # gathers (10,740,893,696 B).
        assert _peak(compiled) <= 10_740_893_696


def test_digest_tree_compiles_at_2_27(one_chip):
    # The leaf fold once padded a [n/8, 8] u64 reshape to 128 lanes
    # (16.5 GB at 2^27); the 1-D window fold keeps a 2^27-slot
    # store's digest within the chip. The interior levels read their
    # children with slices: a gather there ran as index-array fusions,
    # most of the digest's time.
    from crdt_tpu.ops.digest import _digest_tree_jit
    lane = functools.partial(_sds, (N27,), sharding=one_chip)
    compiled = _compile(_digest_tree_jit(8, False), lane(jnp.int64),
                        lane(jnp.int64), lane(jnp.bool_), lane(jnp.bool_),
                        kernel=False)
    assert "gather(" not in compiled.as_text()
