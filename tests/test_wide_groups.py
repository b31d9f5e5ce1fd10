"""Grouped aggregation at any group count, against the reference row loop.

The executor's grouped fold (numpy, and pallas with its kernels in
interpret mode) must give what ``GroupState``'s row loop over key tuples
gives on the filtered rows: the same groups in first-seen row order, exact
counts and integer sums, min/max equal bit for bit, and float sums equal to
the row loop's float64 fold.  Float32, int32 and int64 keys run at 1, 256
(the one-hot fold's cap), 257 and 4,096 groups and at one group per row
(the windowed fold above the cap); float keys holding a NaN or ``-0.0``
leave the fused fold for that morsel and still match; and the windowed
fold compiles for a TPU v5e at the executor's largest morsel.
"""

import functools
import os

import numpy as np
import pytest

from repro.core.backend import _SEG_GROUP_CAP, get_backend
from repro.core.batch import RecordBatch
from repro.core.dag import Dag
from repro.core.executor import ExecutorConfig, ExecutorStats, execute_parallel
from repro.core.expr import col
from repro.core.operators import GroupState
from repro.core.sdf import StreamingDataFrame

ROWS = 5000
AGGS = {
    "n": {"fn": "count"},
    "sv": {"fn": "sum", "column": "v"},
    "sw": {"fn": "sum", "column": "w"},
    "si": {"fn": "sum", "column": "i"},
    "lo": {"fn": "min", "column": "v"},
    "hi": {"fn": "max", "column": "v"},
    "ilo": {"fn": "min", "column": "i"},
    "ihi": {"fn": "max", "column": "i"},
}
KEYS = ["a", "b"]


def _keys(dtype, groups: int):
    """``groups`` distinct (a, b) pairs of ``dtype``."""
    g = np.arange(groups)
    if dtype == np.float32:
        return ((g // 240) * 1.5 - 90.0).astype(np.float32), ((g % 240) * 1.5).astype(np.float32)
    if dtype == np.int64:
        return (g // 37 * 1_000_003 - 2**40).astype(np.int64), (g % 37 - 18).astype(np.int64)
    return (g // 37 * 7 - 1000).astype(np.int32), (g % 37 - 18).astype(np.int32)


def _batch(rng, dtype, groups: int, rows: int = ROWS) -> RecordBatch:
    """Rows of ``groups`` groups in a seeded order (every group has a row);
    ``w`` is ``v``'s square, as a projection would make it."""
    ka, kb = _keys(dtype, groups)
    which = np.concatenate([rng.permutation(groups), rng.integers(0, groups, rows - groups)])
    rng.shuffle(which)
    v = rng.standard_normal(rows).astype(np.float32) * 40
    return RecordBatch.from_pydict(
        {"a": ka[which], "b": kb[which], "v": v, "w": v * v, "i": rng.integers(-(2**20), 2**20, rows).astype(np.int32)}
    )


def _dag(keys=KEYS, filtered=True, aggs=AGGS) -> Dag:
    bld = Dag.build()
    node = bld.source("dacp://h:1/d")
    if filtered:
        node = bld.add("filter", {"predicate": col("v") > -30.0}, [node])
    return bld.finish(bld.add("aggregate", {"keys": list(keys), "aggs": aggs}, [node]))


def _run(batch: RecordBatch, backend: str, morsel_rows: int, filtered=True, aggs=AGGS):
    def sdf():
        def gen():
            for s in range(0, batch.num_rows, morsel_rows):
                yield batch.slice(s, s + morsel_rows)

        return StreamingDataFrame(batch.schema, gen)

    stats = ExecutorStats()
    cfg = ExecutorConfig(num_workers=2, morsel_rows=morsel_rows, backend=backend)
    out = execute_parallel(_dag(filtered=filtered, aggs=aggs), lambda n: sdf(), cfg, stats=stats).collect()
    return out, stats.progress()


def _reference(batch: RecordBatch, morsel_rows: int, filtered=True) -> RecordBatch:
    """The row loop: each morsel folded by key tuple, merged in morsel order."""
    from repro.core.operators import agg_out_fields
    from repro.core.schema import Schema

    total = GroupState(KEYS, AGGS, "full", batch.schema)
    for s in range(0, batch.num_rows, morsel_rows):
        m = batch.slice(s, s + morsel_rows)
        if filtered:
            m = m.filter(np.asarray(m.column("v").values) > -30.0)
        st = GroupState(KEYS, AGGS, "full", batch.schema)
        st.update(m)
        total.merge(st)
    return total.result(Schema(agg_out_fields(batch.schema, KEYS, AGGS, "full")))


def _assert_same(got: RecordBatch, want: RecordBatch) -> None:
    assert got.schema.names == want.schema.names
    assert got.num_rows == want.num_rows
    for name in want.schema.names:
        g, w = np.asarray(got.column(name).values), np.asarray(want.column(name).values)
        assert g.dtype == w.dtype, name
        assert g.tobytes() == w.tobytes(), f"{name} differs"


@pytest.mark.parametrize("groups", [1, _SEG_GROUP_CAP, _SEG_GROUP_CAP + 1, 4096, ROWS], ids=lambda g: f"g{g}")
@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.int64], ids=["f32", "i32", "i64"])
@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_grouped_fold_matches_the_row_loop(backend, dtype, groups):
    batch = _batch(np.random.default_rng([groups, np.dtype(dtype).itemsize]), dtype, groups)
    got, prog = _run(batch, backend, ROWS)
    want = _reference(batch, ROWS)
    _assert_same(got, want)
    # first-seen order of the surviving rows, and one row per group
    kept = batch.filter(np.asarray(batch.column("v").values) > -30.0)
    pairs = list(zip(kept.column("a").values.tolist(), kept.column("b").values.tolist()))
    assert list(zip(got.column("a").values.tolist(), got.column("b").values.tolist())) == list(dict.fromkeys(pairs))
    assert prog["groups"] == want.num_rows
    if backend == "pallas":
        assert prog["fused_launches"] == prog["morsels_done"] == 1  # float keys and > 256 groups stay fused


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_groups_spanning_morsels_merge_into_one_table(backend):
    """1,500 float-keyed groups over five morsels of 1,000 rows: the breaker
    maps each morsel's groups into the request's table."""
    batch = _batch(np.random.default_rng(7), np.float32, 1500)
    got, prog = _run(batch, backend, 1000)
    _assert_same(got, _reference(batch, 1000))
    assert prog["morsels_done"] == 5 and prog["groups"] == got.num_rows
    if backend == "pallas":
        assert prog["fused_launches"] == 5


def test_float64_sums_are_not_float32_sums():
    """The comparison above is exact, so a float32 accumulation fails it."""
    batch = _batch(np.random.default_rng(3), np.float32, 300)
    want = _reference(batch, ROWS, filtered=False)
    v = np.asarray(batch.column("v").values)
    gidx = GroupState(KEYS, {}, "full", batch.schema, vectorized=True)._factorize(batch)
    f32 = np.zeros(want.num_rows, np.float32)
    np.add.at(f32, gidx, v)
    assert not np.array_equal(f32.astype(np.float64), np.asarray(want.column("sv").values))


@pytest.mark.parametrize("odd", ["nan", "negzero"])
@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_nan_and_negative_zero_keys_fall_back_and_match(backend, odd):
    """A morsel whose float key holds a NaN (never equal to itself) or a
    ``-0.0`` (equal to ``+0.0``, first seen wins) groups through the row
    loop; the morsels without one stay fused."""
    batch = _batch(np.random.default_rng(11), np.float32, 600, rows=2000)
    cols = {n: np.array(batch.column(n).values) for n in batch.schema.names}
    if odd == "nan":
        cols["a"][[1500, 1700]] = np.nan
    else:
        zero = np.flatnonzero(cols["b"] == 0.0)
        assert zero[0] < 1000 < zero[-1]
        cols["b"][zero[zero >= 1000]] = -0.0  # the first morsel saw +0.0 first
    batch = RecordBatch.from_pydict(cols)
    bk = get_backend(backend)
    rejects = getattr(bk, "key_rejects", 0)
    got, prog = _run(batch, backend, 1000, filtered=False)
    _assert_same(got, _reference(batch, 1000, filtered=False))
    if backend == "pallas":
        assert bk.key_rejects - rejects == 1  # the second morsel only
        assert prog["fused_launches"] == 1 and prog["morsels_done"] == 2


@pytest.mark.parametrize("groups", [_SEG_GROUP_CAP + 1, 3000], ids=lambda g: f"g{g}")
def test_per_op_segment_reduce_above_the_cap(groups):
    """More than 256 groups a morsel in an aggregate the fused plan declines
    (int64 min/max): the pallas backend folds every morsel op by op on the
    numpy path, a fresh state and then one with groups already, and matches
    the numpy backend bit for bit."""
    aggs = {**AGGS, "alo": {"fn": "min", "column": "a"}, "bhi": {"fn": "max", "column": "b"}}
    batch = _batch(np.random.default_rng(groups), np.int64, groups, rows=4000)
    got, prog = _run(batch, "pallas", 2500, aggs=aggs)
    want, _ = _run(batch, "numpy", 2500, aggs=aggs)
    _assert_same(got, want)
    assert prog["fused_launches"] == 0 and prog["morsels_done"] == 2
    assert prog["groups"] == want.num_rows


def test_columnar_keys_map_like_the_row_loop():
    """Keys as columns: new groups append in first-seen order, known keys
    map to their ids, and a merge of another table's groups maps them."""
    batch = _batch(np.random.default_rng(5), np.int64, 40, rows=300)
    ref = GroupState(KEYS, AGGS, "full", batch.schema)
    vec = GroupState(KEYS, AGGS, "full", batch.schema, vectorized=True)
    for s in range(0, 300, 70):
        m = batch.slice(s, s + 70)
        assert vec._factorize(m).tolist() == ref._intern_rows(list(zip(*[m.column(k).to_pylist() for k in KEYS]))).tolist()
    assert vec.key_rows == ref.key_rows and vec._rows is None
    other = GroupState(KEYS, AGGS, "full", batch.schema, vectorized=True)
    other.update(batch.slice(100, 300))
    idx = vec.merge_indexed(other)
    assert [vec.key_rows[i] for i in idx.tolist()] == other.key_rows


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def test_windowed_fold_compiles_for_a_tpu(one_chip):
    """The windowed fold (step table in scalar memory, one 256-group window
    of accumulators resident a step) compiles with Mosaic at the largest
    morsel, 262,144 rows: the clim cell's chain (six squares, twelve
    float32 min/max, float sums through the compaction)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import fused_pipeline
    from repro.kernels.fused_pipeline import SUM_ROW_CAP

    n = SUM_ROW_CAP
    squares = tuple(("mul", ("col", i), ("col", i)) for i in range(6))
    fn = functools.partial(
        fused_pipeline.fused_chain_tiles,
        op="eq",
        kind="i32",
        descrs_f=squares,
        descrs_i=(),
        csums=(),
        fns_f=("min", "max") * 6,
        fns_i=("min",),
        with_gidx=True,
        segmented=True,
        ngroups=256,
        tile=256,
        interpret=False,
    )
    i32, f32 = jnp.int32, jnp.float32
    shapes = [((4,), i32), ((n, 1), i32), ((n,), i32), ((n, 6), i32), ((n, 8), i32), ((n, 12), f32), ((n, 1), i32),
              ((n, 6), f32), ((n, 1), i32), ((4 * (n // 256),), i32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()
