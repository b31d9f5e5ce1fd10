"""Morsel-driven parallel executor: parity with the reference pull chain,
streaming preservation (first output before last input morsel), ordering,
breakers (aggregate/join), serial tails (limit/rebatch), error propagation,
and engine/server integration."""

import threading
import time

import numpy as np
import pytest

from repro.core.batch import RecordBatch, concat_batches
from repro.core.dag import Dag
from repro.core.errors import SchemaError
from repro.core.executor import ExecutorConfig, execute_parallel, prefetch_sdf
from repro.core.expr import col
from repro.core.operators import execute
from repro.core.sdf import StreamingDataFrame


def _table(n=10_000, seed=0):
    rng = np.random.default_rng(seed)
    return RecordBatch.from_pydict(
        {
            "k": rng.integers(0, 23, n),
            "x": rng.standard_normal(n),
            "tag": np.asarray([f"t{i % 5}" for i in range(n)]),
        }
    )


def _sdf(batch, rows=1000):
    def gen():
        for s in range(0, batch.num_rows, rows):
            yield batch.slice(s, s + rows)

    return StreamingDataFrame(batch.schema, gen)


def _cfg(workers, **kw):
    kw.setdefault("morsel_rows", 512)
    kw.setdefault("backend", "numpy")
    return ExecutorConfig(num_workers=workers, **kw)


def _agg_dict(pd, keys):
    vals = [pd[k] for k in keys]
    other = [c for c in pd if c not in keys]
    return {tuple(kt): tuple(pd[c][i] for c in other) for i, kt in enumerate(zip(*vals))}


# ---------------------------------------------------------------------------
# parity with the reference pull chain
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("workers", [1, 2, 4])
def test_pipeline_parity_with_reference(workers):
    full = _table()
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > 0.0}, [s])
    p = bld.add("project", {"exprs": {"y": col("x") * 2.0 + 1.0}, "keep": True}, [f])
    sel = bld.add("select", {"columns": ["k", "y"]}, [p])
    dag = bld.finish(sel)

    ref = execute(dag, lambda n: _sdf(full)).collect()
    got = execute_parallel(dag, lambda n: _sdf(full), _cfg(workers)).collect()
    assert got.schema.names == ref.schema.names
    # streaming ops preserve row order exactly, regardless of worker count
    for name in ref.schema.names:
        assert np.array_equal(got.column(name).to_numpy(), ref.column(name).to_numpy())


@pytest.mark.parametrize("workers", [1, 4])
def test_aggregate_parity(workers):
    full = _table()
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > -0.5}, [s])
    a = bld.add(
        "aggregate",
        {
            "keys": ["k"],
            "aggs": {
                "n": {"fn": "count"},
                "sx": {"fn": "sum", "column": "x"},
                "mx": {"fn": "mean", "column": "x"},
                "lo": {"fn": "min", "column": "k"},
                "hi": {"fn": "max", "column": "k"},
            },
        },
        [f],
    )
    dag = bld.finish(a)
    ref_pd = execute(dag, lambda n: _sdf(full)).collect().to_pydict()
    got_pd = execute_parallel(dag, lambda n: _sdf(full), _cfg(workers)).collect().to_pydict()
    # group order matches the reference first-seen order exactly
    assert got_pd["k"] == ref_pd["k"]
    ref, got = _agg_dict(ref_pd, ["k"]), _agg_dict(got_pd, ["k"])
    assert set(got) == set(ref)
    for kt in ref:
        rn, rsx, rmx, rlo, rhi = ref[kt]
        gn, gsx, gmx, glo, ghi = got[kt]
        assert gn == rn and glo == rlo and ghi == rhi
        assert gsx == pytest.approx(rsx)
        assert gmx == pytest.approx(rmx)


def test_aggregate_group_order_deterministic_for_string_keys():
    """String keys keep first-seen group order (the reference semantics the
    v2 session tests rely on), at any worker count."""
    full = _table()
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    a = bld.add("aggregate", {"keys": ["tag"], "aggs": {"n": {"fn": "count"}}}, [s])
    dag = bld.finish(a)
    for workers in (1, 4):
        got = execute_parallel(dag, lambda n: _sdf(full), _cfg(workers)).collect().to_pydict()
        assert got["tag"] == ["t0", "t1", "t2", "t3", "t4"]
        assert got["n"] == [full.num_rows // 5] * 5


@pytest.mark.parametrize("workers", [1, 4])
def test_join_and_union(workers):
    full = _table(4000)
    bld = Dag.build()
    sl = bld.source("dacp://h:1/left")
    sr = bld.source("dacp://h:1/right")
    fl = bld.add("filter", {"predicate": col("x") > 0.0}, [sl])
    sell = bld.add("select", {"columns": ["k", "x"]}, [fl])
    ar = bld.add("aggregate", {"keys": ["k"], "aggs": {"n": {"fn": "count"}}}, [sr])
    j = bld.add("join", {"on": ["k"]}, [sell, ar])
    dag = bld.finish(j)

    def resolver(node):
        return _sdf(full)

    ref = execute(dag, resolver).collect()
    got = execute_parallel(dag, resolver, _cfg(workers)).collect()
    assert got.num_rows == ref.num_rows
    assert got.schema.names == ref.schema.names
    for name in ref.schema.names:
        assert np.array_equal(got.column(name).to_numpy(), ref.column(name).to_numpy())

    # union of two branches preserves branch-major order
    bld2 = Dag.build()
    a = bld2.source("dacp://h:1/a")
    b = bld2.source("dacp://h:1/b")
    u = bld2.add("union", {}, [a, b])
    f2 = bld2.add("filter", {"predicate": col("x") > -10.0}, [u])
    dag2 = bld2.finish(f2)
    got2 = execute_parallel(dag2, lambda n: _sdf(full), _cfg(workers)).collect()
    expect = concat_batches([full, full])
    assert np.array_equal(got2.column("k").to_numpy(), expect.column("k").to_numpy())


@pytest.mark.parametrize("workers", [1, 4])
def test_limit_and_rebatch_serial_tails(workers):
    full = _table(5000)
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > -10.0}, [s])
    r = bld.add("rebatch", {"rows": 300}, [f])
    lim = bld.add("limit", {"n": 1234}, [r])
    dag = bld.finish(lim)
    got = execute_parallel(dag, lambda n: _sdf(full), _cfg(workers))
    batches = list(got.iter_batches())
    assert sum(b.num_rows for b in batches) == 1234
    assert all(b.num_rows <= 300 for b in batches)
    cat = concat_batches(batches)
    assert np.array_equal(cat.column("k").to_numpy(), full.column("k").to_numpy()[:1234])


# ---------------------------------------------------------------------------
# streaming semantics (the acceptance assertion)
# ---------------------------------------------------------------------------
def test_first_output_before_last_input_morsel():
    """The parallel executor must stream: its first output batch is yielded
    while later input morsels are still unconsumed (backpressure window)."""
    full = _table(64_000)
    consumed = []

    def gen():
        for i in range(64):
            consumed.append(i)
            yield full.slice(i * 1000, (i + 1) * 1000)

    sdf = StreamingDataFrame(full.schema, gen)
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > -10.0}, [s])
    dag = bld.finish(f)
    out = execute_parallel(dag, lambda n: sdf, _cfg(4, morsel_rows=1000))
    it = out.iter_batches()
    first = next(it)
    assert first.num_rows > 0
    # strictly before the source is exhausted — parallelism did not degrade
    # into drain-everything-then-emit
    assert len(consumed) < 64
    rest = [first] + list(it)
    assert sum(b.num_rows for b in rest) == full.num_rows


def test_early_close_stops_workers():
    full = _table(20_000)
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > -10.0}, [s])
    dag = bld.finish(f)
    before = threading.active_count()
    out = execute_parallel(dag, lambda n: _sdf(full, rows=500), _cfg(4, morsel_rows=500))
    it = out.iter_batches()
    next(it)
    it.close()
    for _ in range(100):  # workers + prefetchers wind down on close
        if threading.active_count() <= before + 1:
            break
        time.sleep(0.05)
    assert threading.active_count() <= before + 1


def test_error_propagates_from_workers():
    full = _table(5000)
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("nope") > 0.0}, [s])
    dag = bld.finish(f)
    out = execute_parallel(dag, lambda n: _sdf(full), _cfg(4))
    with pytest.raises(SchemaError):
        out.collect()


def test_source_error_propagates():
    full = _table(2000)

    def gen():
        yield full.slice(0, 500)
        raise SchemaError("mid-stream source failure")

    sdf = StreamingDataFrame(full.schema, gen)
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > -10.0}, [s])
    dag = bld.finish(f)
    with pytest.raises(SchemaError):
        execute_parallel(dag, lambda n: sdf, _cfg(4, morsel_rows=100)).collect()


def test_prefetch_sdf_passthrough_and_overlap():
    full = _table(3000)
    wrapped = prefetch_sdf(_sdf(full, rows=500), depth=2)
    assert wrapped.schema.equals(full.schema)
    got = wrapped.collect()
    assert got.num_rows == full.num_rows
    assert prefetch_sdf(_sdf(full), 0) is not None  # depth<=0 → original sdf


# ---------------------------------------------------------------------------
# engine / server integration
# ---------------------------------------------------------------------------
def _server(tmp_tree, workers):
    from repro.client import LocalNetwork
    from repro.server import FairdServer

    net = LocalNetwork()
    srv = FairdServer(
        "exec:3101",
        executor=ExecutorConfig(num_workers=workers, morsel_rows=128, backend="numpy"),
    )
    srv.catalog.register_path("structured", str(tmp_tree / "structured"))
    net.register(srv)
    return net.client_for("exec:3101")


def test_cook_results_match_reference_engine(tmp_tree):
    frames = {}
    for workers in (0, 4):  # 0 = legacy reference pull chain
        c = _server(tmp_tree, workers)
        out = (
            c.open("dacp://exec:3101/structured/table.csv")
            .filter(col("id") % 2 == 0)
            .group_by("tag")
            .agg(n="count", s=("sum", "score"), m=("mean", "id"))
            .collect()
        )
        frames[workers] = out.to_pydict()
    ref, got = frames[0], frames[4]
    assert got["tag"] == ref["tag"]
    assert got["n"] == ref["n"]
    assert got["s"] == pytest.approx(ref["s"])
    assert got["m"] == pytest.approx(ref["m"])


def test_vectorized_groupstate_matches_reference_factorization():
    """First-seen group order and null-key handling: the vectorized
    factorization must agree with the reference row loop exactly."""
    from repro.core import dtypes
    from repro.core.batch import Column
    from repro.core.operators import GroupState
    from repro.core.schema import Field, Schema

    schema = Schema([Field("k", dtypes.INT64)])
    b = RecordBatch(schema, [Column.from_values(dtypes.INT64, [3, 1, 3, 2, 1])])
    for vec in (False, True):
        st = GroupState(["k"], {"n": {"fn": "count"}}, "full", schema, vectorized=vec)
        st.update(b)
        assert st.key_rows == [(3,), (1,), (2,)]  # first-seen row order
        assert st.acc["n"].tolist() == [2, 2, 1]

    # a validity mask on a key column must keep null keys distinct from the
    # sentinel value (vectorized path falls back to the row loop)
    col = Column.from_values(dtypes.INT64, [7, 7, 5])
    col.validity = np.asarray([True, False, True])
    bn = RecordBatch(schema, [col])
    st = GroupState(["k"], {"n": {"fn": "count"}}, "full", schema, vectorized=True)
    st.update(bn)
    assert st.key_rows == [(7,), (None,), (5,)]
    assert st.acc["n"].tolist() == [1, 1, 1]


# ---------------------------------------------------------------------------
# masked-key aggregation (row-loop fallback path) and merge
# ---------------------------------------------------------------------------
def _masked_batch(keys, mask, vals):
    from repro.core import dtypes
    from repro.core.batch import Column
    from repro.core.schema import Field, Schema

    schema = Schema([Field("k", dtypes.INT64), Field("v", dtypes.INT64)])
    kc = Column.from_values(dtypes.INT64, keys)
    if mask is not None:
        kc.validity = np.asarray(mask, dtype=bool)
    return RecordBatch(schema, [kc, Column.from_values(dtypes.INT64, vals)])


def _agg_state(schema, vectorized=True):
    from repro.core.operators import GroupState

    return GroupState(
        ["k"],
        {"n": {"fn": "count"}, "s": {"fn": "sum", "column": "v"}, "hi": {"fn": "max", "column": "v"}},
        "full",
        schema,
        vectorized=vectorized,
    )


def test_masked_key_aggregate_matches_row_loop():
    """Validity-masked keys take the row-loop factorization; null keys stay
    distinct from the same-valued sentinel and from each other's groups."""
    b = _masked_batch([7, 7, 5, 7], [True, False, True, True], [1, 2, 3, 4])
    for vec in (False, True):
        st = _agg_state(b.schema, vectorized=vec)
        st.update(b)
        assert st.key_rows == [(7,), (None,), (5,)]
        assert st.acc["n"].tolist() == [2, 1, 1]
        assert st.acc["s"].tolist() == [5, 2, 3]
        assert st.acc["hi"].tolist() == [4, 2, 3]


def test_all_masked_morsel_aggregate():
    """A morsel whose key column is entirely masked folds into a single
    null-key group (and survives the merge path)."""
    b = _masked_batch([1, 2, 3], [False, False, False], [10, 20, 30])
    st = _agg_state(b.schema)
    st.update(b)
    assert st.key_rows == [(None,)]
    assert st.acc["n"].tolist() == [3]
    assert st.acc["s"].tolist() == [60]

    # merge an all-masked partial into a state that has never seen nulls
    other = _agg_state(b.schema)
    other.update(_masked_batch([1, 2], None, [5, 6]))
    other.merge(st)
    assert other.key_rows == [(1,), (2,), (None,)]
    assert other.acc["s"].tolist() == [5, 6, 60]
    assert other.acc["hi"].tolist() == [5, 6, 30]


def test_mask_appearing_only_in_later_morsel():
    """A validity mask that first appears mid-stream must merge into the
    vectorized groups built from earlier (unmasked) morsels — end-to-end
    through the parallel executor's fold/merge breaker."""
    from repro.core.batch import concat_batches as _cat

    b1 = _masked_batch([1, 2, 1, 2] * 100, None, list(range(400)))
    b2 = _masked_batch([1, 9, 9, 1] * 50, [True, False, True, True] * 50, list(range(400, 600)))
    full = _cat([b1, b2])

    def gen():
        yield b1
        yield b2

    sdf = StreamingDataFrame(b1.schema, gen)
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    a = bld.add(
        "aggregate",
        {"keys": ["k"], "aggs": {"n": {"fn": "count"}, "s": {"fn": "sum", "column": "v"}}},
        [s],
    )
    dag = bld.finish(a)
    ref = execute(dag, lambda n: sdf).collect().to_pydict()
    for workers in (1, 4):
        got = execute_parallel(dag, lambda n: sdf, _cfg(workers, morsel_rows=128)).collect().to_pydict()
        assert got["k"] == ref["k"]  # first-seen order, null group included
        assert got["n"] == ref["n"]
        assert got["s"] == ref["s"]
    assert None in ref["k"] and full.num_rows == 600


# ---------------------------------------------------------------------------
# adaptive morsel sizing
# ---------------------------------------------------------------------------
def test_auto_morsel_rows_results_and_stats():
    from repro.core.executor import (
        AUTO_MORSEL_MAX,
        AUTO_MORSEL_MIN,
        ExecutorStats,
    )

    full = _table(60_000)
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > 0.0}, [s])
    a = bld.add("aggregate", {"keys": ["k"], "aggs": {"n": {"fn": "count"}, "sx": {"fn": "sum", "column": "x"}}}, [f])
    dag = bld.finish(a)
    ref = execute(dag, lambda n: _sdf(full)).collect().to_pydict()

    stats = ExecutorStats()
    cfg = ExecutorConfig(num_workers=4, morsel_rows="auto", backend="numpy")
    got = execute_parallel(dag, lambda n: _sdf(full), cfg, stats=stats).collect().to_pydict()
    assert got["k"] == ref["k"]
    assert got["n"] == ref["n"]
    for g, r in zip(got["sx"], ref["sx"]):
        assert g == pytest.approx(r)
    assert stats.pipelines, "stats must record the aggregate pipeline"
    for p in stats.pipelines:
        assert p["auto"] is True
        assert AUTO_MORSEL_MIN <= p["morsel_rows"] <= AUTO_MORSEL_MAX
        assert p["morsel_rows"] % 4096 == 0
        assert p["rows"] > 0
    assert stats.progress()["rows_processed"] == full.num_rows  # every source row ran through the stats passed in


def test_adaptive_window_and_prefetch_exported():
    """The adaptive latency signal also tunes the reorder window and source
    prefetch depth; both land in ExecutorStats per pipeline."""
    from repro.core.executor import ExecutorStats

    full = _table(60_000)
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > 0.0}, [s])
    dag = bld.finish(f)

    stats = ExecutorStats()
    cfg = ExecutorConfig(num_workers=4, morsel_rows="auto", backend="numpy")
    execute_parallel(dag, lambda n: _sdf(full), cfg, stats=stats).collect()
    assert stats.pipelines
    for p in stats.pipelines:
        # window in [workers+1, effective_window], depth in [1, prefetch_batches]
        assert 5 <= p["window"] <= cfg.effective_window()
        assert 1 <= p["prefetch_depth"] <= cfg.prefetch_batches
    # static configs report their fixed values
    stats2 = ExecutorStats()
    cfg2 = ExecutorConfig(num_workers=2, morsel_rows=512, backend="numpy")
    execute_parallel(dag, lambda n: _sdf(full), cfg2, stats=stats2).collect()
    for p in stats2.pipelines:
        assert p["window"] == cfg2.effective_window()
        assert p["prefetch_depth"] == cfg2.prefetch_batches


def test_adaptive_window_shrinks_for_slow_morsels():
    """Morsels far over the latency target pull the reorder window toward
    one-per-worker (bounded in-flight memory) instead of 4× workers."""
    from repro.core.executor import _MorselSizer

    sizer = _MorselSizer(4096, True, workers=4, window=16, prefetch=4)
    for _ in range(20):
        sizer.observe(4096, 0.05)  # 50x the 1 ms target
    assert sizer.window == 5  # workers + 1
    assert sizer.prefetch_depth == 1
    for _ in range(40):
        sizer.observe(4096, 1e-4)  # far under target: full read-ahead again
    assert sizer.window == 16
    assert sizer.prefetch_depth == 4


def test_cancel_event_stops_parallel_execution():
    """The flow-lifecycle hook: setting the cancel event makes the driver
    raise FlowCancelled and wind its workers down."""
    from repro.core.errors import FlowCancelled

    full = _table(60_000)
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > -10.0}, [s])
    dag = bld.finish(f)
    cancel = threading.Event()
    out = execute_parallel(dag, lambda n: _sdf(full, rows=500), _cfg(4), cancel=cancel)
    it = out.iter_batches()
    next(it)
    before = threading.active_count()
    cancel.set()
    with pytest.raises(FlowCancelled):
        for _ in it:
            pass
    deadline = time.time() + 5
    while time.time() < deadline and threading.active_count() > before - 1:
        time.sleep(0.05)
    assert threading.active_count() <= before


def test_morsel_rows_env_validation(monkeypatch):
    from repro.core.executor import DEFAULT_MORSEL_ROWS

    for bad in ("garbage", "0", "-5"):
        monkeypatch.setenv("DACP_MORSEL_ROWS", bad)
        with pytest.warns(UserWarning):
            cfg = ExecutorConfig(num_workers=1)
        assert cfg.morsel_rows == DEFAULT_MORSEL_ROWS
    monkeypatch.setenv("DACP_MORSEL_ROWS", "auto")
    assert ExecutorConfig(num_workers=1).morsel_rows == "auto"
    monkeypatch.setenv("DACP_MORSEL_ROWS", "8192")
    assert ExecutorConfig(num_workers=1).morsel_rows == 8192
    monkeypatch.delenv("DACP_MORSEL_ROWS")
    with pytest.raises(ValueError):
        ExecutorConfig(num_workers=1, morsel_rows=0)
    with pytest.raises(ValueError):
        ExecutorConfig(num_workers=1, morsel_rows="sometimes")


def test_dense_factorization_narrow_signed_keys():
    """int8 keys spanning beyond the dtype's positive range must not wrap in
    the sort-free dense factorization (regression: -100..100 span 201)."""
    from repro.core import dtypes
    from repro.core.batch import Column
    from repro.core.operators import GroupState
    from repro.core.schema import Field, Schema

    schema = Schema([Field("k", dtypes.INT8)])
    vals = [-100, 100, 50, -100, 100]
    b = RecordBatch(schema, [Column.from_values(dtypes.INT8, vals)])
    for vec in (False, True):
        st = GroupState(["k"], {"n": {"fn": "count"}}, "full", schema, vectorized=vec)
        st.update(b)
        assert st.key_rows == [(-100,), (100,), (50,)], (vec, st.key_rows)
        assert st.acc["n"].tolist() == [2, 2, 1]


# ---------------------------------------------------------------------------
# micro-morsel batching (PR 7)
# ---------------------------------------------------------------------------
def test_micromorsel_coalescing_preserves_order():
    """Adaptive mode coalesces runs of tiny source batches into one morsel;
    only *consecutive* batches merge, so the output stays in exact input
    order across a multi-worker pool."""
    from repro.core.executor import ExecutorStats

    n = 30_000
    full = RecordBatch.from_pydict({"seq": np.arange(n), "x": np.ones(n, np.float32)})
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > 0.0}, [s])
    dag = bld.finish(f)

    stats = ExecutorStats()
    cfg = ExecutorConfig(num_workers=4, morsel_rows="auto", backend="numpy")
    # 150-row fragments: far below AUTO_MORSEL_MIN, so runs of them coalesce
    got = execute_parallel(dag, lambda nn: _sdf(full, rows=150), cfg, stats=stats).collect()
    assert np.array_equal(got.column("seq").to_numpy(), np.arange(n))
    assert stats.progress()["micromorsels_coalesced"] > 0, "tiny batches never coalesced"


def test_cancel_mid_batch_clears_staged_buffers(monkeypatch):
    """CANCEL with coalesced morsels in flight on the fused path: the
    teardown sweeps every staged device buffer, including one staged by a
    worker racing the sweep."""
    from repro.core import backend as backend_mod
    from repro.core.errors import FlowCancelled

    plans = []
    orig_bind = backend_mod.FusedChainPlan.bind

    def spy_bind(self, sizer, device_index=None):
        plans.append(self)
        return orig_bind(self, sizer, device_index)

    high_water = []
    orig_stage = backend_mod.FusedChainPlan.stage

    def spy_stage(self, batch):
        orig_stage(self, batch)
        high_water.append(self.staged_count)

    monkeypatch.setattr(backend_mod.FusedChainPlan, "bind", spy_bind)
    monkeypatch.setattr(backend_mod.FusedChainPlan, "stage", spy_stage)

    n = 60_000
    full = RecordBatch.from_pydict(
        {"x": np.random.default_rng(3).standard_normal(n).astype(np.float32), "k": np.arange(n, dtype=np.int64)}
    )
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > -3.0}, [s])
    dag = bld.finish(bld.add("select", {"columns": ["x", "k"]}, [f]))

    cancel = threading.Event()
    base = threading.active_count()
    cfg = ExecutorConfig(num_workers=4, morsel_rows="auto", backend="pallas")
    out = execute_parallel(dag, lambda nn: _sdf(full, rows=150), cfg, cancel=cancel)
    it = out.iter_batches()
    next(it)  # first morsel out: later morsels are staged/coalesced in flight
    cancel.set()
    with pytest.raises(FlowCancelled):
        for _ in it:
            pass
    deadline = time.time() + 5
    while time.time() < deadline and threading.active_count() > base:
        time.sleep(0.05)  # workers/prefetchers wind down before we inspect
    assert plans, "chain did not compile to a fused plan"
    assert max(high_water, default=0) > 0, "double-buffering never staged a morsel"
    deadline = time.time() + 5
    while time.time() < deadline and any(p.staged_count for p in plans):
        time.sleep(0.05)
    assert all(p.staged_count == 0 for p in plans), "staged device buffers leaked past CANCEL"
    # a straggler worker staging after the sweep must be refused, not leaked
    plans[0].stage(full.slice(0, 150))
    assert plans[0].staged_count == 0
