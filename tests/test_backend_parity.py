"""numpy ↔ pallas backend parity: the two compute backends must produce
**byte-identical** RecordBatches — filter/select over every supported
predicate dtype (float32/int32/int64) and comparison (< <= > >= == !=),
multi-dtype projections (f64/i64/u8/f16/bool ride through the bit-plane
kernel), project arithmetic, and aggregation — including ``-0.0``, NaN
payloads, and full-range int64.  Every case runs through the executor:
chains inside the fused kernel's envelope must run one ``fused_chain_tiles``
launch per morsel, and chains or morsels outside it must leave the kernel
(``plan_fused_chain`` returns None, or the morsel is ``FUSED_INELIGIBLE``)
and still match.  Skipped cleanly when jax is absent (the pallas backend
then cannot be resolved).  A kernel failure is never turned into a numpy
answer: the last section checks that it fails the request instead."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import dtypes  # noqa: E402
from repro.core.backend import FUSED_INELIGIBLE, get_backend, plan_fused_chain  # noqa: E402
from repro.core.batch import Column, RecordBatch  # noqa: E402
from repro.core.dag import Dag  # noqa: E402
from repro.core.executor import ExecutorConfig, ExecutorStats, execute_parallel  # noqa: E402
from repro.core.expr import col  # noqa: E402
from repro.core.operators import project_schema  # noqa: E402
from repro.core.schema import Field, Schema  # noqa: E402
from repro.core.sdf import StreamingDataFrame  # noqa: E402

N_ROWS = 700  # spans multiple kernel tiles (256) incl. a ragged tail


def _random_batch(rng, n=N_ROWS):
    """Random schema: a shuffled mix of fixed-width dtypes + a string key.
    The float32 column carries -0.0; int64 spans the full 64-bit range."""
    f32 = rng.standard_normal(n).astype(np.float32)
    f32[::97] = -0.0
    data = {
        "f32_a": f32,
        "f32_b": (rng.standard_normal(n) * 3).astype(np.float32),
        "f64_c": rng.standard_normal(n),
        "i64_d": rng.integers(-(2**62), 2**62, n),
        "i32_e": rng.integers(0, 9, n).astype(np.int32),
        "u8_f": rng.integers(0, 255, n).astype(np.uint8),
        "f16_g": rng.standard_normal(n).astype(np.float16),
        "bool_h": rng.integers(0, 2, n).astype(bool),
        "tag": np.asarray([f"g{i}" for i in rng.integers(0, 6, n)]),
    }
    names = list(data)
    rng.shuffle(names)
    return RecordBatch.from_pydict({k: data[k] for k in names})


def _sdf(batch, rows=200):
    def gen():
        for s in range(0, batch.num_rows, rows):
            yield batch.slice(s, s + rows)

    return StreamingDataFrame(batch.schema, gen)


def _column_bytes(batch):
    out = {}
    for f, c in zip(batch.schema, batch.columns):
        if f.dtype.is_varwidth:
            out[f.name] = (c.offsets.tobytes(), c.data.tobytes())
        else:
            out[f.name] = c.values.tobytes()
    return out


def _assert_byte_identical(a: RecordBatch, b: RecordBatch):
    if a is None or b is None:
        assert a is b
        return
    assert a.schema.to_json() == b.schema.to_json()
    assert a.num_rows == b.num_rows
    ab, bb = _column_bytes(a), _column_bytes(b)
    for name in ab:
        assert ab[name] == bb[name], f"column {name} differs between backends"


def _run(dag, batch, backend):
    cfg = ExecutorConfig(num_workers=2, morsel_rows=200, backend=backend)
    return execute_parallel(dag, lambda n: _sdf(batch), cfg).collect()


def _fused_run(dag, batch, backend_name, **cfg_kw):
    stats = ExecutorStats()
    cfg = ExecutorConfig(num_workers=2, morsel_rows=200, backend=backend_name, **cfg_kw)
    out = execute_parallel(dag, lambda n: _sdf(batch), cfg, stats=stats).collect()
    return out, stats


def _chain(*links):
    """A DAG of ``(op, params)`` links over one source."""
    bld = Dag.build()
    node = bld.source("dacp://h:1/d")
    for op, params in links:
        node = bld.add(op, params, [node])
    return bld.finish(node)


def _filter_select(pred, cols):
    return _chain(("filter", {"predicate": pred}), ("select", {"columns": cols}))


def _aggregate(keys, aggs):
    return _chain(("aggregate", {"keys": keys, "aggs": aggs}))


def _parity(dag, batch):
    """Run ``dag`` on both backends, assert byte-identical answers, and
    return the pallas answer and executor progress."""
    got, stats = _fused_run(dag, batch, "pallas")
    ref, _ = _fused_run(dag, batch, "numpy")
    _assert_byte_identical(got, ref)
    return got, stats.progress()


def _all_fused(prog):
    """Every morsel ran as one fused launch."""
    assert prog["morsels_done"] > 0
    assert prog["fused_launches"] == prog["morsels_done"], "a morsel left the fused kernel"


def _plan(specs, schema, agg=None):
    return plan_fused_chain(specs, schema, agg=agg, backend=get_backend("pallas"))


# ---------------------------------------------------------------------------
# fused filter+select
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize(
    "pred_col,sel_cols",
    [
        ("f32_a", ["f32_a", "f32_b"]),  # all-f32 fused kernel
        ("f64_c", ["f64_c", "i64_d"]),  # f64 predicate: numpy fallback
        ("i64_d", ["f32_a", "tag"]),  # string in projection: numpy fallback
        ("i64_d", ["i64_d", "f64_c", "u8_f"]),  # i64 predicate, mixed planes
        ("i32_e", ["i32_e", "f16_g", "bool_h"]),  # i32 predicate, narrow cols
    ],
)
def test_filter_select_parity(seed, pred_col, sel_cols):
    batch = _random_batch(np.random.default_rng(seed))
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col(pred_col) > 0.25}, [s])
    sel = bld.add("select", {"columns": sel_cols}, [f])
    dag = bld.finish(sel)
    _assert_byte_identical(_run(dag, batch, "numpy"), _run(dag, batch, "pallas"))


@pytest.mark.parametrize("op", ["lt", "le", "gt", "ge", "eq", "ne"])
@pytest.mark.parametrize("pred_col,threshold", [("f32_a", 0.25), ("i32_e", 4), ("i64_d", 0)])
def test_comparison_set_parity(op, pred_col, threshold):
    """Every comparison × predicate dtype must run fused AND stay
    byte-identical (eq/ne exercise the padded-tail row masking)."""
    batch = _random_batch(np.random.default_rng(3))
    pred = getattr(col(pred_col), f"__{op}__")(threshold)
    _, prog = _parity(_filter_select(pred, [pred_col, "f32_b"]), batch)
    _all_fused(prog)


def test_eq_matches_exact_int64_value():
    batch = _random_batch(np.random.default_rng(11))
    target = int(batch.column("i64_d").values[123])
    got, prog = _parity(_filter_select(col("i64_d") == target, ["i64_d"]), batch)
    _all_fused(prog)
    assert got.num_rows >= 1


def test_negative_zero_is_bit_exact():
    """-0.0 must survive the kernel with its sign bit (parity means parity —
    the old MXU float path normalized it to +0.0)."""
    data = np.asarray([-0.0, 1.0, -0.0, -1.0, 0.0] * 60, np.float32)
    batch = RecordBatch.from_pydict({"a": data, "b": data[::-1].copy()})
    out, prog = _parity(_filter_select(col("a") <= 0.0, ["a", "b"]), batch)
    _all_fused(prog)
    assert np.signbit(out.column("a").values).any()


def test_nonfinite_dispatches_bit_exact():
    """NaN/Inf force no fallback: integer bit-plane compaction moves
    payloads verbatim and float compares keep IEEE NaN semantics."""
    data = np.asarray([1.0, np.inf, -1.0, np.nan, 2.0] * 60, np.float32)
    batch = RecordBatch.from_pydict({"a": data, "b": data[::-1].copy()})
    for pred in (col("a") > 0.5, col("a") != 1.0, col("a") <= 0.5):
        _, prog = _parity(_filter_select(pred, ["a", "b"]), batch)
        _all_fused(prog)


@pytest.mark.parametrize(
    "threshold",
    [5, np.int64(5), np.float32(0.5), np.float16(0.5), np.float64(0.25)],
)
def test_numpy_typed_literals_dispatch(threshold):
    """Literal dtype is normalized before the representability test: an
    integer-typed or numpy-scalar literal against a float32 column must not
    be rejected when exactly representable (regression: ``col > 5``)."""
    batch = _random_batch(np.random.default_rng(5))
    _, prog = _parity(_filter_select(col("f32_a") > threshold, ["f32_a"]), batch)
    _all_fused(prog)


def test_float_literal_on_int_column_rewrites():
    """``i32 > 2.5`` rewrites to the equivalent integer comparison and runs
    fused; ``i32 == 2.5`` (a constant mask) is declined."""
    batch = _random_batch(np.random.default_rng(6))
    for pred in (col("i32_e") > 2.5, col("i32_e") <= 2.5, col("i32_e") < 4.5, col("i32_e") >= 4.5):
        _, prog = _parity(_filter_select(pred, ["i32_e"]), batch)
        _all_fused(prog)
    pred = col("i32_e") == 2.5
    assert _plan([("filter", (pred,)), ("select", (["i32_e"],))], batch.schema) is None
    _, prog = _parity(_filter_select(pred, ["i32_e"]), batch)
    assert prog["fused_launches"] == 0  # constant mask → numpy


def test_pallas_kernel_actually_dispatches():
    """The all-float32 chain must execute device-resident: ONE fused launch
    per morsel (guards against the backend silently degrading to numpy or
    the fused planner silently declining)."""
    batch = _random_batch(np.random.default_rng(7))
    _, stats = _fused_run(_filter_select(col("f32_a") > 0.0, ["f32_b", "f32_a"]), batch, "pallas")
    _all_fused(stats.progress())


def test_pallas_falls_back_on_unsupported_shapes():
    """f64 predicates and var-width projections are declined at plan time,
    and a morsel with a validity mask at run time; each runs the
    (bit-identical) numpy path."""
    batch = _random_batch(np.random.default_rng(8))
    for pred, cols in ((col("f64_c") > 0, ["i64_d", "f64_c"]), (col("i64_d") > 0, ["tag"])):
        assert _plan([("filter", (pred,)), ("select", (cols,))], batch.schema) is None
        _, prog = _parity(_filter_select(pred, cols), batch)
        assert prog["fused_launches"] == 0

    masked = Column.from_values(batch.schema.field("f32_a").dtype, batch.column("f32_a").values)
    masked.validity = np.ones(batch.num_rows, bool)
    vb = batch.with_column(batch.schema.field("f32_a"), masked)
    plan = _plan([("filter", (col("f32_a") > 0.0,)), ("select", (["f32_a"],))], vb.schema)
    assert plan.run(vb) is FUSED_INELIGIBLE  # validity mask → numpy
    _, prog = _parity(_filter_select(col("f32_a") > 0.0, ["f32_a"]), vb)
    assert prog["fused_launches"] == 0


# ---------------------------------------------------------------------------
# project arithmetic
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 4])
@pytest.mark.parametrize(
    "exprs,keep",
    [
        ({"y": col("f32_a") * 2.0 + 1.1}, True),
        ({"y": col("f32_a") / col("f32_b"), "z": col("f32_a") - col("f32_b") * 0.5}, True),
        ({"w": col("i32_e") * 3 - 7}, False),
        ({"y": (col("f32_a") + col("f32_b")) * (col("f32_a") - 2.0)}, True),
        ({"y": col("f32_a") * 2.5, "d": col("f64_c") + 1.0}, True),  # f64 expr → per-expr fallback
    ],
)
def test_project_parity(seed, exprs, keep):
    batch = _random_batch(np.random.default_rng(seed))
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    p = bld.add("project", {"exprs": exprs, "keep": keep}, [s])
    dag = bld.finish(p)
    _assert_byte_identical(_run(dag, batch, "numpy"), _run(dag, batch, "pallas"))


def test_project_kernel_dispatches():
    batch = _random_batch(np.random.default_rng(9))
    dag = _chain(
        ("filter", {"predicate": col("i32_e") >= 0}),  # keeps every row
        ("project", {"exprs": {"y": col("f32_a") * 2.0 + 1.0, "b": col("f32_b")}, "keep": False}),
    )
    _, prog = _parity(dag, batch)
    _all_fused(prog)


def test_project_division_by_zero_parity():
    """float32 division is declined (a TPU's is not correctly rounded); the
    numpy path keeps inf and nan bit patterns."""
    a = np.asarray([1.0, -1.0, 0.0, 2.0] * 70, np.float32)
    b = np.asarray([0.0, 0.0, 0.0, 1.0] * 70, np.float32)
    batch = RecordBatch.from_pydict({"a": a, "b": b})
    exprs = {"q": col("a") / col("b")}
    pred = col("b") >= 0.0
    specs = [("filter", (pred,)), ("project", (exprs, project_schema(batch.schema, exprs, True)))]
    assert _plan(specs, batch.schema) is None
    dag = _chain(("filter", {"predicate": pred}), ("project", {"exprs": exprs, "keep": True}))
    with np.errstate(divide="ignore", invalid="ignore"):
        _, prog = _parity(dag, batch)  # inf and nan bit patterns included
    assert prog["fused_launches"] == 0


# ---------------------------------------------------------------------------
# aggregation (the fused fold)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [3, 4])
@pytest.mark.parametrize("key", ["i32_e", "tag"])
def test_filter_aggregate_parity(seed, key):
    batch = _random_batch(np.random.default_rng(seed))
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("f32_a") > -0.5}, [s])
    a = bld.add(
        "aggregate",
        {
            "keys": [key],
            "aggs": {
                "n": {"fn": "count"},
                "s64": {"fn": "sum", "column": "i64_d"},
                "m": {"fn": "mean", "column": "f64_c"},
                "lo": {"fn": "min", "column": "f32_b"},
                "hi": {"fn": "max", "column": "i32_e"},
                "s8": {"fn": "sum", "column": "u8_f"},
            },
        },
        [f],
    )
    dag = bld.finish(a)
    _assert_byte_identical(_run(dag, batch, "numpy"), _run(dag, batch, "pallas"))


def test_segment_reduce_kernel_dispatches():
    batch = _random_batch(np.random.default_rng(10))
    aggs = {"n": {"fn": "count"}, "s": {"fn": "sum", "column": "i64_d"}, "hi": {"fn": "max", "column": "i32_e"}}
    _, prog = _parity(_aggregate(["i32_e"], aggs), batch)
    _all_fused(prog)


def test_segment_reduce_int64_wraparound_parity():
    """Limb recombination must reproduce numpy's int64 wraparound exactly
    when a group's sum overflows."""
    big = np.asarray([2**62, 2**62, 2**62, -(2**61)] * 64, np.int64)
    keys = np.asarray([0, 1, 0, 1] * 64, np.int32)
    batch = RecordBatch.from_pydict({"k": keys, "v": big})
    with np.errstate(over="ignore"):
        _, prog = _parity(_aggregate(["k"], {"s": {"fn": "sum", "column": "v"}}), batch)
    _all_fused(prog)


def test_segment_reduce_nan_minmax_falls_back():
    """min/max over a float column containing NaN must leave the kernel
    (order keys do not propagate NaN) — and still match numpy."""
    vals = np.asarray([1.0, np.nan, -2.0, 3.0] * 64, np.float32)
    keys = np.asarray([0, 0, 1, 1] * 64, np.int32)
    batch = RecordBatch.from_pydict({"k": keys, "v": vals})
    aggs = {"lo": {"fn": "min", "column": "v"}}
    plan = _plan([], batch.schema, agg=(["k"], aggs, "full", batch.schema))
    assert plan.fold(batch) is FUSED_INELIGIBLE
    _, prog = _parity(_aggregate(["k"], aggs), batch)
    assert prog["fused_launches"] == 0


def test_masked_keys_still_use_value_kernel():
    """A validity mask on the key column forces the row-loop factorization,
    but the fused kernel still folds the values."""
    schema = Schema([Field("k", dtypes.INT64), Field("v", dtypes.INT64)])
    kc = Column.from_values(dtypes.INT64, [1, 1, 2, 2] * 64)
    kc.validity = np.asarray([True, False, True, True] * 64)
    vc = Column.from_values(dtypes.INT64, list(range(256)))
    batch = RecordBatch(schema, [kc, vc])
    aggs = {"s": {"fn": "sum", "column": "v"}, "n": {"fn": "count"}}
    got, prog = _parity(_aggregate(["k"], aggs), batch)
    _all_fused(prog)
    assert got.column("k").to_pylist() == [1, None, 2]  # null key stays a distinct group


# ---------------------------------------------------------------------------
# wide min/max and float sums: min/max over int64, uint32, uint64 and
# float64 are declined by the fused plan; float sums fold fused
# ---------------------------------------------------------------------------
def _declined_minmax(batch, aggs):
    """``aggs`` grouped by ``k``: declined at plan time, byte-identical."""
    assert _plan([], batch.schema, agg=(["k"], aggs, "full", batch.schema)) is None
    got, prog = _parity(_aggregate(["k"], aggs), batch)
    assert prog["fused_launches"] == 0
    return got


def test_segment_reduce_int64_minmax_two_word_parity():
    """Full-range int64 min/max (hi-word ties, so the low word decides)."""
    rng = np.random.default_rng(17)
    vals = rng.integers(-(2**63), 2**63 - 1, 512, dtype=np.int64)
    vals[1::4] = vals[::4] | np.int64(1)
    keys = rng.integers(0, 9, 512).astype(np.int32)
    batch = RecordBatch.from_pydict({"k": keys, "v": vals})
    _declined_minmax(batch, {"lo": {"fn": "min", "column": "v"}, "hi": {"fn": "max", "column": "v"}})


def test_segment_reduce_uint32_minmax_parity():
    rng = np.random.default_rng(18)
    vals = rng.integers(0, 2**32 - 1, 512, dtype=np.uint32)
    keys = rng.integers(0, 5, 512).astype(np.int32)
    batch = RecordBatch.from_pydict({"k": keys, "v": vals})
    _declined_minmax(batch, {"hi": {"fn": "max", "column": "v"}})


def test_segment_reduce_uint64_minmax_parity():
    """Full-range uint64 min/max — values straddling 2^63 compare unsigned
    (min over [1, 2^63+5] is 1, never a wrapped negative)."""
    rng = np.random.default_rng(19)
    vals = rng.integers(0, 2**64 - 1, 512, dtype=np.uint64)
    vals[:4] = [1, 2**63 + 5, 2**64 - 1, 0]
    keys = rng.integers(0, 7, 512).astype(np.int32)
    keys[:4] = 0
    batch = RecordBatch.from_pydict({"k": keys, "v": vals})
    got = _declined_minmax(batch, {"lo": {"fn": "min", "column": "v"}, "hi": {"fn": "max", "column": "v"}})
    assert got.column("lo").values.dtype == np.uint64


def test_segment_reduce_float64_minmax_parity():
    """float64 min/max (incl. ±Inf and subnormals), byte-identical."""
    rng = np.random.default_rng(20)
    vals = rng.standard_normal(512) * 10.0**rng.integers(-200, 200, 512)
    vals[:4] = [np.inf, -np.inf, 5e-324, -5e-324]
    keys = rng.integers(0, 6, 512).astype(np.int32)
    keys[:4] = 1
    batch = RecordBatch.from_pydict({"k": keys, "v": vals})
    _declined_minmax(batch, {"lo": {"fn": "min", "column": "v"}, "hi": {"fn": "max", "column": "v"}})


def test_segment_reduce_float64_sentinels_on_absent_groups():
    """A second morsel that misses some already-interned groups: the merged
    extremes are the ±Inf identities' results, not NaN."""
    b1 = RecordBatch.from_pydict(
        {"k": np.asarray([0, 1, 2, 3] * 64, np.int32), "v": np.arange(256, dtype=np.float64) - 128.0}
    )
    b2 = RecordBatch.from_pydict(
        {"k": np.asarray([1, 3] * 128, np.int32), "v": -(np.arange(256, dtype=np.float64)) * 7.5}
    )
    batch = RecordBatch.from_pydict({n: np.concatenate([b1.column(n).values, b2.column(n).values]) for n in "kv"})
    got = _declined_minmax(batch, {"lo": {"fn": "min", "column": "v"}, "hi": {"fn": "max", "column": "v"}})
    assert not np.isnan(got.column("lo").values).any()


@pytest.mark.parametrize("poison", ["nan", "negzero"])
def test_segment_reduce_float64_nan_negzero_fall_back(poison):
    """NaN (numpy propagates it) and -0.0 (numpy's min/max result depends
    on operand order) in a float64 column: the numpy result is
    bit-preserved."""
    vals = np.arange(256, dtype=np.float64)
    vals[7] = np.nan if poison == "nan" else -0.0
    keys = np.asarray([0, 1] * 128, np.int32)
    batch = RecordBatch.from_pydict({"k": keys, "v": vals})
    _declined_minmax(batch, {"lo": {"fn": "min", "column": "v"}})


def test_float_sums_take_f64_reference_path():
    """Float sums (and mean partial sums) fold fused: their source planes
    ride the compaction and the host folds them in float64, in row order —
    bit-identical to the numpy scatter."""
    batch = _random_batch(np.random.default_rng(19))
    aggs = {
        "sf": {"fn": "sum", "column": "f32_a"},
        "sd": {"fn": "sum", "column": "f64_c"},
        "m": {"fn": "mean", "column": "f64_c"},
    }
    _, prog = _parity(_aggregate(["i32_e"], aggs), batch)
    _all_fused(prog)


def test_spill_composes_with_pallas_backend():
    """Grace-hash spilling must not disable the fused fold: every
    per-morsel fold still runs on the kernel, and the spilled result stays
    byte-identical to the numpy in-memory run."""
    batch = _random_batch(np.random.default_rng(20), n=2000)
    aggs = {
        "n": {"fn": "count"},
        "s64": {"fn": "sum", "column": "i64_d"},
        "sf": {"fn": "sum", "column": "f32_a"},
        "lo": {"fn": "min", "column": "f32_b"},
    }
    dag = _aggregate(["tag"], aggs)
    ref = _run(dag, batch, "numpy")
    got, stats = _fused_run(dag, batch, "pallas", memory_budget=1)
    _all_fused(stats.progress())
    assert stats.to_dict()["spill"]["spills"] >= 1
    _assert_byte_identical(got, ref)


# ---------------------------------------------------------------------------
# PR 7: device-resident fused pipelines (one launch per morsel chain)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(6))
def test_fused_chain_random_eligible_chains_parity(seed):
    """Random eligible filter/select/project chains — filter leading or
    mid-chain, computed-of-computed arithmetic, mixed-dtype passthrough —
    run as ONE fused launch per morsel, byte-identical to numpy."""
    rng = np.random.default_rng(100 + seed)
    batch = _random_batch(np.random.default_rng(seed))
    pc, thr = [
        ("f32_a", float(rng.standard_normal())),
        ("i32_e", int(rng.integers(0, 9))),
        ("i64_d", int(rng.integers(-(2**61), 2**61))),
    ][seed % 3]
    cmp_op = ["lt", "le", "gt", "ge", "eq", "ne"][int(rng.integers(6))]
    pred = getattr(col(pc), f"__{cmp_op}__")(thr)
    # pow2 scale: the only mul shape allowed directly under add/sub (exact
    # product — immune to XLA CPU's fmul+fadd → FMA contraction); arbitrary
    # literals stay eligible away from add/sub, e.g. at the tree root
    scale = float(2.0 ** int(rng.integers(-3, 4)))
    exprs = {
        "y": col("f32_a") * scale + col("f32_b"),
        "z": (col("f32_a") - col("f32_b")) * float(rng.standard_normal()),
        "w": col("i32_e") * int(rng.integers(1, 5)) - 3,
    }
    links = [
        ("filter", {"predicate": pred}),
        ("project", {"exprs": exprs, "keep": True}),
        ("project", {"exprs": {"y2": col("y") * 0.5}, "keep": True}),  # computed-of-computed
        ("select", {"columns": ["y", "y2", "z", "w", "f32_a", "i64_d", "u8_f", "f16_g", "bool_h"]}),
    ]
    if seed % 2:
        links = [links[1], links[2], links[0], links[3]]  # filter mid-chain
    bld = Dag.build()
    node = bld.source("dacp://h:1/d")
    for op, params in links:
        node = bld.add(op, params, [node])
    dag = bld.finish(node)
    got, stats = _fused_run(dag, batch, "pallas")
    ref, _ = _fused_run(dag, batch, "numpy")
    _assert_byte_identical(got, ref)
    assert stats.progress()["fused_launches"] > 0, "eligible chain did not fuse"


def test_fused_chain_nan_negzero_payload_parity():
    """-0.0 / NaN / ±Inf payloads ride the fused compaction verbatim, and a
    NaN-poisoned predicate column keeps IEEE comparison semantics."""
    n = 600
    a = np.asarray([1.0, -0.0, np.nan, -1.0, np.inf, 0.0] * (n // 6), np.float32)
    b = np.asarray([-np.inf, np.nan, -0.0, 2.5, -2.5, np.nan] * (n // 6), np.float32)
    batch = RecordBatch.from_pydict({"a": a, "b": b})
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("a") <= 0.0}, [s])
    p = bld.add("project", {"exprs": {"c": col("b") * 2.0}, "keep": True}, [f])
    dag = bld.finish(p)
    got, stats = _fused_run(dag, batch, "pallas")
    ref, _ = _fused_run(dag, batch, "numpy")
    _assert_byte_identical(got, ref)
    assert stats.progress()["fused_launches"] > 0


def test_fused_chain_full_range_int64_parity():
    """Full-range int64 payloads (both 32-bit words live) survive the
    bit-plane passthrough unchanged; the int64 predicate compares as two
    words."""
    rng = np.random.default_rng(21)
    v = rng.integers(-(2**63), 2**63 - 1, 640, dtype=np.int64)
    v[:4] = [2**63 - 1, -(2**63), -1, 0]
    k = rng.integers(0, 9, 640).astype(np.int32)
    batch = RecordBatch.from_pydict({"v": v, "k": k})
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("v") > -(2**62)}, [s])
    dag = bld.finish(bld.add("select", {"columns": ["v", "k"]}, [f]))
    got, stats = _fused_run(dag, batch, "pallas")
    ref, _ = _fused_run(dag, batch, "numpy")
    _assert_byte_identical(got, ref)
    assert stats.progress()["fused_launches"] > 0


def test_fused_aggregate_single_launch_per_morsel():
    """filter → project → group-by folds in the SAME launch: the fused
    counter ticks exactly once per morsel."""
    batch = _random_batch(np.random.default_rng(23))
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("f32_a") > -0.25}, [s])
    p = bld.add("project", {"exprs": {"c": (col("f32_a") - 0.5) * 3.0}, "keep": True}, [f])
    a = bld.add(
        "aggregate",
        {
            "keys": ["i32_e"],
            "aggs": {
                "n": {"fn": "count"},
                "s64": {"fn": "sum", "column": "i64_d"},
                "sc": {"fn": "sum", "column": "c"},
                "m": {"fn": "mean", "column": "f64_c"},
                "lo": {"fn": "min", "column": "f32_b"},
                "hi": {"fn": "max", "column": "u8_f"},
            },
        },
        [p],
    )
    dag = bld.finish(a)
    got, stats = _fused_run(dag, batch, "pallas")
    ref, _ = _fused_run(dag, batch, "numpy")
    _assert_byte_identical(got, ref)
    assert stats.progress()["fused_launches"] == 4  # 700 rows / 200-row morsels


def test_fused_chain_composes_with_spill(monkeypatch):
    """Fused folds × grace-hash spill (DACP_MEMORY_BUDGET=256KB): per-morsel
    partials come off the fused launch, the merged state crosses the budget
    and spills, and the result stays byte-identical to the in-memory numpy
    run."""
    rng = np.random.default_rng(24)
    n = 4000
    batch = RecordBatch.from_pydict(
        {
            "g": rng.permutation(n).astype(np.int64),  # ~200 fresh groups per morsel
            "v": rng.integers(-(2**40), 2**40, n),
            "x": rng.standard_normal(n).astype(np.float32),
        }
    )
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    f = bld.add("filter", {"predicate": col("x") > -2.5}, [s])
    a = bld.add(
        "aggregate",
        {
            "keys": ["g"],
            "aggs": {"n": {"fn": "count"}, "sv": {"fn": "sum", "column": "v"}, "lo": {"fn": "min", "column": "x"}},
        },
        [f],
    )
    dag = bld.finish(a)
    ref, _ = _fused_run(dag, batch, "numpy")
    monkeypatch.setenv("DACP_MEMORY_BUDGET", "256KB")
    stats = ExecutorStats()
    cfg = ExecutorConfig(num_workers=2, morsel_rows=200, backend="pallas")
    assert cfg.memory_budget == 256 * 1024
    got = execute_parallel(dag, lambda nn: _sdf(batch), cfg, stats=stats).collect()
    _assert_byte_identical(got, ref)
    assert stats.progress()["fused_launches"] > 0, "spill run did not use the fused path"
    assert stats.to_dict()["spill"]["spills"] >= 1, "budget never triggered a spill"


# ---------------------------------------------------------------------------
# coalesced morsels: thinned batches merged up to morsel_rows, one kernel shape
# ---------------------------------------------------------------------------
# At 2048-row morsels these batches make four morsels: 500+500+500 merged,
# 1100 alone, 1400+300 merged, 700 alone.  A merged morsel's own rows would
# round up to 1536 and 1792.
COALESCE_SIZES = [500, 500, 500, 1100, 1400, 300, 700]
COALESCE_LAUNCHES = [(700, 768), (1100, 1280), (1500, 2048), (1700, 2048)]  # (rows, kernel rows)


def _coalesce_dag(kind):
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    if kind == "rows":
        node = bld.add("filter", {"predicate": col("price") > 20.0}, [s])
        node = bld.add("select", {"columns": ["price", "g"]}, [node])
        return bld.finish(node)
    node = bld.add("project", {"exprs": {"revenue": col("price") * col("disc")}, "keep": True}, [s])
    aggs = {"revenue": {"fn": "sum", "column": "revenue"}}
    keys = {"keyless_sum": [], "keyed_fold": ["k"], "windowed_fold": ["g"]}[kind]
    if keys:
        aggs.update({"n": {"fn": "count"}, "lo": {"fn": "min", "column": "price"}, "q": {"fn": "sum", "column": "qty"}})
    return bld.finish(bld.add("aggregate", {"keys": keys, "aggs": aggs}, [node]))


@pytest.mark.parametrize("kind", ["keyless_sum", "keyed_fold", "windowed_fold", "rows"])
def test_coalesced_morsels_match_numpy_at_one_kernel_shape(monkeypatch, kind):
    """Static morsels merged from thinned batches give the numpy backend's
    bytes (a Q6-shaped keyless float sum, a one-hot keyed fold, a windowed
    fold above 256 groups, a row stream); every merged morsel reaches the
    kernel at ``morsel_rows`` rows, and a lone batch at its own rows
    rounded up to a tile."""
    from repro.core.backend import FusedChainPlan

    launches = []
    orig = FusedChainPlan._launch

    def spy(self, arrs, gidx, n, *args, **kwargs):
        launches.append((n, gidx.shape[0], arrs["pred"].shape[0]))
        return orig(self, arrs, gidx, n, *args, **kwargs)

    monkeypatch.setattr(FusedChainPlan, "_launch", spy)
    rng = np.random.default_rng(40)
    n = sum(COALESCE_SIZES)
    batch = RecordBatch.from_pydict(
        {
            "price": rng.uniform(1.0, 100.0, n).astype(np.float32),
            "disc": rng.uniform(0.0, 0.1, n).astype(np.float32),
            "qty": rng.integers(1, 50, n).astype(np.int32),
            "k": rng.integers(0, 6, n).astype(np.int32),
            "g": (np.arange(n) % 700).astype(np.int64),
        }
    )
    edges = np.cumsum([0] + COALESCE_SIZES)

    def sdf():
        def gen():
            for a, b in zip(edges[:-1], edges[1:]):
                yield batch.slice(a, b)

        return StreamingDataFrame(batch.schema, gen)

    outs = {}
    for backend in ("numpy", "pallas"):
        stats = ExecutorStats()
        cfg = ExecutorConfig(num_workers=2, morsel_rows=2048, backend=backend)
        outs[backend] = execute_parallel(_coalesce_dag(kind), lambda nn: sdf(), cfg, stats=stats).collect()
        prog = stats.progress()
        assert prog["morsels_done"] == 4
        assert prog["micromorsels_coalesced"] == 3
    _assert_byte_identical(outs["pallas"], outs["numpy"])
    assert prog["fused_launches"] == 4
    assert sorted((rows, pad) for rows, pad, _ in launches) == COALESCE_LAUNCHES
    assert all(pad == pred_rows for _, pad, pred_rows in launches)


# ---------------------------------------------------------------------------
# no silent fallback: kernel failures, missing jax, bad devices, the cache
# ---------------------------------------------------------------------------
def _cook_dag(kind):
    bld = Dag.build()
    s = bld.source("dacp://h:1/d")
    if kind == "fused":
        node = bld.add("filter", {"predicate": col("f32_a") > 0.0}, [s])
        node = bld.add("select", {"columns": ["f32_a", "i64_d"]}, [node])
    elif kind == "project":  # a projecting stream chain
        node = bld.add("filter", {"predicate": col("f32_a") > 0.0}, [s])
        node = bld.add("project", {"exprs": {"y": col("f32_a") * 2.0}, "keep": False}, [node])
    elif kind == "fold":  # one-hot fold: at most 9 groups
        node = bld.add("filter", {"predicate": col("f32_a") > -1.0}, [s])
        aggs = {"n": {"fn": "count"}, "lo": {"fn": "min", "column": "f32_b"}}
        node = bld.add("aggregate", {"keys": ["i32_e"], "aggs": aggs}, [node])
    elif kind == "global_fold":  # no keys: one group
        node = bld.add("aggregate", {"keys": [], "aggs": {"s": {"fn": "sum", "column": "i64_d"}}}, [s])
    else:  # windowed fold: 700 distinct keys in one morsel, above the 256-group cap
        aggs = {"n": {"fn": "count"}, "s": {"fn": "sum", "column": "f32_a"}}
        node = bld.add("aggregate", {"keys": ["i64_d"], "aggs": aggs}, [s])
    return bld.finish(node)


@pytest.mark.parametrize(
    "kind", ["fused", "project", "fold", "global_fold", "windowed"], ids=lambda k: f"{k}-fused_chain_tiles"
)
def test_kernel_failure_fails_the_cook(monkeypatch, kind):
    """A fused launch that raises fails the request — in a stream chain, a
    projecting chain, a one-hot fold, a keyless fold and a windowed fold;
    it never comes back as the numpy answer."""
    from repro.kernels import ops

    backend = get_backend("pallas")
    backend._ops()

    def broken(*args, **kwargs):
        raise RuntimeError("fused_chain_tiles failed")

    monkeypatch.setattr(ops, "fused_chain_tiles", broken)
    batch = _random_batch(np.random.default_rng(30))
    cfg = ExecutorConfig(num_workers=2, morsel_rows=N_ROWS, backend="pallas")
    with pytest.raises(RuntimeError, match="fused_chain_tiles failed"):
        execute_parallel(_cook_dag(kind), lambda n: _sdf(batch, rows=N_ROWS), cfg).collect()


def test_pallas_backend_needs_jax(monkeypatch):
    import sys

    from repro.core.backend import available_backends

    monkeypatch.setitem(sys.modules, "jax", None)  # import jax -> ImportError
    assert available_backends() == ["numpy"]
    with pytest.raises(RuntimeError, match="needs jax"):
        get_backend("pallas")
    assert get_backend("auto").name == "numpy"  # no jax: auto means numpy


def test_auto_backend_surfaces_jax_init_errors(monkeypatch):
    """A chip that fails to initialise is an error, not a silent numpy."""

    def busy():
        raise RuntimeError("TPU held by another process")

    monkeypatch.setattr(jax, "default_backend", busy)
    with pytest.raises(RuntimeError, match="held by another process"):
        get_backend("auto")


@pytest.mark.parametrize("via_env", [False, True])
def test_out_of_range_device_index_raises(monkeypatch, via_env):
    batch = _random_batch(np.random.default_rng(31))
    dag = _cook_dag("fused")
    if via_env:
        monkeypatch.setenv("DACP_DEVICES", "0,99")
        cfg = ExecutorConfig(num_workers=2, morsel_rows=200, backend="pallas")
    else:
        cfg = ExecutorConfig(num_workers=2, morsel_rows=200, backend="pallas", devices=(99,))
    with pytest.raises(ValueError, match="out of range"):
        for _ in range(2):  # the round-robin reaches the bad index
            execute_parallel(dag, lambda n: _sdf(batch), cfg).collect()


def test_pinned_device_counts_launches():
    """Pinned pipelines report where their fused launches ran."""
    batch = _random_batch(np.random.default_rng(32))
    stats = ExecutorStats()
    cfg = ExecutorConfig(num_workers=2, morsel_rows=200, backend="pallas", devices=(0,))
    got = execute_parallel(_cook_dag("fused"), lambda n: _sdf(batch), cfg, stats=stats).collect()
    _assert_byte_identical(got, _run(_cook_dag("fused"), batch, "numpy"))
    prog = stats.progress()
    assert prog["device_launches"] == {jax.devices()[0].id: prog["fused_launches"]}


def test_compile_cache_default_is_fixed_inside_checkout():
    import pathlib

    from repro.core.backend import COMPILE_CACHE_DIR

    repo = pathlib.Path(__file__).resolve().parents[1]
    assert COMPILE_CACHE_DIR == repo / ".jax_cache"
    ignored = (repo / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_follows_env(tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins where set (jax reads it at
    import, so this runs in a fresh interpreter); otherwise the kernels'
    first load points the cache at the fixed checkout path."""
    import os
    import pathlib
    import subprocess
    import sys

    from repro.core.backend import COMPILE_CACHE_DIR

    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=src)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = (
        "import jax\n"
        "from repro.core.backend import get_backend\n"
        "get_backend('pallas')._ops()\n"
        "print(jax.config.jax_compilation_cache_dir, jax.config.jax_persistent_cache_min_compile_time_secs)\n"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, check=True)
    cache_dir, min_secs = out.stdout.split()
    assert cache_dir == str(tmp_path if env_dir else COMPILE_CACHE_DIR)
    assert float(min_secs) == 0.0
