"""Property-based tests (hypothesis) on the protocol's invariants."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st

from repro.core import Dag, RecordBatch, Schema, StreamingDataFrame, col, execute, optimize
from repro.core.batch import concat_batches

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

names = st.sampled_from(["a", "b", "c", "d"])


@st.composite
def tables(draw, min_rows=0, max_rows=60):
    n = draw(st.integers(min_rows, max_rows))
    cols = {}
    cols["a"] = draw(st.lists(st.integers(-1000, 1000), min_size=n, max_size=n))
    cols["b"] = draw(st.lists(st.floats(-1e3, 1e3, allow_nan=False, width=32), min_size=n, max_size=n))
    cols["s"] = draw(st.lists(st.text(alphabet="xyz", max_size=5), min_size=n, max_size=n))
    cols["blob"] = draw(st.lists(st.binary(max_size=12), min_size=n, max_size=n))
    schema = Schema([("a", "int64"), ("b", "float32"), ("s", "string"), ("blob", "binary")])
    return RecordBatch.from_pydict(
        {"a": np.asarray(cols["a"], np.int64), "b": np.asarray(cols["b"], np.float32), "s": cols["s"], "blob": cols["blob"]},
        schema,
    )


@given(tables(min_rows=0))
def test_wire_roundtrip_identity(batch):
    hdr, bufs = batch.to_buffers()
    payload = memoryview(RecordBatch.payload_bytes(bufs))
    back = RecordBatch.from_buffers(batch.schema, hdr, payload)
    assert back.to_pydict() == batch.to_pydict()


@given(tables(min_rows=1), st.integers(1, 17))
def test_rebatch_invariance(batch, rows):
    """Re-batching changes framing, never content."""
    sdf = StreamingDataFrame.from_batches([batch])
    bld = Dag.build()
    s = bld.source("dacp://h:1/x")
    r = bld.add("rebatch", {"rows": rows}, [s])
    dag = bld.finish(r)
    out = execute(dag, lambda n: sdf)
    rebatched = list(out.iter_batches())
    assert all(b.num_rows <= rows for b in rebatched[:-1])
    merged = concat_batches(rebatched) if rebatched else batch.slice(0, 0)
    assert merged.to_pydict() == batch.to_pydict()


@given(tables(min_rows=1), st.integers(-500, 500))
def test_pushdown_equivalence_property(batch, threshold):
    """optimize(dag) ≡ dag for filter/select/limit chains (the paper's
    pushdown must be semantics-preserving)."""
    sdf = StreamingDataFrame.from_batches([batch])
    bld = Dag.build()
    s = bld.source("dacp://h:1/x")
    f = bld.add("filter", {"predicate": col("a") > threshold}, [s])
    sel = bld.add("select", {"columns": ["a", "s"]}, [f])
    f2 = bld.add("filter", {"predicate": col("a") % 2 == 0}, [sel])
    lim = bld.add("limit", {"n": 7}, [f2])
    dag = bld.finish(lim)

    def scan_resolver(node):
        cols = node.params.get("columns")
        pred = node.params.get("predicate")

        def gen():
            for b in sdf.iter_batches():
                if pred is not None:
                    b = b.filter(np.asarray(pred.evaluate(b), bool))
                if cols is not None:
                    b = b.select([c for c in cols if c in b.schema])
                yield b

        schema = sdf.schema if cols is None else sdf.schema.select([c for c in cols if c in sdf.schema])
        return StreamingDataFrame(schema, gen)

    plain = execute(dag, lambda n: sdf).collect().to_pydict()
    opt = execute(optimize(dag), scan_resolver).collect().to_pydict()
    assert plain == opt


@given(tables(min_rows=2), st.data())
def test_filter_then_concat_is_subset(batch, data):
    thr = data.draw(st.integers(-1000, 1000))
    mask = np.asarray((col("a") > thr).evaluate(batch), bool)
    filtered = batch.filter(mask)
    assert filtered.num_rows == int(mask.sum())
    assert filtered.to_pydict()["a"] == [v for v, m in zip(batch.to_pydict()["a"], mask) if m]


@given(st.lists(st.floats(-100, 100, allow_nan=False, width=32), min_size=8, max_size=64))
def test_filter_select_kernel_property(vals):
    """The fused kernel's stream-mode compaction == numpy boolean indexing
    for arbitrary data."""
    import jax.numpy as jnp

    from repro.kernels import ops

    n = (len(vals) + 7) // 8 * 8
    arr = np.zeros((n, 4), np.float32)
    arr[: len(vals), 0] = vals
    arr[:, 1] = np.arange(n)
    planes = arr.view(np.int32)
    thr = np.float32(1.5)
    scalars = np.array([n, np.array([thr], np.float32).view(np.int32)[0], 0, 0], np.int32)
    zcol = jnp.zeros((n, 1), jnp.int32)
    out, counts, *_ = ops.fused_chain_tiles(
        scalars,
        jnp.asarray(planes[:, :1]),
        jnp.zeros(n, jnp.int32),
        jnp.asarray(planes),
        zcol,
        jnp.zeros((n, 1), jnp.float32),
        zcol,
        jnp.zeros((n, 1), jnp.float32),
        zcol,
        op="gt",
        kind="f32",
        descrs_f=(),
        descrs_i=(),
        csums=(),
        fns_f=("min",),
        fns_i=("min",),
        with_gidx=False,
        segmented=False,
        ngroups=8,
        tile=8,
    )
    out, counts = np.asarray(out), np.asarray(counts)
    mask = arr[:, 0] > thr
    assert counts.sum() == mask.sum()
    front = np.concatenate([out[i * 8 : i * 8 + c] for i, c in enumerate(counts)])
    np.testing.assert_array_equal(front[:, 1].view(np.float32), arr[mask][:, 1])
