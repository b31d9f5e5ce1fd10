"""Native column projection in the columnar adapter: a projected scan reads
only the part-file members of the columns asked for, and gives the same
batches as the full scan followed by ``select``."""

import os

import numpy as np
import pytest

from repro.core.batch import RecordBatch
from repro.core.expr import col
from repro.core.sdf import StreamingDataFrame
from repro.server.adapters import ColumnarAdapter
from repro.server.datasource import scan_path, write_sdf_dataset

PARTS, PART_ROWS = 3, 700


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    """A columnar dataset of PARTS part files: two fixed-width columns and
    two string columns."""
    rng = np.random.default_rng(11)
    n = PARTS * PART_ROWS
    full = RecordBatch.from_pydict(
        {
            "k": rng.integers(0, 7, n).astype(np.int32),
            "mode": np.asarray([("AIR", "RAIL", "TRUCK")[i % 3] for i in range(n)]),
            "x": rng.standard_normal(n),
            "note": np.asarray([f"comment {i % 53} " * (1 + i % 4) for i in range(n)]),
        }
    )
    path = str(tmp_path_factory.mktemp("proj") / "tbl")

    def gen():
        for p in range(PARTS):
            yield full.slice(p * PART_ROWS, (p + 1) * PART_ROWS)

    write_sdf_dataset(path, StreamingDataFrame(full.schema, gen))
    return path, full


def _member_sizes(path) -> dict:
    sizes = {}
    for part in sorted(os.listdir(path)):
        if part.endswith(".npz"):
            with np.load(os.path.join(path, part)) as z:
                for info in z.zip.infolist():
                    name = info.filename.removesuffix(".npy")
                    sizes[name] = sizes.get(name, 0) + info.file_size
    return sizes


def _assert_same_batches(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.schema.names == w.schema.names
        assert [f.dtype for f in g.schema] == [f.dtype for f in w.schema]
        for a, b in zip(g.columns, w.columns):
            assert a.dtype == b.dtype
            if a.dtype.is_varwidth:
                assert np.array_equal(a.offsets, b.offsets) and a.offsets.dtype == b.offsets.dtype
                assert a.data.tobytes() == b.data.tobytes()
            else:
                assert a.values.dtype == b.values.dtype
                assert a.values.tobytes() == b.values.tobytes()
            assert (a.validity is None) == (b.validity is None)


@pytest.mark.parametrize("scan_workers", [1, 2])
@pytest.mark.parametrize("part_range", [None, (1, 3)])
@pytest.mark.parametrize(
    "columns, predicate",
    [
        (["x", "k"], None),
        (["mode", "x"], None),
        (["k", "x"], col("mode") == "RAIL"),
        (["note"], col("x") > 0.0),
    ],
)
def test_projected_scan_equals_full_scan_then_select(dataset, columns, predicate, scan_workers, part_range):
    path, _full = dataset
    kw = dict(predicate=predicate, batch_rows=256, scan_workers=scan_workers, part_range=part_range)
    got = list(scan_path(path, columns=columns, **kw).iter_batches())
    # the same scan with the columnar adapter made to read every member
    unprojected = ColumnarAdapter(path).scan(
        columns=None, batch_rows=256, scan_workers=scan_workers, part_range=part_range
    )
    want = []
    for b in unprojected.iter_batches():
        if predicate is not None:
            mask = np.asarray(predicate.evaluate(b), bool)
            if not mask.any():
                continue
            if not mask.all():
                b = b.filter(mask)
        want.append(b.select(columns))
    _assert_same_batches(got, want)


def test_adapter_streams_the_columns_in_the_order_given(dataset):
    path, full = dataset
    sdf = ColumnarAdapter(path).scan(columns=["x", "note"], batch_rows=PART_ROWS)
    assert sdf.schema.names == ["x", "note"]
    got = list(sdf.iter_batches())
    want = [full.slice(p * PART_ROWS, (p + 1) * PART_ROWS).select(["x", "note"]) for p in range(PARTS)]
    _assert_same_batches(got, want)


def test_members_outside_the_projection_are_never_read(dataset, monkeypatch):
    from repro.server.adapters import columnar

    path, _full = dataset
    accessed = []
    orig = np.lib.npyio.NpzFile.__getitem__
    orig_mapped = columnar._mapped_member

    def spy(self, key):
        accessed.append(key)
        return orig(self, key)

    def spy_mapped(mm, f, info):
        a = orig_mapped(mm, f, info)
        if a is not None:
            accessed.append(info.filename.removesuffix(".npy"))
        return a

    # a member is read either mapped in place or through np.load
    monkeypatch.setattr(np.lib.npyio.NpzFile, "__getitem__", spy)
    monkeypatch.setattr(columnar, "_mapped_member", spy_mapped)
    report = {}
    rows = sum(b.num_rows for b in scan_path(path, columns=["k", "note"], scan_workers=2, report=report).iter_batches())
    assert rows == PARTS * PART_ROWS
    assert sorted(set(accessed)) == ["k", "note__data", "note__offsets"]
    assert len(accessed) == 3 * PARTS
    sizes = _member_sizes(path)
    assert report["bytes_read"] == report["bytes_needed"] == sizes["k"] + sizes["note__offsets"] + sizes["note__data"]


def test_part_span_bytes_are_the_projected_members(dataset, monkeypatch):
    from repro.server.adapters import columnar

    path, _full = dataset
    seen = []
    real_span = columnar.span

    def spy(name, **stats):
        if name == "dacp.scan.part":
            seen.append(stats)
        return real_span(name, **stats)

    monkeypatch.setattr(columnar, "span", spy)
    report = {}
    list(scan_path(path, columns=["x"], predicate=col("k") > 2, report=report).iter_batches())
    sizes = _member_sizes(path)
    # the reader pool opens part spans in whatever order its threads start
    assert sorted(st["part"] for st in seen) == [f"part-{i:05d}.npz" for i in range(PARTS)]
    assert sum(st["bytes"] for st in seen) == report["bytes_read"] == sizes["x"] + sizes["k"]


@pytest.mark.parametrize("columns", [None, []])
def test_no_projection_reads_every_member_and_keeps_the_rows(dataset, columns):
    path, full = dataset
    report = {}
    sdf = ColumnarAdapter(path).scan(columns=columns, batch_rows=PART_ROWS, report=report)
    assert sdf.schema.names == full.schema.names
    got = list(sdf.iter_batches())
    assert sum(b.num_rows for b in got) == PARTS * PART_ROWS
    _assert_same_batches(got, [full.slice(p * PART_ROWS, (p + 1) * PART_ROWS) for p in range(PARTS)])
    assert report["bytes_read"] == sum(_member_sizes(path).values())


def test_get_with_columns_through_faird_returns_the_same_table(dataset):
    from repro.client import LocalNetwork
    from repro.server import FairdServer

    path, full = dataset
    net = LocalNetwork()
    srv = FairdServer("h1:3101")
    srv.catalog.register_path("ds", os.path.dirname(path))
    net.register(srv)
    client = net.client_for("h1:3101")
    uri = "dacp://h1:3101/ds/tbl"
    got = client.get(uri, columns=["note", "k"]).collect()
    assert got.to_pydict() == full.select(["note", "k"]).to_pydict()
    mask = np.asarray(full.column("x").values > 0.5)
    got = client.get(uri, columns=["mode"], predicate=col("x") > 0.5).collect()
    assert got.to_pydict() == full.filter(mask).select(["mode"]).to_pydict()
