"""The columnar adapter maps stored part-file members in place: the batches
are byte-identical to those of ``np.load``'s read-check-copy path, every
member's CRC-32 is still checked, writes to a scanned array never reach the
file, and a part replaced mid-scan leaves the arrays already handed out
intact.  The engagement counters reach PING and STATUS."""

import os
import shutil
import zipfile

import numpy as np
import pytest

from repro.core.batch import RecordBatch
from repro.core.expr import col
from repro.core.sdf import StreamingDataFrame
from repro.server.adapters import columnar
from repro.server.datasource import scan_path, write_sdf_dataset

PARTS, PART_ROWS = 3, 600
# the members of one part file: five fixed-width columns and one string column
MEMBERS = ["i8", "i32", "i64", "f32", "f64", "s__offsets", "s__data"]


def _table(seed: int) -> RecordBatch:
    rng = np.random.default_rng(seed)
    n = PARTS * PART_ROWS
    return RecordBatch.from_pydict(
        {
            "i8": rng.integers(-100, 100, n).astype(np.int8),
            "i32": rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32),
            "i64": rng.integers(-(2**62), 2**62, n).astype(np.int64),
            "f32": rng.standard_normal(n).astype(np.float32),
            "f64": rng.standard_normal(n),
            "s": np.asarray([f"row {i % 41} " * (1 + i % 3) for i in range(n)]),
        }
    )


def _write(path: str, table: RecordBatch) -> str:
    def gen():
        for p in range(PARTS):
            yield table.slice(p * PART_ROWS, (p + 1) * PART_ROWS)

    write_sdf_dataset(path, StreamingDataFrame(table.schema, gen))
    return path


@pytest.fixture
def dataset(tmp_path):
    return _write(str(tmp_path / "tbl"), _table(5))


def _part(path: str, i: int = 0) -> str:
    return os.path.join(path, f"part-{i:05d}.npz")


def _copied_path(monkeypatch):
    """Send every member through ``np.load``, the read before mapping."""
    monkeypatch.setattr(columnar, "_mapped_member", lambda mm, f, info: None)


def _batch_bytes(batches) -> list:
    out = []
    for b in batches:
        cols = []
        for f, c in zip(b.schema, b.columns):
            if f.dtype.is_varwidth:
                cols.append((f.name, f.dtype.name, c.offsets.dtype.str, c.offsets.tobytes(), c.data.tobytes()))
            else:
                cols.append((f.name, f.dtype.name, c.values.dtype.str, c.values.tobytes()))
            assert c.validity is None
        out.append(cols)
    return out


@pytest.mark.parametrize("scan_workers", [1, 2])
@pytest.mark.parametrize("part_range", [None, (1, 3)])
@pytest.mark.parametrize("predicate", [None, col("i8") > 10])
def test_mapped_scan_is_byte_identical_to_np_load(dataset, monkeypatch, predicate, part_range, scan_workers):
    kw = dict(predicate=predicate, batch_rows=256, scan_workers=scan_workers, part_range=part_range)
    mapped_report, copied_report = {}, {}
    got = list(scan_path(dataset, report=mapped_report, **kw).iter_batches())
    with monkeypatch.context() as m:
        _copied_path(m)
        want = list(scan_path(dataset, report=copied_report, **kw).iter_batches())
    assert got and _batch_bytes(got) == _batch_bytes(want)
    parts = PARTS if part_range is None else part_range[1] - part_range[0]
    assert (mapped_report["members_mapped"], mapped_report["members_copied"]) == (parts * len(MEMBERS), 0)
    assert (copied_report["members_mapped"], copied_report["members_copied"]) == (0, parts * len(MEMBERS))
    assert mapped_report["bytes_read"] == copied_report["bytes_read"]


def _compress(path: str) -> None:
    """Rewrite the part file at ``path`` with ``np.savez_compressed``,
    replacing it as PUT does."""
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    with open(path + ".tmp", "wb") as f:
        np.savez_compressed(f, **arrays)
    os.replace(path + ".tmp", path)


def test_compressed_part_is_copied_and_scans_the_same(dataset):
    want = list(scan_path(dataset, batch_rows=256).iter_batches())
    _compress(_part(dataset, 1))
    report = {}
    got = list(scan_path(dataset, batch_rows=256, scan_workers=2, report=report).iter_batches())
    assert _batch_bytes(got) == _batch_bytes(want)
    assert report["members_copied"] == len(MEMBERS)
    assert report["members_mapped"] == (PARTS - 1) * len(MEMBERS)


@pytest.mark.parametrize("member", ["f32", "s__data"])
def test_a_flipped_data_byte_fails_the_scan(dataset, member):
    path = _part(dataset, 2)
    with zipfile.ZipFile(path) as zf:
        info = zf.getinfo(f"{member}.npy")
    with open(path, "r+b") as f:
        f.seek(info.header_offset + 26)
        name_len, extra_len = np.frombuffer(f.read(4), "<u2")
        last = info.header_offset + 30 + int(name_len) + int(extra_len) + info.file_size - 1
        f.seek(last)
        byte = f.read(1)[0]
        f.seek(last)
        f.write(bytes([byte ^ 0x01]))
    with pytest.raises(zipfile.BadZipFile, match="CRC"):
        list(scan_path(dataset, columns=[member.removesuffix("__data")]).iter_batches())
    # a scan that needs no member of the damaged column still reads
    assert sum(b.num_rows for b in scan_path(dataset, columns=["i8"]).iter_batches()) == PARTS * PART_ROWS


def test_writes_to_a_scanned_array_never_reach_the_file(dataset):
    before = open(_part(dataset), "rb").read()
    first = next(scan_path(dataset, columns=["f32", "s"], batch_rows=256).iter_batches())
    values, data = first.column("f32").values, first.column("s").data
    assert values.flags.writeable
    values[:] = 7.0
    data[:] = 0
    assert open(_part(dataset), "rb").read() == before
    again = next(scan_path(dataset, columns=["f32"], batch_rows=256).iter_batches())
    assert not np.any(again.column("f32").values == 7.0)


def test_a_part_replaced_mid_scan_leaves_handed_out_arrays_intact(dataset, tmp_path):
    orig = _write(str(tmp_path / "orig"), _table(5))
    other = _write(str(tmp_path / "other"), _table(6))
    it = scan_path(dataset, batch_rows=256, scan_workers=1).iter_batches()
    first = next(it)
    for i in range(PARTS):
        shutil.copyfile(_part(other, i), _part(dataset, i) + ".new")
        os.replace(_part(dataset, i) + ".new", _part(dataset, i))
    rest = list(it)
    # part 0 was mapped before the replacement, the later parts after it
    per_part = -(-PART_ROWS // 256)
    old0, later = [first] + rest[: per_part - 1], rest[per_part - 1 :]
    assert _batch_bytes(old0) == _batch_bytes(scan_path(orig, batch_rows=256, part_range=(0, 1)).iter_batches())
    assert _batch_bytes(later) == _batch_bytes(scan_path(other, batch_rows=256, part_range=(1, PARTS)).iter_batches())


def test_part_span_counts_the_members_it_mapped(dataset, monkeypatch):
    seen = []

    class _Span:
        def __init__(self, stats):
            self.stats = stats

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

        def set_metadata(self, **stats):
            self.stats.update(stats)

    def spy(name, **stats):
        if name == "dacp.scan.part":
            seen.append(stats)
        return _Span(stats)

    monkeypatch.setattr(columnar, "span", spy)
    list(scan_path(dataset, columns=["i32", "s"], scan_workers=2).iter_batches())
    assert sorted(st["part"] for st in seen) == [f"part-{i:05d}.npz" for i in range(PARTS)]
    assert [st["mapped"] for st in seen] == [3] * PARTS


@pytest.mark.parametrize(
    "array, mapped",
    [
        (np.arange(12, dtype=np.int32).reshape(3, 4), True),
        (np.asfortranarray(np.arange(12, dtype=np.float64).reshape(3, 4)), False),
        (np.arange(5, dtype=">f4"), True),
        (np.array(3.5), True),
        (np.zeros(0, np.int16), True),
        (np.array([(1, 2.0)], dtype=[("a", "<i4"), ("b", "<f8")]), True),
        (np.array(["x", 1], dtype=object), False),
    ],
)
def test_read_members_maps_what_np_load_reads(tmp_path, array, mapped):
    path = tmp_path / "one.npz"
    np.savez(path, a=array)
    with open(path, "rb") as f, np.load(f) as z:
        if array.dtype.hasobject:
            with pytest.raises(ValueError, match="allow_pickle"):
                columnar._read_members(f, z, z.zip.infolist())
            return
        arrays, n_mapped = columnar._read_members(f, z, z.zip.infolist())
        want = z["a"]
    got = arrays["a"]
    assert n_mapped == int(mapped)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes(order="A") == want.tobytes(order="A")


def test_scan_member_counters_reach_ping_and_status(dataset):
    from repro.client import LocalNetwork
    from repro.core.executor import ExecutorConfig
    from repro.server import FairdServer

    net = LocalNetwork()
    srv = FairdServer("h1:3101", executor=ExecutorConfig(num_workers=2, backend="numpy"))
    srv.catalog.register_path("ds", os.path.dirname(dataset))
    net.register(srv)
    client = net.client_for("h1:3101")
    client.open("dacp://h1:3101/ds/tbl").filter(col("f64") > 0.0).group_by("i8").agg(n="count").collect()
    ex = client.ping()["executor"]
    # "i8" and "f64" are read from each part, both mapped
    assert (ex["scan_members_mapped"], ex["scan_members_copied"]) == (2 * PARTS, 0)

    _compress(_part(dataset, 0))
    fl = client.open("dacp://h1:3101/ds/tbl").group_by("i8").agg(n="count").start()
    fl.collect()
    st = fl.status()["executor"]
    assert (st["scan_members_mapped"], st["scan_members_copied"]) == (PARTS - 1, 1)
