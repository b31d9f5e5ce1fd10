"""Columnar-dataset adapter: a directory of ``part-*.npz`` files plus a
``_schema.json`` sidecar (what PUT persistence writes).  The sorted part
file is the ``part_range`` split unit — batches never span part files, so
disjoint contiguous ranges concatenated in order reproduce the full scan
byte-identically (the partition-parallel planner's contract).

The adapter projects natively: ``scan(columns=[...])`` reads only the
members of those columns (``c``, or ``c__offsets`` and ``c__data`` for a
string column) from each part file it opens, and streams them in the order
given.  ``columns=None`` or an empty list reads every member, as Parquet's
``names or None`` does, so a zero-column plan keeps its row count.

A part file is mapped, not read: each stored (uncompressed) member becomes
an array that views the mapping in place, after one CRC-32 pass over its
bytes against the zip directory.  Members that are compressed or
encrypted, hold objects, or are multi-dimensional Fortran-ordered arrays go
through ``np.load``, which copies them.  Part files are only ever replaced
(``os.replace``), never rewritten in place, so a mapping stays valid.

Its ``report`` counts ``bytes_read`` (the bytes of the members read, all of
which the CRC pass reads), ``bytes_needed`` (those of the members of
``columns_needed``, the columns the plan asked for), ``rows_read``,
``members_mapped`` and ``members_copied``; spans ``dacp.scan.part`` time
one part file's read (stat ``mapped``: its members mapped) and
``dacp.scan.batch`` one batch's build.
"""

from __future__ import annotations

import json
import math
import mmap
import os
import struct
import zipfile
import zlib
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.core.batch import Column, RecordBatch
from repro.core.schema import Schema
from repro.core.sdf import StreamingDataFrame
from repro.core.trace import bind, span
from repro.server.adapters.base import DEFAULT_BATCH_ROWS, Capabilities, ScanAdapter
from repro.server.adapters.structured import npz_arrays_sdf

__all__ = ["ColumnarAdapter", "is_columnar_dataset", "columnar_parts"]


def is_columnar_dataset(path: str) -> bool:
    return os.path.isdir(path) and os.path.exists(os.path.join(path, "_schema.json"))


def columnar_parts(root: str) -> list:
    return sorted(p for p in os.listdir(root) if p.startswith("part-") and p.endswith(".npz"))


def _member_column(member: str) -> str:
    """The column a part file's member holds (``c``, ``c__offsets``, ``c__data``)."""
    for suffix in ("__offsets", "__data"):
        if member.endswith(suffix):
            return member[: -len(suffix)]
    return member


# a zip local file header: signature, 22 bytes of fields the central
# directory repeats, then the lengths of the name and the extra field
_LOCAL_HEADER = struct.Struct("<4s22xHH")


def _read_members(f, z, infos: list) -> tuple[dict, int]:
    """The arrays of the members ``infos`` of the part file open as ``f``
    (``z`` its ``np.load``), keyed as ``z`` keys them, and how many of them
    were mapped.

    A stored member is a view of the file mapped copy-on-write: writes to
    it go to private pages, never to the file, and the mapping lives as long
    as any array that views it, so a part replaced (``os.replace``) while
    its arrays are out leaves them intact.  Each mapped member's bytes are
    checked against the directory's CRC-32 in one ``zlib.crc32`` call (which
    releases the interpreter lock); a mismatch raises ``zipfile.BadZipFile``,
    as ``np.load`` does.  A member that is compressed or encrypted, holds
    objects or is a Fortran-ordered array of more than one dimension goes
    through ``np.load``, which reads, checks and copies it."""
    mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_COPY)
    arrays, mapped = {}, 0
    for info in infos:
        key = info.filename.removesuffix(".npy")
        a = _mapped_member(mm, f, info)
        if a is None:
            a = z[key]
        else:
            mapped += 1
        arrays[key] = a
    return arrays, mapped


def _mapped_member(mm: mmap.mmap, f, info: zipfile.ZipInfo):
    """``info``'s array as a view of ``mm``, CRC-checked; None when the
    member has to go through ``np.load``."""
    if info.compress_type != zipfile.ZIP_STORED or info.flag_bits & 0x1:
        return None
    sig, name_len, extra_len = _LOCAL_HEADER.unpack_from(mm, info.header_offset)
    if sig != b"PK\x03\x04":
        raise zipfile.BadZipFile(f"Bad magic number for file header of {info.filename!r}")
    start = info.header_offset + _LOCAL_HEADER.size + name_len + extra_len
    end = start + info.file_size
    f.seek(start)
    # np.load's own header reader (format versions 1.0, 2.0 and 3.0)
    shape, fortran_order, dtype = np.lib.format._read_array_header(f, np.lib.format.read_magic(f))
    offset, count = f.tell(), math.prod(shape)
    if dtype.hasobject or (fortran_order and len(shape) > 1) or offset + count * dtype.itemsize > end:
        return None
    with memoryview(mm) as view:
        crc = zlib.crc32(view[start:end])
    if crc != info.CRC:
        raise zipfile.BadZipFile(f"Bad CRC-32 for file {info.filename!r}")
    return np.frombuffer(mm, dtype, count, offset).reshape(shape)


class ColumnarAdapter(ScanAdapter):
    format = "columnar"

    def capabilities(self) -> Capabilities:
        return Capabilities(column_projection=True, part_ranges=True)

    def schema(self) -> Schema:
        with open(os.path.join(self.path, "_schema.json")) as f:
            return Schema.from_json(json.load(f))

    def part_count(self) -> int | None:
        return len(columnar_parts(self.path))

    def version(self) -> dict:
        # the newest part file + the part list length catch both appended
        # parts and a rewritten sidecar schema
        latest, size = 0, 0
        for fn in ["_schema.json"] + columnar_parts(self.path):
            try:
                st = os.stat(os.path.join(self.path, fn))
            except OSError:
                continue
            latest = max(latest, st.st_mtime_ns)
            size += st.st_size
        return {"size": size, "mtime_ns": latest, "parts": self.part_count()}

    def scan(
        self,
        columns=None,
        predicate=None,
        batch_rows=DEFAULT_BATCH_ROWS,
        scan_workers: int = 1,
        part_range=None,
        report: dict | None = None,
        **_kw,
    ):
        root = self.path
        full = self.schema()
        schema = full.select(columns) if columns else full
        wanted = set(schema.names)
        parts = columnar_parts(root)
        if part_range is not None:
            lo, hi = int(part_range[0]), int(part_range[1])
            parts = parts[lo:hi]
        if report is not None:
            needed = set(report.get("columns_needed", full.names))
            for key in ("bytes_read", "bytes_needed", "rows_read", "members_mapped", "members_copied"):
                report.setdefault(key, 0)

        def _cast(batch: RecordBatch) -> RecordBatch:
            # npz inference loses STRING-vs-BINARY and column order; restore both
            cols = []
            for f in schema:
                c = batch.column(f.name)
                if f.dtype.is_varwidth and c.dtype is not f.dtype:
                    c = Column(f.dtype, offsets=c.offsets, data=c.data, validity=c.validity)
                cols.append(c)
            return RecordBatch(schema, cols)

        def _load(p: str) -> tuple:
            with open(os.path.join(root, p), "rb") as f, np.load(f) as z:
                infos = {i.filename.removesuffix(".npy"): i for i in z.zip.infolist()}
                members = [infos[k] for k in z.files if _member_column(k) in wanted]
                with span("dacp.scan.part", part=p, bytes=sum(i.file_size for i in members)) as sp:
                    arrays, mapped = _read_members(f, z, members)
                    sp.set_metadata(mapped=mapped)
            return arrays, {k: i.file_size for k, i in infos.items()}, mapped

        def _batches(loaded: tuple):
            # runs on the consuming thread, the report's one writer
            arrays, sizes, mapped = loaded
            if report is not None:
                report["bytes_read"] += sum(sizes[k] for k in arrays)
                report["bytes_needed"] += sum(n for m, n in sizes.items() if _member_column(m) in needed)
                report["members_mapped"] += mapped
                report["members_copied"] += len(arrays) - mapped
            it = npz_arrays_sdf(arrays, batch_rows).iter_batches()
            while True:
                with span("dacp.scan.batch"):
                    b = next(it, None)
                    if b is not None:
                        b = _cast(b)
                if b is None:
                    return
                if report is not None:
                    report["rows_read"] += b.num_rows
                yield b

        def gen():
            if scan_workers <= 1 or len(parts) <= 1:
                for p in parts:
                    yield from _batches(_load(p))
                return
            # bounded read-ahead: up to scan_workers part files decode in
            # background threads while earlier parts stream out, in part order
            with ThreadPoolExecutor(max_workers=scan_workers) as pool:
                pending: deque = deque()
                it = iter(parts)
                for p in it:
                    pending.append(pool.submit(bind(_load), p))
                    if len(pending) >= scan_workers:
                        break
                while pending:
                    loaded = pending.popleft().result()
                    nxt = next(it, None)
                    if nxt is not None:
                        pending.append(pool.submit(bind(_load), nxt))
                    yield from _batches(loaded)

        return StreamingDataFrame(schema, gen)
