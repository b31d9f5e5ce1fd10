"""Host spans (``repro.core.trace``) and the per-flow scan counters: the
span names are one closed set, spans cost nothing and keep nothing while no
profiler records, a traced run puts the caller's flow id on the spans of
every thread that works for it, and the scan counters reach the flow's
``ExecutorStats``, PING and STATUS."""

import ast
import glob
import os
import re
from pathlib import Path

import numpy as np
import pytest

from repro.core import trace
from repro.core.batch import RecordBatch
from repro.core.dag import Dag
from repro.core.executor import ExecutorConfig, ExecutorStats, execute_parallel
from repro.core.expr import col
from repro.core.sdf import StreamingDataFrame
from repro.server.datasource import scan_path, write_sdf_dataset

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
PARTS, PART_ROWS = 4, 3000


def _sources():
    return sorted(SRC.rglob("*.py"))


def test_span_names_are_closed_and_documented():
    opened = set()
    for path in _sources():
        opened |= set(re.findall(r'\bspan\(\s*"([^"]+)"', path.read_text()))
    assert opened == set(trace.SPANS)
    assert len(set(trace.SPANS)) == len(trace.SPANS)
    perf = (ROOT / "PERF.md").read_text()
    missing = [name for name in trace.SPANS if f"`{name}`" not in perf]
    assert not missing, f"PERF.md does not name {missing}"


def _yields_inside(node) -> bool:
    """A yield in ``node``'s body, not counting nested functions."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.Yield, ast.YieldFrom)):
            return True
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            stack.extend(ast.iter_child_nodes(n))
    return False


def test_no_span_encloses_a_yield():
    found = []
    for path in _sources():
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.With):
                continue
            opens = any(
                isinstance(c, ast.Call) and ast.unparse(c.func) in ("span", "trace.span")
                for item in node.items
                for c in ast.walk(item.context_expr)
            )
            if opens:
                found.append(node)
                assert not any(_yields_inside(stmt) for stmt in node.body), f"{path}:{node.lineno}"
    assert len(found) >= len(trace.SPANS)


def test_span_is_a_shared_noop_while_no_profiler_records():
    from jaxlib._profiler import TraceMe

    assert not TraceMe.is_enabled()
    a = trace.span("dacp.morsel", rows=3)
    with trace.flow("flow-x"):
        b = trace.span("dacp.cook", flow="other")
        with b as entered:
            entered.set_metadata(flow="flow-y")
    assert a is b is entered
    assert not hasattr(a, "__dict__")  # nothing to keep anything in
    assert getattr(trace._local, "flow", None) is None  # the flow id is reset on exit


# ---------------------------------------------------------------------------
# traced runs
# ---------------------------------------------------------------------------
def _dataset(root) -> str:
    """A columnar dataset of PARTS part files: key, float, and a string
    column that no plan below needs."""
    rng = np.random.default_rng(7)
    n = PARTS * PART_ROWS
    full = RecordBatch.from_pydict(
        {
            "k": rng.integers(0, 5, n).astype(np.int32),
            "x": rng.standard_normal(n).astype(np.float32),
            "note": np.asarray([f"comment {i % 97}" for i in range(n)]),
        }
    )
    path = str(root / "tbl")

    def gen():
        for p in range(PARTS):
            yield full.slice(p * PART_ROWS, (p + 1) * PART_ROWS)

    write_sdf_dataset(path, StreamingDataFrame(full.schema, gen))
    return path


def _agg_dag() -> Dag:
    bld = Dag.build()
    s = bld.add("source", {"uri": "dacp://h:1/tbl", "columns": ["k", "x"], "predicate": col("x") > 0.0})
    a = bld.add("aggregate", {"keys": ["k"], "aggs": {"n": {"fn": "count"}, "sx": {"fn": "sum", "column": "x"}}}, [s])
    return bld.finish(a)


def _traced(fn, log_dir) -> list:
    """Run ``fn`` under the profiler: ``(fn's result, [(thread line, span
    name, stats)])`` for every ``dacp.*`` host event."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            events.extend(((plane.name, i), e.name, dict(e.stats)) for e in line.events if e.name.startswith("dacp."))
    return result, events


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_traced_run_carries_the_flow_id_on_every_thread(tmp_path, backend):
    path = _dataset(tmp_path)
    stats = ExecutorStats()
    cfg = ExecutorConfig(num_workers=2, morsel_rows=1024, backend=backend)

    def resolver(node):
        p = node.params
        return scan_path(
            path, columns=p["columns"], predicate=p["predicate"], batch_rows=1500, scan_workers=2, report=stats.scan_report()
        )

    def run():
        with trace.flow("flow-7"):
            return execute_parallel(_agg_dag(), resolver, cfg, stats=stats).collect()

    out, events = _traced(run, tmp_path / "prof")
    assert sum(out.column("n").values) > 0
    assert {f for _line, _n, st in events for f in [st.get("flow")]} == {"flow-7"}
    lines = {}
    for line, name, _st in events:
        lines.setdefault(name, set()).add(line)
    want = {"dacp.scan.part", "dacp.scan.batch", "dacp.scan.filter", "dacp.morsel", "dacp.merge"}
    if backend == "pallas":
        want |= {"dacp.morsel.factorize", "dacp.morsel.encode", "dacp.morsel.launch", "dacp.morsel.sync", "dacp.morsel.fold"}
    assert want <= set(lines)
    # reader pool, prefetcher and workers are three sets of threads
    assert not lines["dacp.scan.part"] & lines["dacp.scan.batch"]
    assert not lines["dacp.morsel"] & lines["dacp.scan.batch"]
    parts = [st for _line, name, st in events if name == "dacp.scan.part"]
    assert sorted(st["part"] for st in parts) == [f"part-{i:05d}.npz" for i in range(PARTS)]
    assert sum(st["bytes"] for st in parts) == stats.progress()["scan_bytes_read"]


def _members(path) -> dict:
    """Bytes of each part-file member, summed over the dataset's parts."""
    members = {}
    for part in sorted(os.listdir(path)):
        if part.endswith(".npz"):
            with np.load(os.path.join(path, part)) as z:
                for info in z.zip.infolist():
                    members[info.filename] = members.get(info.filename, 0) + info.file_size
    return members


def test_scan_counters_count_what_the_scan_read_and_needed(tmp_path):
    path = _dataset(tmp_path)
    stats = ExecutorStats()
    cfg = ExecutorConfig(num_workers=2, morsel_rows=1024, backend="numpy")

    def resolver(node):
        p = node.params
        return scan_path(path, columns=p["columns"], predicate=p["predicate"], report=stats.scan_report())

    execute_parallel(_agg_dag(), resolver, cfg, stats=stats).collect()
    prog = stats.progress()
    members = _members(path)
    assert prog["scan_rows"] == PARTS * PART_ROWS
    # the columnar adapter projects: it reads just the members the plan needs
    assert prog["scan_bytes_read"] == prog["scan_bytes_needed"] == members["k.npy"] + members["x.npy"]
    assert 0 < prog["scan_bytes_read"] < sum(members.values())

    # a scan that names no columns still reads, and needs, every member
    stats = ExecutorStats()
    rows = sum(b.num_rows for b in scan_path(path, report=stats.scan_report()).iter_batches())
    prog = stats.progress()
    assert rows == prog["scan_rows"] == PARTS * PART_ROWS
    assert prog["scan_bytes_read"] == prog["scan_bytes_needed"] == sum(members.values())


def test_scan_counters_reach_ping_and_status(tmp_path):
    from repro.client import LocalNetwork
    from repro.server import FairdServer

    members = _members(_dataset(tmp_path))
    net = LocalNetwork()
    srv = FairdServer("h1:3101", executor=ExecutorConfig(num_workers=2, backend="numpy"))
    srv.catalog.register_path("ds", str(tmp_path))
    net.register(srv)
    client = net.client_for("h1:3101")
    frame = client.open("dacp://h1:3101/ds/tbl").filter(col("x") > 0.0).union(client.open("dacp://h1:3101/ds/tbl"))
    frame.group_by("k").agg(n="count").collect()
    ex = client.ping()["executor"]
    # a union's two sources add into the one flow's totals
    assert ex["scan_rows"] == 2 * PARTS * PART_ROWS
    # each side reads just its pruned columns ("k", plus "x" under the filter)
    assert ex["scan_bytes_read"] == ex["scan_bytes_needed"] == 2 * members["k.npy"] + members["x.npy"]

    fl = client.open("dacp://h1:3101/ds/tbl").group_by("k").agg(n="count").start()
    fl.collect()
    st = fl.status()["executor"]
    assert st["scan_rows"] == PARTS * PART_ROWS
    assert st["scan_bytes_read"] == st["scan_bytes_needed"] == members["k.npy"]


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_agg_map_span_opens_under_the_morsel(tmp_path, backend):
    """``dacp.agg.map`` (keys to group ids) is a named span, and a traced
    grouped run opens it inside a ``dacp.morsel`` on the same thread."""
    import jax
    from jax.profiler import ProfileData

    assert "dacp.agg.map" in trace.SPANS
    path = _dataset(tmp_path)
    cfg = ExecutorConfig(num_workers=2, morsel_rows=1024, backend=backend)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        with trace.flow("flow-9"):
            execute_parallel(_agg_dag(), lambda n: scan_path(path, columns=n.params["columns"]), cfg).collect()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(os.path.join(str(tmp_path / "prof"), "**", "*.xplane.pb"), recursive=True)
    nested = 0
    for plane in ProfileData.from_file(xplane).planes:
        for line in plane.lines if plane.name.startswith("/host:") else []:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            morsels = [(s, e) for n, s, e in evs if n == "dacp.morsel"]
            for n, s, e in evs:
                if n == "dacp.agg.map" and any(ms <= s and e <= me for ms, me in morsels):
                    nested += 1
    assert nested >= PARTS


def test_agg_counters_move_on_a_grouped_cook_only(tmp_path):
    """``agg_morsels`` and ``agg_host_s`` count a grouped COOK's morsels and
    their key mapping and merge time; a COOK without keys leaves them."""
    from repro.core.backend import get_backend

    path = _dataset(tmp_path)
    bk = get_backend("numpy")
    cfg = ExecutorConfig(num_workers=2, morsel_rows=1024, backend="numpy")
    stats = ExecutorStats()
    before = (bk.agg_morsels, bk.agg_host_s)
    execute_parallel(_agg_dag(), lambda n: scan_path(path, columns=n.params["columns"]), cfg, stats=stats).collect()
    assert bk.agg_morsels - before[0] == stats.progress()["morsels_done"] > 0
    assert bk.agg_host_s > before[1]
    assert stats.progress()["groups"] == 5

    bld = Dag.build()
    s = bld.add("source", {"uri": "dacp://h:1/tbl", "columns": ["x"]})
    dag = bld.finish(bld.add("aggregate", {"keys": [], "aggs": {"sx": {"fn": "sum", "column": "x"}}}, [s]))
    before = (bk.agg_morsels, bk.agg_host_s)
    out = execute_parallel(dag, lambda n: scan_path(path, columns=n.params["columns"]), cfg).collect()
    assert out.num_rows == 1
    assert (bk.agg_morsels, bk.agg_host_s) == before
