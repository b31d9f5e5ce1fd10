"""The chip benchmark's harness: one cell, one process.

A run reads its cell from ``BENCHMARK.json`` (configuration and traffic by
name), makes the configuration's tables from the seed into a temporary
directory outside the checkout, starts a faird server on localhost TCP
with the default ``ExecutorConfig`` and a zero-budget plan cache (repeated
COOKs execute, not replay), warms every morsel shape the window will use,
drives the window through ``DacpClient.cook`` over ``TcpNetwork``, stops the
server, compares a seeded sample of the served answers with the plain
reference, and prints one JSON line last.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by name:

- ``configs/<config>.json`` and ``data/<generator>.py`` (``make(config, seed)``);
- ``traffic/<traffic>.json`` (read by ``cb_traffic``);
- ``e2e_metrics/<metric>.py`` and ``layer_metrics/<metric>.py``
  (``read(ctx)``, which returns None when it finds nothing to read).  A
  metric split by the end-to-end metric it moves, such as
  ``device.idle_pct.scan`` and ``device.idle_pct.stats``, is read by
  ``<metric without its last suffix>.py`` when it has no file of its own.
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import queue
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout

import numpy as np

import cb_reference
import cb_trace
import cb_traffic
import cb_work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SRC = os.path.join(ROOT, "src")
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")
DRAIN_S = 60.0  # how long past the window's close an answer may still come
# A traced run measures this much of the same traffic: the profiler's trace
# of the TPU runtime's host threads grows by millions of events a window
# second, and stopping and reading it must end inside the run's time limit.
TRACE_SECONDS = 10.0


class Refused(Exception):
    """The run cannot measure this cell here (no chip, unknown chip, ...)."""


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path) and "." in name:
        name = name.rsplit(".", 1)[0]
        path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"cb_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, bench: dict | None = None) -> dict:
    """The cell's entry, configuration, traffic and metric entries."""
    if bench is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf_entry["file"])) as f:
        config = json.load(f)

    def applies(m: dict) -> bool:
        return workload in m["workloads"] if "workloads" in m else True

    return {
        "workload": w,
        "run_seconds": bench["run_seconds"],
        "config": config,
        "traffic": load_json("traffic", f"{w['traffic']}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------
def configure_compile_cache(jax) -> None:
    """Keep jax's persistent compilation cache at the checkout's fixed
    ``.jax_cache`` (the program keeps a directory that is already set)."""
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def check_device(jax, chips: int, peaks: dict) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise Refused(f"needs a TPU; jax's first device is {dev.platform} ({dev.device_kind})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; jax has {len(devs)}")
    if dev.device_kind not in peaks["devices"]:
        raise Refused(f"device kind {dev.device_kind!r} is not in peaks.json")
    return describe_device(jax, chips)


def describe_device(jax, chips: int) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": min(chips, len(devs))}


def memory_peak(jax, chips: int) -> int:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileCounter:
    """XLA backend compilations, counted from jax's monitoring events."""

    def __init__(self, jax):
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, _secs, **_kw):
            if event == BACKEND_COMPILE_EVENT:
                self.compiles += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# data and server
# ---------------------------------------------------------------------------
def _column(values: np.ndarray):
    """(dtype, column) of one part's values; NUL-padded fixed-width bytes
    become a variable-width string column."""
    from repro.core import dtypes
    from repro.core.batch import Column

    if values.dtype.kind == "S":
        u8 = values.view(np.uint8).reshape(values.size, values.dtype.itemsize)
        keep = u8 != 0
        offsets = np.zeros(values.size + 1, np.int64)
        np.cumsum(keep.sum(axis=1), out=offsets[1:])
        return dtypes.STRING, Column(dtypes.STRING, offsets=offsets, data=u8[keep])
    dt = dtypes.from_numpy(values.dtype)
    return dt, Column(dt, values=np.ascontiguousarray(values))


def write_tables(ds_root: str, tables: dict) -> None:
    from repro.core.batch import RecordBatch
    from repro.core.schema import Field, Schema
    from repro.core.sdf import StreamingDataFrame
    from repro.server import write_sdf_dataset

    for name, t in tables.items():
        cols, sizes = t["columns"], t["parts"]
        edges = np.concatenate([[0], np.cumsum(sizes)])

        def part(j, cols=cols, edges=edges):
            typed = {c: _column(v[edges[j] : edges[j + 1]]) for c, v in cols.items()}
            return RecordBatch(Schema([Field(c, dt) for c, (dt, _col) in typed.items()]), [col for _dt, col in typed.values()])

        schema = part(0).schema
        write_sdf_dataset(
            os.path.join(ds_root, name), StreamingDataFrame(schema, lambda p=part, n=len(sizes): (p(j) for j in range(n)))
        )


class Server:
    """A faird server on localhost TCP over the run's catalog, with the
    ExecutorStats of every COOK it runs kept for the window's totals
    (``engine.executor_stats()`` keeps only the most recent COOK's, and the
    window's COOKs overlap)."""

    def __init__(self, data_root: str, executor_overrides: dict | None = None):
        import socket

        from repro.client import TcpNetwork
        from repro.core.executor import ExecutorConfig, ExecutorStats
        from repro.server import FairdServer
        from repro.server.plancache import PlanCache

        t = socket.socket()
        t.bind(("127.0.0.1", 0))
        port = t.getsockname()[1]
        t.close()
        self.authority = f"127.0.0.1:{port}"  # the real endpoint: flow URIs embed it
        self.srv = FairdServer(self.authority, executor=ExecutorConfig(**(executor_overrides or {})))
        self.srv.flows.plan_cache = PlanCache(budget_bytes=0)  # repeats execute, not replay
        self.srv.catalog.register_path("ds", os.path.join(data_root, "ds"))
        self.srv.serve_tcp(port=port)
        self.srv.network = TcpNetwork()
        self._networks = []
        self._lock = threading.Lock()
        self.stats = []
        engine = self.srv.engine
        run = engine.execute_dag

        def execute_dag(dag, stats=None, cancel=None):
            if stats is None:
                stats = ExecutorStats()
            with self._lock:
                self.stats.append(stats)
            return run(dag, stats=stats, cancel=cancel)

        engine.execute_dag = execute_dag

    def client(self):
        """A client with its own session (one user, one connection)."""
        from repro.client import TcpNetwork

        net = TcpNetwork()
        with self._lock:
            self._networks.append(net)
        return net.client_for(self.authority)

    def take_stats(self) -> list:
        with self._lock:
            out, self.stats = self.stats, []
        return out

    def close(self) -> None:
        self.srv.shutdown()
        for net in self._networks:
            net.close_all()
        self.srv.network.close_all()


def executor_totals(stats_list: list) -> dict:
    seen, tot = set(), {"morsels": 0, "rows": 0, "fused_launches": 0, "transfers_overlapped": 0}
    for st in stats_list:
        if id(st) in seen:
            continue
        seen.add(id(st))
        p = st.progress()
        tot["morsels"] += p["morsels_done"]
        tot["rows"] += p["rows_processed"]
        tot["fused_launches"] += p["fused_launches"]
        tot["transfers_overlapped"] += p["transfers_overlapped"]
    return tot


def backend_counters() -> dict:
    from repro.core.backend import get_backend

    bk = get_backend("pallas")
    return {"kernel_calls": bk.kernel_calls, "envelope_rejects": bk.envelope_rejects, "f64_folds": bk.f64_folds}


# ---------------------------------------------------------------------------
# warm-up: every morsel shape the window will use
# ---------------------------------------------------------------------------
def morsel_sizes(tables: dict, requests: list, batch_rows: int, morsel_rows) -> dict:
    """Per source table, the morsel row counts the requests will produce:
    the scan cuts each part into ``batch_rows`` batches, the filter keeps
    some rows of each, and the executor cuts a batch above ``morsel_rows``."""
    sizes: dict = {}
    done = set()
    for r in requests:
        for t in r["sources"]:
            key = (t, json.dumps(r["filter"]))
            if key in done:
                continue
            done.add(key)
            (mask,) = cb_reference.filter_mask(tables, {"sources": [t], "filter": r["filter"]})
            parts = tables[t]["parts"]
            rows = int(np.sum(parts))
            starts = []
            edge = 0
            for n in parts:
                starts.extend(range(edge, edge + n, batch_rows))
                edge += n
            kept = np.diff(np.concatenate([starts, [rows]])) if mask is None else np.add.reduceat(mask.astype(np.int64), starts)
            out = sizes.setdefault(t, set())
            for n in kept.tolist():
                if isinstance(morsel_rows, int) and n > morsel_rows:
                    out.update([morsel_rows] if n % morsel_rows == 0 else [morsel_rows, n % morsel_rows])
                elif n > 0:
                    out.add(n)
    return sizes


def warm_tables(tables: dict, requests: list, sizes: dict, tile: int) -> dict:
    """One table per set of padded morsel shapes (source tables of one
    schema and the same shapes share it), with one part per shape, made of
    rows that every request's filter keeps."""
    out, seen = {}, set()
    for t, want in sorted(sizes.items()):
        pads = {}
        for n in sorted(want):
            pads.setdefault(-(-n // tile) * tile, n)
        cols = tables[t]["columns"]
        shape = (tuple(sorted(pads)), tuple((c, v.dtype.str) for c, v in cols.items()))
        if shape in seen:
            continue
        seen.add(shape)
        keep = None
        for flt in {json.dumps(r["filter"]) for r in requests if t in r["sources"]}:
            (m,) = cb_reference.filter_mask(tables, {"sources": [t], "filter": json.loads(flt)})
            if m is not None:
                keep = m if keep is None else keep & m
        idx = np.arange(next(iter(cols.values())).size) if keep is None else np.flatnonzero(keep)
        parts = sorted(pads.values())
        take = np.resize(idx, int(sum(parts)))
        out[f"warm_{t}"] = {"columns": {c: v[take] for c, v in cols.items()}, "parts": parts}
    return out


def warm_requests(requests: list, warm: dict) -> list:
    """One request over each warm table (every morsel shape of one source),
    and one per distinct number of sources above one (the merge of the
    union's partial aggregates)."""
    by_count = {}
    for r in requests:
        if len(r["sources"]) > 1:
            by_count.setdefault(len(r["sources"]), r)
    out = [by_count[k] for k in sorted(by_count)]
    base = requests[0]
    for name in warm:
        out.append({**base, "id": -1, "sources": [name]})
    return out


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
class Driver:
    """Sends requests and times them; one ``record`` per request."""

    def __init__(self, query: dict, authority: str, annotate: bool):
        import cb_query

        self.query = query
        self.authority = authority
        self.build = cb_query.build_dag
        self.annotate = annotate

    def answer(self, client, request: dict) -> dict:
        """COOK, collect, and order the rows by the query's ``order_by``."""
        dag = self.build(self.query, request, self.authority)
        batch = client.cook(dag).collect()
        cols = {name: np.asarray(batch.column(name).values) for name in batch.schema.names}
        order_by = self.query.get("order_by") or []
        if order_by and batch.num_rows > 1:
            order = np.lexsort([cols[k] for k in reversed(order_by)])
            cols = {k: v[order] for k, v in cols.items()}
        return cols

    def run(self, client, rec: dict) -> dict:
        import jax

        rec["sent"] = time.perf_counter()
        try:
            if self.annotate:
                with jax.profiler.TraceAnnotation(cb_trace.REQUEST_SPAN):
                    rec["answer"] = self.answer(client, rec["request"])
            else:
                rec["answer"] = self.answer(client, rec["request"])
        except Exception as e:  # noqa: BLE001 - a failed request is counted, and fails the run's check
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["end"] = time.perf_counter()
        return rec


def closed_loop(driver: Driver, traffic: dict, requests: list, seconds: float, clients: list) -> tuple:
    lists = cb_traffic.streams(traffic, requests)
    records: list = []
    lock = threading.Lock()
    t0 = time.perf_counter()
    stop = t0 + seconds

    def stream(lst, client):
        for r in lst:
            now = time.perf_counter()
            if now >= stop:
                return
            rec = driver.run(client, {"request": r, "due": now})
            with lock:
                records.append(rec)
        with lock:
            records.append({"exhausted": True})

    threads = [threading.Thread(target=stream, args=(lst, c), daemon=True) for lst, c in zip(lists, clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(seconds + DRAIN_S * 5)
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a closed-loop stream did not finish")
    if any(r.get("exhausted") for r in records):
        raise RuntimeError("a stream ran out of requests inside the window: raise requests_per_stream")
    return t0, records


def open_loop(driver: Driver, traffic: dict, requests: list, offsets, seconds: float, clients: list) -> tuple:
    pool_clients: queue.Queue = queue.Queue()
    for c in clients:
        pool_clients.put(c)

    def task(rec):
        c = pool_clients.get()
        try:
            return driver.run(c, rec)
        finally:
            pool_clients.put(c)

    records, futures = [], []
    pool = ThreadPoolExecutor(max_workers=len(clients))
    try:
        t0 = time.perf_counter()
        for r, off in zip(requests, offsets):
            if off >= seconds:
                break
            due = t0 + float(off)
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            rec = {"request": r, "due": due, "queued": time.perf_counter()}
            records.append(rec)
            futures.append(pool.submit(task, rec))
        deadline = t0 + seconds + DRAIN_S
        for f in futures:
            try:
                f.result(timeout=max(0.0, deadline - time.perf_counter()))
            except FuturesTimeout:
                break  # the rest never came: counted as failed
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
    return t0, records


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------
def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t0: float,
    require_tpu: bool = True,
    executor_overrides: dict | None = None,
    config_overrides: dict | None = None,
    traffic_overrides: dict | None = None,
    log=None,
) -> dict:
    """Run one cell; returns the result line's object (``checks`` last).
    Progress lines (``{"info": ...}``) go to standard error, so that standard
    output holds nothing but the result line."""
    if log is None:
        log = functools.partial(print, file=sys.stderr)
    seed = int(seed) % (1 << 63)
    window_s = min(seconds, TRACE_SECONDS) if trace else seconds
    cell = load_cell(workload)
    config = {**cell["config"], **(config_overrides or {})}
    traffic = {**cell["traffic"], **(traffic_overrides or {})}
    query = traffic["query"]
    chips = int(cell["workload"]["chips"])
    peaks = load_json("peaks.json")

    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import jax

    configure_compile_cache(jax)
    device = check_device(jax, chips, peaks) if require_tpu else describe_device(jax, chips)
    jax_ready_s = time.perf_counter() - t0  # interpreter, imports and the runtime's start
    log(json.dumps({"info": {"device": device, "workload": workload, "seed": seed, "seconds": window_s}}), flush=True)
    counter = CompileCounter(jax)

    from repro.core.backend import PallasBackend, get_backend
    from repro.core.executor import ExecutorConfig
    from repro.server.adapters.base import DEFAULT_BATCH_ROWS

    tmp = tempfile.mkdtemp(prefix="dacp_chipbench_")
    server = None
    info: dict = {"jax_ready_s": jax_ready_s}
    try:
        tg = time.perf_counter()
        gen = load_module("data", config["generator"])
        tables = gen.make(config, seed)
        n_req = cb_traffic.request_count(traffic, window_s)
        requests = cb_traffic.build_requests(traffic, config, seed, n_req)
        sizes = morsel_sizes(tables, requests, DEFAULT_BATCH_ROWS, ExecutorConfig().morsel_rows)
        warm = warm_tables(tables, requests, sizes, PallasBackend.tile)
        info["data_make_s"] = time.perf_counter() - tg
        tw = time.perf_counter()
        write_tables(os.path.join(tmp, "ds"), {**tables, **warm})
        info["data_write_s"] = time.perf_counter() - tw
        info["warm_shapes"] = {t: w["parts"] for t, w in warm.items()}

        ts = time.perf_counter()
        server = Server(tmp, executor_overrides)
        backend = get_backend(server.srv.executor.backend).name
        if require_tpu and backend != "pallas":
            raise Refused(f"backend {server.srv.executor.backend!r} resolved to {backend}, not pallas")
        info["server_start_s"] = time.perf_counter() - ts

        loop = traffic["loop"]
        n_clients = int(traffic["streams"]) if loop == "closed" else int(traffic["workers"])
        clients = [server.client() for _ in range(n_clients)]
        driver = Driver(query, server.authority, annotate=False)
        tw = time.perf_counter()
        compiles_before = counter.compiles
        for r in warm_requests(requests, warm):
            rec = driver.run(clients[0], {"request": r, "due": time.perf_counter()})
            if "error" in rec:
                raise RuntimeError(f"warm-up request failed: {rec['error']}")
        info["warmup_s"] = time.perf_counter() - tw
        # a program read from the persistent cache still counts as a backend compile
        info["warmup_compiles"] = counter.compiles - compiles_before - counter.cache_hits
        info["warmup_cache_hits"] = counter.cache_hits
        server.take_stats()

        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="dacp_chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the benchmark's own spans, not the runtime's
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            driver.annotate = True
        bk_before = backend_counters() if backend == "pallas" else {}
        sent_before = [(c.bytes_sent, c.bytes_received) for c in clients]
        compiles_before = counter.compiles
        setup_s = time.perf_counter() - t0
        win = jax.profiler.TraceAnnotation(cb_trace.WINDOW_SPAN) if trace else None
        if win is not None:
            win.__enter__()
        try:
            if loop == "closed":
                w0, records = closed_loop(driver, traffic, requests, window_s, clients)
            else:
                offsets = cb_traffic.arrival_offsets(len(requests), int(traffic["schedule_seed"]), window_s)
                w0, records = open_loop(driver, traffic, requests, offsets, window_s, clients)
        finally:
            if win is not None:
                win.__exit__(None, None, None)
        w1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
            info["trace_stop_s"] = time.perf_counter() - w1
        info["compiles_in_window"] = counter.compiles - compiles_before
        stats = executor_totals(server.take_stats())
        info["executor"] = stats
        if bk_before:
            info["backend"] = {k: v - bk_before[k] for k, v in backend_counters().items()}
        info["client_bytes"] = {
            "sent": sum(c.bytes_sent - s for c, (s, _r) in zip(clients, sent_before)),
            "received": sum(c.bytes_received - r for c, (_s, r) in zip(clients, sent_before)),
        }
        device["memory_peak_bytes"] = memory_peak(jax, chips)
    finally:
        if server is not None:
            server.close()
        shutil.rmtree(tmp, ignore_errors=True)

    done = [r for r in records if "answer" in r]
    failed = [r for r in records if "answer" not in r]
    info["requests"] = {"attempted": len(records), "completed": len(done), "failed": len(failed)}
    if failed:
        info["first_error"] = failed[0].get("error", "no answer")
    if loop == "open":
        late = np.asarray([r["queued"] - r["due"] for r in records]) * 1e3
        wait = np.asarray([r["sent"] - r["queued"] for r in records if "sent" in r]) * 1e3
        if late.size:
            info["generator_late_ms"] = {"p50": float(np.percentile(late, 50)), "max": float(late.max())}
        if wait.size:
            info["client_wait_ms"] = {"p50": float(np.percentile(wait, 50)), "max": float(wait.max())}
        half = len(records) // 2
        if half and not failed:  # a backlog that grows shows as a later half that waits longer
            lat = np.asarray([r["end"] - r["due"] for r in records]) * 1e3
            info["latency_p50_ms_by_half"] = [float(np.median(lat[:half])), float(np.median(lat[half:]))]
            info["latency_ms"] = {f"p{q}": float(np.percentile(lat, q)) for q in (50, 80, 90, 95, 99, 100)}
    itemsize = {}
    for t in tables.values():
        for c, v in t["columns"].items():
            itemsize[c] = v.dtype.itemsize
    table_rows = {t: int(np.sum(v["parts"])) for t, v in tables.items()}
    ctx = {
        "records": records,
        "window_start": w0,
        "seconds": window_s,
        "setup_s": setup_s,
        "drain_s": DRAIN_S,
        "table_rows": table_rows,
        "executor": stats,
        "peaks": peaks["devices"].get(device["kind"]),
    }

    result_metrics = {}
    result = {"correct": False, "attempted": len(records), "failed": len(failed), "metrics": result_metrics, "device": device}
    if trace:
        tl = time.perf_counter()
        tr = cb_trace.load_xplane(cb_trace.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        info["trace_read_s"] = time.perf_counter() - tl
        lo, hi = cb_trace.window(tr)
        ctx.update(trace=tr, lo=lo, hi=hi)
        ctx["work"] = window_work(tables, query, done, itemsize)
        device["busy_s"] = cb_trace.busy_ns(tr, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {"device_ops": cb_trace.top_ops(tr, lo, hi), "idle_gaps": cb_trace.idle_gaps(tr, lo, hi)}
        info["work"] = ctx["work"]
    for m in cell["per_layer"] if trace else cell["end_to_end"]:
        value = load_module("layer_metrics" if trace else "e2e_metrics", m["name"]).read(ctx)
        if value is not None:
            result_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    info.update(ctx.get("notes", {}))
    log(json.dumps({"info": info}), flush=True)

    tc = time.perf_counter()
    checks, compared = check_answers(tables, query, traffic["check"], done, failed, seed)
    log(json.dumps({"info": {"answers_compared": compared, "reference_s": time.perf_counter() - tc}}), flush=True)
    result["correct"] = verdict(checks, compared)
    result["checks"] = checks
    return result


def window_work(tables: dict, query: dict, done: list, itemsize: dict) -> dict:
    """Bytes and operations of the window's completed requests (cb_work)."""
    rows_memo: dict = {}
    tot_bytes = tot_ops = 0
    for r in done:
        req = r["request"]
        key = (tuple(req["sources"]), json.dumps(req["filter"]))
        if key not in rows_memo:
            rows_memo[key] = cb_reference.rows_passing(tables, req)
        groups = len(next(iter(r["answer"].values())))
        b, o = cb_work.request_work(query, itemsize, rows_memo[key], groups)
        tot_bytes += b
        tot_ops += o
    return {"bytes": tot_bytes, "ops": tot_ops, "requests": len(done)}


def check_answers(tables: dict, query: dict, check: dict, done: list, failed: list, seed: int, answer_of=None) -> tuple:
    """Compare a seeded sample of the answers (with the request that reads
    the most rows in it) with the plain reference: ``({number: {"value",
    "limit"}}, answers compared)``.  ``answer_of(record)`` gives a record's
    answer in the reference's form: by default the served one; the control
    puts another computation in the program's place."""
    if answer_of is None:

        def answer_of(r):
            return cb_reference.served_answer(r["answer"], query)

    limits = check["limits"]
    sample = []
    if done:
        rng = np.random.default_rng([seed, 0xC4])
        k = min(int(check["sample"]), len(done))
        picked = set(rng.choice(len(done), size=k, replace=False).tolist())
        widest = max(range(len(done)), key=lambda i: (len(done[i]["request"]["sources"]), -i))
        picked.add(widest)
        sample = [done[i] for i in sorted(picked)]
    memo: dict = {}
    readings = []
    for r in sample:
        req = r["request"]
        key = (tuple(req["sources"]), json.dumps(req["filter"]))
        if key not in memo:
            memo[key] = cb_reference.answer(tables, req, query)
        readings.append(cb_reference.compare(answer_of(r), memo[key], query))
    values = {"failed": len(failed), **cb_reference.merge_readings(readings)}
    return {name: {"value": v, "limit": limits[name]} for name, v in values.items() if name in limits}, len(sample)


def verdict(checks: dict, compared: int) -> bool:
    """``correct``: some answers were compared, and every number is within its limit."""
    return compared > 0 and all(c["value"] <= c["limit"] for c in checks.values())


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="DACP chip benchmark: one cell of BENCHMARK.json")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None, help="open loop: override the traffic file's rate (rate sweeps)")
    args = ap.parse_args(argv)
    overrides = {"rate_per_s": args.rate} if args.rate is not None else None
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t0, traffic_overrides=overrides)
    except Refused as e:
        print(f"chipbench: refused: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
