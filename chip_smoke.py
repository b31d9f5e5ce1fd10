"""Smoke run of DACP's COOK path on a TPU, through the entry points a user
calls: faird servers on localhost TCP and a ``TcpNetwork`` client, all in
this one process (server and client threads; no child processes, so the
chip has one owner).

    python chip_smoke.py [--seed S] [--rows R]      # one chip
    python chip_smoke.py --chips 4 [--seed S] [--rows R]

One chip: a seeded table of ``--rows`` rows (default 2**26: int64 ``k`` in
[0, 100), float32 ``x`` and ``w``, int32 ``i``; 16 parts, ~1.3 GB) and a
small adversarial table (``-0.0``, NaN payloads, ±Inf, float32 subnormals,
full-range int64 and int32) are written to a temporary directory.  A
server with the default ``ExecutorConfig`` (backend ``auto``, which must
resolve to ``pallas``) answers PING, DESCRIBE, a streaming
filter→project→select COOK (``FusedChainPlan.run``), an aggregate COOK
(``FusedChainPlan.fold``), a group-by with int64 min/max (the per-op
segment-reduce kernels), the same over the adversarial table, and a
cross-domain union of two servers followed by a group-by (partial
aggregates exchanged between domains).  Every answer must be
byte-identical to the same request on ``backend="numpy"`` servers over the
same files.

``--chips 4``: only the multi-device path — the aggregate COOK over a
union of four sources with ``devices=(0, 1, 2, 3)``, against the same COOK
on one chip: launches must land on every device, and the bytes must match.

Printed times are smoke timings of one warm repeat, not benchmark numbers.
The last line of a passing run is ``{"ok": true, "device": {...}}``; the
run exits nonzero without it when jax finds no TPU or any phase fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

PARTS = 16
I64_MIN, I64_MAX = -(2**63), 2**63 - 1
I32_MIN, I32_MAX = -(2**31), 2**31 - 1


# ---------------------------------------------------------------------------
# data, made from the seed
# ---------------------------------------------------------------------------
def main_part(seed: int, part: int, rows: int):
    from repro.core.batch import RecordBatch

    rng = np.random.default_rng([seed, part])
    return RecordBatch.from_pydict(
        {
            "k": rng.integers(0, 100, rows),
            "x": rng.standard_normal(rows, dtype=np.float32),
            "w": rng.standard_normal(rows, dtype=np.float32),
            "i": rng.integers(0, 200, rows, dtype=np.int32),
        }
    )


_F32_SPECIAL_BITS = [
    0x80000000,  # -0.0
    0x00000000,
    0x7F800000,  # +inf
    0xFF800000,  # -inf
    0x7FC00000,  # quiet NaN
    0x7FC12345,  # NaN payloads
    0xFFC00000,
    0xFFEDCBA9,
    0x00000001,  # smallest subnormal
    0x807FFFFF,  # -largest subnormal
    0x00400000,
    0x00800000,  # smallest normal
    0x7F7FFFFF,  # largest finite
    0xFF7FFFFF,
]


def _f32_adversarial(rng, n: int, normal_scale: float = 1.0):
    """A third specials, a third random subnormals, a third normals."""
    specials = np.asarray(_F32_SPECIAL_BITS, np.uint32).view(np.float32)
    sub_bits = rng.integers(1, 0x800000, n, dtype=np.uint32) | (rng.integers(0, 2, n, dtype=np.uint32) << 31)
    pick = rng.integers(0, 3, n)
    out = (rng.standard_normal(n, dtype=np.float32) * np.float32(normal_scale)).astype(np.float32)
    out[pick == 0] = rng.choice(specials, int((pick == 0).sum()))
    out[pick == 1] = sub_bits[pick == 1].view(np.float32)
    return out


def adversarial_part(seed: int, part: int, rows: int):
    from repro.core.batch import RecordBatch

    rng = np.random.default_rng([seed, 1000 + part])
    k_vals = np.concatenate(
        [[I64_MIN, I64_MAX, -1, 0, 1], rng.integers(I64_MIN, I64_MAX, 59, dtype=np.int64, endpoint=True)]
    )
    i_vals = np.concatenate(
        [[I32_MIN, I32_MAX, -1, 0, 1], rng.integers(I32_MIN, I32_MAX, 59, dtype=np.int32, endpoint=True)]
    ).astype(np.int32)
    w = _f32_adversarial(rng, rows)
    passing = rng.integers(0, 2, rows).astype(bool)  # about half pass ``w > 2.0``
    w[passing] = rng.uniform(2.5, 10.0, int(passing.sum())).astype(np.float32)
    return RecordBatch.from_pydict(
        {
            "k": rng.choice(k_vals, rows),
            "x": _f32_adversarial(rng, rows),
            "w": w,
            "i": rng.choice(i_vals, rows),
        }
    )


def write_table(path: str, make, seed: int, parts: list, rows: int) -> None:
    from repro.core.sdf import StreamingDataFrame
    from repro.server import write_sdf_dataset

    schema = make(seed, parts[0], 1).schema
    sizes = [rows // len(parts) + (1 if j < rows % len(parts) else 0) for j in range(len(parts))]
    sdf = StreamingDataFrame(schema, lambda: (make(seed, p, n) for p, n in zip(parts, sizes)))
    write_sdf_dataset(path, sdf)


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------
def stream_dag(uri: str):
    """filter → project → select; the filter stays in the chain (a keep=False
    project renames its column), so the fused launch evaluates it."""
    from repro.core import col
    from repro.core.dag import Dag

    bld = Dag.build()
    s = bld.source(uri)
    p = bld.add(
        "project",
        {
            "exprs": {
                "k": col("k"),
                "x": col("x"),
                "w": col("w"),
                "y": col("x") * 2.0 + col("w"),
                "z": (col("x") - col("w")) * 0.75,
                "j": col("i") * 3 - 7,
            },
            "keep": False,
        },
        [s],
    )
    f = bld.add("filter", {"predicate": col("w") > 2.0}, [p])
    return bld.finish(bld.add("select", {"columns": ["k", "x", "y", "z", "j", "w"]}, [f]))


def aggregate_dag(*uris: str):
    """The executor benchmark's COOK: filter → project → group-by(k)."""
    from repro.core import col
    from repro.core.dag import Dag

    bld = Dag.build()
    srcs = [bld.source(u) for u in uris]
    node = srcs[0]
    for other in srcs[1:]:
        node = bld.add("union", {}, [node, other])
    f = bld.add("filter", {"predicate": col("x") > 0.0}, [node])
    p = bld.add("project", {"exprs": {"y": col("x") * 2.0 + 1.0}, "keep": True}, [f])
    a = bld.add(
        "aggregate",
        {
            "keys": ["k"],
            "aggs": {
                "n": {"fn": "count"},
                "sy": {"fn": "sum", "column": "y"},
                "mx": {"fn": "mean", "column": "x"},
            },
        },
        [p],
    )
    return bld.finish(a)


def minmax_dag(uri: str):
    """int64 min/max: outside the fused envelope, so the per-op
    segment-reduce kernels fold it."""
    from repro.core.dag import Dag

    bld = Dag.build()
    s = bld.source(uri)
    aggs = {"n": {"fn": "count"}, "lo": {"fn": "min", "column": "k"}, "hi": {"fn": "max", "column": "k"}}
    return bld.finish(bld.add("aggregate", {"keys": ["i"], "aggs": aggs}, [s]))


def union_frame(client, uri_a: str, uri_b: str):
    """Cross-domain union followed by a group-by (R9 partial aggregates)."""
    return (
        client.open(uri_a)
        .union(client.open(uri_b))
        .group_by("k")
        .agg(n="count", sx=("sum", "x"), lo=("min", "i"), hi=("max", "i"), mw=("max", "w"))
    )


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------
def _col_bytes(batch):
    out = {}
    for f, c in zip(batch.schema, batch.columns):
        if f.dtype.is_varwidth:
            out[f.name] = (c.offsets.tobytes(), c.data.tobytes())
        else:
            out[f.name] = c.values.tobytes()
    return out


def differences(got, want) -> list:
    """Where two result batches are not byte-identical (empty: identical)."""
    if got.schema.to_json() != want.schema.to_json():
        return [f"schema {got.schema.to_json()} != {want.schema.to_json()}"]
    if got.num_rows != want.num_rows:
        return [f"{got.num_rows} rows != {want.num_rows} rows"]
    out = []
    gb, wb = _col_bytes(got), _col_bytes(want)
    for f, gc, wc in zip(got.schema, got.columns, want.columns):
        if gb[f.name] == wb[f.name]:
            continue
        g = np.ascontiguousarray(gc.values).view(np.uint8).reshape(got.num_rows, -1)
        w = np.ascontiguousarray(wc.values).view(np.uint8).reshape(want.num_rows, -1)
        bad = np.flatnonzero((g != w).any(axis=1))
        sample = [(int(r), g[r].tobytes().hex(), w[r].tobytes().hex()) for r in bad[:4]]
        out.append(f"column {f.name}: {bad.size} rows differ, (row, got, want) {sample}")
    return out


class Smoke:
    """Runs named phases, records failures, prints one line per phase."""

    def __init__(self):
        self.failures = []

    def phase(self, name: str, fn):
        try:
            note = fn()
        except Exception as e:  # noqa: BLE001 - recorded; the run then exits nonzero
            traceback.print_exc()
            self.failures.append(f"{name}: {type(e).__name__}: {e}")
            print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
            return
        print(f"ok   {name}" + (f": {note}" if note else ""), flush=True)


def compare(name: str, run, ref, ref_name: str = "numpy") -> str:
    """Cold run, warm repeat (timed), reference; byte comparison."""
    run()
    t0 = time.perf_counter()
    got = run()
    warm = time.perf_counter() - t0
    want = ref()
    diff = differences(got, want)
    if diff:
        raise AssertionError(f"{name} differs from {ref_name}: " + "; ".join(diff))
    return f"{got.num_rows} rows byte-identical to {ref_name}, warm {warm:.3f} s (smoke timing, not a benchmark)"


def count_compiles():
    """A counter of XLA backend compilations (jax monitoring events)."""
    import jax

    count = [0]

    def listener(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return count


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def start_servers(root: str, configs: dict) -> tuple:
    """{name: ExecutorConfig} → ({name: (server, authority)}, network)."""
    import socket

    from repro.client import TcpNetwork
    from repro.server import FairdServer
    from repro.server.plancache import PlanCache

    net = TcpNetwork()
    servers = {}
    for name, cfg in configs.items():
        t = socket.socket()
        t.bind(("127.0.0.1", 0))
        port = t.getsockname()[1]
        t.close()
        auth = f"127.0.0.1:{port}"  # the real endpoint: flow URIs embed it
        srv = FairdServer(auth, executor=cfg)
        srv.flows.plan_cache = PlanCache(budget_bytes=0)  # repeats execute, not replay
        srv.catalog.register_path("ds", os.path.join(root, "ds"))
        srv.serve_tcp(port=port)
        srv.network = TcpNetwork()  # cross-domain exchange pulls
        servers[name] = (srv, auth)
    return servers, net


def one_chip(args, root: str, smoke: Smoke) -> None:
    from repro.core.executor import ExecutorConfig

    t0 = time.perf_counter()
    write_table(os.path.join(root, "ds", "main"), main_part, args.seed, list(range(PARTS)), args.rows)
    write_table(os.path.join(root, "ds", "adv"), adversarial_part, args.seed, [0, 1], 8192)
    print(f"rows {args.rows} (+8192 adversarial), written in {time.perf_counter() - t0:.1f} s", flush=True)

    servers, net = start_servers(
        root,
        {
            "a": ExecutorConfig(),
            "b": ExecutorConfig(),
            "a_ref": ExecutorConfig(backend="numpy"),
            "b_ref": ExecutorConfig(backend="numpy"),
        },
    )
    try:
        _one_chip_requests(smoke, servers, net)
    finally:
        for srv, _auth in servers.values():
            srv.shutdown()
        net.close_all()
        for srv, _auth in servers.values():
            srv.network.close_all()


def _one_chip_requests(smoke: Smoke, servers: dict, net) -> None:
    from repro.core.backend import get_backend

    (a, auth_a), (b, auth_b) = servers["a"], servers["b"]
    _a_ref, auth_a_ref = servers["a_ref"]
    _b_ref, auth_b_ref = servers["b_ref"]
    client, ref_client = net.client_for(auth_a), net.client_for(auth_a_ref)
    pallas = get_backend("pallas")

    def backend_is_pallas():
        name = get_backend(a.executor.backend).name
        assert name == "pallas", f"backend auto resolved to {name}"
        return f"auto -> {name}"

    smoke.phase("backend", backend_is_pallas)
    smoke.phase("PING", lambda: str(client.ping().get("authority", "pong")))
    smoke.phase("DESCRIBE", lambda: f"{len(client.describe(f'dacp://{auth_a}/ds/main')['schema'])} fields")

    def cook(c, dag):
        return lambda: c.cook(dag).collect()

    def fused(name, dag_fn, table):
        def run():
            before = pallas.envelope_rejects
            note = compare(
                name,
                cook(client, dag_fn(f"dacp://{auth_a}/ds/{table}")),
                cook(ref_client, dag_fn(f"dacp://{auth_a_ref}/ds/{table}")),
            )
            launches = a.engine.executor_stats()["fused_launches"]
            assert launches > 0, f"{name} ran no fused launch"
            rejects = pallas.envelope_rejects - before
            return f"{note}, fused launches {launches}, float-envelope rejects {rejects}"

        smoke.phase(name, run)

    def per_op(name, table):
        def run():
            before = pallas.kernel_calls
            note = compare(
                name,
                cook(client, minmax_dag(f"dacp://{auth_a}/ds/{table}")),
                cook(ref_client, minmax_dag(f"dacp://{auth_a_ref}/ds/{table}")),
            )
            grew = pallas.kernel_calls - before
            assert grew > 0, f"{name} launched no per-op kernel"
            return f"{note}, per-op kernel calls +{grew}"

        smoke.phase(name, run)

    for table in ("main", "adv"):
        fused(f"stream COOK [{table}]", stream_dag, table)
        fused(f"aggregate COOK [{table}]", aggregate_dag, table)
        per_op(f"int64 min/max group-by [{table}]", table)

    def repeat_compiles():
        counter = count_compiles()
        dag = aggregate_dag(f"dacp://{auth_a}/ds/main")
        client.cook(dag).collect()
        before = counter[0]
        client.cook(dag).collect()
        return f"{counter[0] - before} compilations in a warm repeat of the aggregate COOK"

    smoke.phase("compile count", repeat_compiles)

    def union():
        got_frame = union_frame(client, f"dacp://{auth_a}/ds/main", f"dacp://{auth_b}/ds/adv")
        ref_frame = union_frame(ref_client, f"dacp://{auth_a_ref}/ds/main", f"dacp://{auth_b_ref}/ds/adv")
        note = compare("cross-domain union", got_frame.collect, ref_frame.collect)
        launches = a.engine.executor_stats()["fused_launches"] + b.engine.executor_stats()["fused_launches"]
        return f"{note}, fused launches {launches}"

    smoke.phase("cross-domain union + group-by", union)


def four_chips(args, root: str, smoke: Smoke) -> None:
    import jax

    from repro.core.executor import ExecutorConfig

    devs = jax.devices()
    assert len(devs) >= 4, f"--chips 4 needs 4 devices, jax has {len(devs)}"
    quarters = [list(range(q * PARTS // 4, (q + 1) * PARTS // 4)) for q in range(4)]
    for q, parts in enumerate(quarters):
        write_table(os.path.join(root, "ds", f"q{q}"), main_part, args.seed, parts, args.rows // 4)
    print(f"rows {args.rows // 4 * 4} in 4 sources", flush=True)
    servers, net = start_servers(
        root, {"four": ExecutorConfig(devices=(0, 1, 2, 3)), "one": ExecutorConfig(devices=(0,))}
    )
    try:
        (srv4, auth4), (srv1, auth1) = servers["four"], servers["one"]

        def run():
            dag4 = aggregate_dag(*[f"dacp://{auth4}/ds/q{q}" for q in range(4)])
            dag1 = aggregate_dag(*[f"dacp://{auth1}/ds/q{q}" for q in range(4)])
            c4, c1 = net.client_for(auth4), net.client_for(auth1)
            note = compare(
                "four-chip aggregate", lambda: c4.cook(dag4).collect(), lambda: c1.cook(dag1).collect(), "one chip"
            )
            per_dev = srv4.engine.executor_stats()["device_launches"]
            want = {d.id for d in devs[:4]}
            assert set(per_dev) == want, f"launches landed on devices {sorted(per_dev)}, want {sorted(want)}"
            one = srv1.engine.executor_stats()["device_launches"]
            return f"{note}; launches per device {per_dev} (one chip: {one})"

        smoke.phase("aggregate COOK over 4 sources on 4 chips", run)
    finally:
        for srv, _auth in servers.values():
            srv.shutdown()
        net.close_all()
        for srv, _auth in servers.values():
            srv.network.close_all()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=2**26)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax's first device is {dev.platform}", file=sys.stderr)
        return 2
    print(f"device {dev.device_kind} x{len(jax.devices())}, seed {args.seed}", flush=True)

    smoke = Smoke()
    with tempfile.TemporaryDirectory(prefix="dacp_chip_smoke_") as root:
        (four_chips if args.chips == 4 else one_chip)(args, root, smoke)
    if smoke.failures:
        print(f"{len(smoke.failures)} phase(s) failed:", file=sys.stderr)
        for f in smoke.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
