"""Read-amplification: predicate + projection pushdown vs full transfer
(paper §I-A, §III-B) — measured in BYTES ON THE WIRE.

    full scan      — GET everything, filter client-side
    pushdown       — GET with (columns, predicate); server filters in-situ
    COOK pushdown  — same, expressed as a DAG (optimizer sinks the filter)

Derived metric: amplification = bytes_full / bytes_pushdown — how many
bytes the legacy path moves per useful byte.
"""

from __future__ import annotations

import json
import os
import tempfile

import numpy as np

from benchmarks.common import emit, timer
from repro.client import TcpNetwork
from repro.core import col
from repro.data import write_reviews_jsonl
from repro.server import FairdServer, scan_path, write_sdf_dataset


def run(rows: int = 100_000, selectivity: float = 0.02, verbose: bool = True) -> dict:
    root = tempfile.mkdtemp(prefix="dacp_pushdown_")
    jsonl = os.path.join(root, "reviews.jsonl")
    write_reviews_jsonl(jsonl, rows)
    write_sdf_dataset(os.path.join(root, "columnar"), scan_path(jsonl))

    srv = FairdServer("bench:0")
    srv.catalog.register_path("ds", root)
    port = srv.serve_tcp()
    net = TcpNetwork()
    uri = f"dacp://127.0.0.1:{port}/ds/columnar"
    cutoff = int(50 * selectivity)
    pred = col("useful") < cutoff  # ~selectivity of rows

    results = {"rows": rows}

    c1 = net.client_for(f"127.0.0.1:{port}")
    with timer() as t:
        full = c1.get(uri).collect()
        kept_client = full.filter(np.asarray(pred.evaluate(full), bool)).select(["review_id"])
    results["full_bytes"] = c1.bytes_received
    results["full_s"] = t.s

    c2 = TcpNetwork().client_for(f"127.0.0.1:{port}")
    with timer() as t:
        kept_server = c2.get(uri, columns=["review_id"], predicate=pred).collect()
    results["pushdown_bytes"] = c2.bytes_received
    results["pushdown_s"] = t.s
    assert kept_server.num_rows == kept_client.num_rows

    c3 = TcpNetwork().client_for(f"127.0.0.1:{port}")
    with timer() as t:
        via_cook = c3.open(uri).filter(pred).select("review_id").collect()
    results["cook_bytes"] = c3.bytes_received
    results["cook_s"] = t.s
    assert via_cook.num_rows == kept_server.num_rows

    srv.shutdown()
    results["selected_rows"] = int(kept_server.num_rows)
    results["amplification"] = results["full_bytes"] / max(results["pushdown_bytes"], 1)
    results["speedup"] = results["full_s"] / results["pushdown_s"]

    if verbose:
        emit("pushdown.full_scan", results["full_s"] * 1e6, f"{results['full_bytes']}B")
        emit("pushdown.pushdown", results["pushdown_s"] * 1e6, f"{results['pushdown_bytes']}B")
        emit("pushdown.cook", results["cook_s"] * 1e6, f"{results['cook_bytes']}B")
        emit("pushdown.amplification", 0.0, f"{results['amplification']:.1f}x")
    return results


if __name__ == "__main__":
    print(json.dumps(run(), indent=1))
