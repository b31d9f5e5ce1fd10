# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark entry point.

    PYTHONPATH=src python -m benchmarks.run [--quick]

One section per paper figure/claim:
    structured    — Fig. 4 (DACP vs FTP, structured rows, up+down)
    unstructured  — Fig. 5 (mixed blob workload, BLOB/Binary/FTP)
    pushdown      — §I-A/§III-B read amplification
    cook_insitu   — §III-D/§VI-C move-operators-not-data
    session_reuse — §III-C phased interaction: v2 multiplexed session vs
                    channel-per-request for N small GETs
    executor      — §III-D morsel-driven parallel executor: 1 vs N workers,
                    numpy vs pallas backend, rows/s on a COOK pipeline
    flows         — flow lifecycle: time-to-first-batch for START+FETCH vs
                    blocking COOK, and START-ack latency
    mesh          — federated catalog mesh: LIST scatter/cache latency +
                    partition-parallel scan vs the single-flow plan
    datasource    — adapter-native pushdown: SQL compilation, parquet
                    row-group pruning, jsonl sidecar block skipping

Results additionally land in benchmarks/results/benchmarks.json.
"""

import json
import os
import sys

from benchmarks.common import pin_blas_threads

pin_blas_threads()  # before any bench module pulls in numpy/jax


def main() -> None:
    quick = "--quick" in sys.argv
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from benchmarks import (
        cook_insitu,
        datasource_bench,
        executor,
        flows_bench,
        mesh_bench,
        pushdown,
        session_reuse,
        structured,
        unstructured,
    )

    out = {}
    print("name,us_per_call,derived")
    out["structured"] = structured.run(rows=20_000 if quick else 200_000)
    out["unstructured"] = unstructured.run(scale=1 / 512 if quick else 1 / 64)
    out["pushdown"] = pushdown.run(rows=10_000 if quick else 100_000)
    out["cook_insitu"] = cook_insitu.run(rows=10_000 if quick else 100_000)
    out["session_reuse"] = session_reuse.run(n_gets=40 if quick else 200)
    out["executor"] = executor.run(rows=100_000 if quick else 400_000)
    out["flows"] = flows_bench.run(rows=50_000 if quick else 200_000)
    out["mesh"] = mesh_bench.run(rows=50_000 if quick else 200_000)
    out["datasource"] = datasource_bench.run(rows=20_000 if quick else 100_000)

    res_dir = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(res_dir, exist_ok=True)
    with open(os.path.join(res_dir, "benchmarks.json"), "w") as f:
        json.dump(out, f, indent=1)

    s = out["structured"]
    u = out["unstructured"]
    p = out["pushdown"]
    c = out["cook_insitu"]
    print("\n# paper-claim check (§V):")
    print(f"#  structured speedup: down {s['speedup_download']:.2f}x up {s['speedup_upload']:.2f}x (paper: 3.10x–5.36x)")
    print(
        f"#  unstructured speedup: blob {u['speedup_blob']:.2f}x binary {u['speedup_binary']:.2f}x loopback; "
        f"{u['speedup_blob_wan']:.2f}x at the paper's 3.45Gb/s WAN (paper: ~1.21x)"
    )
    print(f"#  FTP up/down symmetry: {u['ftp_updown_sym']:.2f} (paper: 0.73–0.87); DACP {s['dacp_updown_sym']:.2f} (~1.0)")
    print(f"#  read amplification avoided: {p['amplification']:.1f}x fewer bytes with pushdown")
    print(f"#  in-situ COOK: {c['byte_reduction']:.0f}x fewer WAN bytes, {c['wan_speedup']:.2f}x at 3.45Gb/s")
    sr = out["session_reuse"]
    print(
        f"#  v2 session reuse: {sr['speedup_session']:.2f}x per GET over channel-per-request; "
        f"{sr['speedup_concurrent']:.2f}x with 8 in-flight"
    )
    ex = out["executor"]
    print(
        f"#  morsel executor: {ex['speedup_4w_vs_seed']:.2f}x rows/s at 4 workers vs the "
        f"single-threaded seed path ({ex['rows_per_s_4w'] / 1e6:.2f} Mrows/s)"
    )
    fb = out["flows"]
    print(
        f"#  flow lifecycle: first batch in {fb['ttfb_start_fetch_s']*1e3:.1f} ms via START+FETCH "
        f"vs {fb['ttfb_cook_s']*1e3:.1f} ms blocking COOK; START acks in {fb['start_ack_s']*1e3:.1f} ms"
    )
    me = out["mesh"]
    print(
        f"#  catalog mesh: federated LIST {me['federated_list_cold_us']/1e3:.1f} ms cold / "
        f"{me['federated_list_cached_us']/1e3:.2f} ms cached; partition-parallel scan "
        f"{me['partition_speedup']:.2f}x vs single flow (byte-identical, K={me['k']})"
    )
    dsb = out["datasource"]
    rg = dsb.get("rowgroups_pruned_ratio")
    print(
        f"#  adapter pushdown at the source: sqlite {dsb['byte_reduction_sqlite_sql']:.0f}x fewer bytes "
        f"via compiled SQL; parquet row groups pruned "
        f"{'n/a (no pyarrow)' if rg is None else format(rg, '.0%')}; "
        f"jsonl blocks skipped {dsb['jsonl_blocks_skipped_ratio']:.0%}"
    )


if __name__ == "__main__":
    main()
