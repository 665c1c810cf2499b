"""Benchmark harness: one function per paper table + kernel/system benches.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only PREFIX]

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark), then a
human-readable table dump.  Kernel rows are additionally written to
``BENCH_kernels.json`` (us_per_call + bytes-ratios per kernel/shape), the
packed-vs-f32 serving rows to ``BENCH_serve.json``, the continuous-batching
engine rows (tok/s, p50/p99 latency, slot utilization) to
``BENCH_engine.json``, and the .pvqz codec rows (bits/weight +
encode/decode MB/s) to ``BENCH_artifact.json`` so future PRs can diff perf
trajectories.
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="EXPERIMENTS.md-scale settings")
    ap.add_argument("--only", default="", help="run only benches whose name starts with this")
    args = ap.parse_args()

    from repro.runtime.caches import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (artifact_bench, attn_bench, engine_bench,
                            kernel_bench, moe_bench, paper_tables, serve_bench)

    all_rows = []

    def run(name, fn, *fa, **fk):
        if args.only and not name.startswith(args.only):
            return
        print(f"[bench] {name} ...", file=sys.stderr, flush=True)
        rows = fn(*fa, **fk)
        for r in rows:
            r["bench_group"] = name
        all_rows.extend(rows)

    steps = {"A": 600, "B": 400, "C": 400, "D": 250} if args.full else {"A": 300, "B": 250, "C": 250, "D": 150}
    run("paper_tables_1_4", paper_tables.bench_tables_1_to_4, steps, args.full)
    run("paper_tables_5_8", paper_tables.bench_tables_5_to_8)
    run("paper_opcount", paper_tables.bench_opcount_claim)
    run("kernel_pvq_matmul", kernel_bench.bench_pvq_matmul)
    run("kernel_pvq_encode", kernel_bench.bench_pvq_encode)
    run("serve_packed", serve_bench.bench_serve_throughput)
    run("engine_continuous_batching", engine_bench.bench_engine)
    run("engine_chunked_prefill", engine_bench.bench_chunked_prefill)
    run("attn_packed_decode", attn_bench.bench_attention_decode)
    run("moe_packed_experts", moe_bench.bench_moe_experts)
    run("artifact_codecs", artifact_bench.bench_artifact_codecs)

    # CSV contract: name,us_per_call,derived
    print("name,us_per_call,derived")
    for r in all_rows:
        name = r.get("bench") or f"{r['bench_group']}:{r.get('table', r.get('net', ''))}"
        us = r.get("us_per_call", "")
        derived = {k: v for k, v in r.items() if k not in ("bench_group", "bench", "us_per_call")}
        print(f"{name},{us},{json.dumps(derived, default=str).replace(',', ';')}")

    # perf-trajectory files, one per bench family, all through the shared
    # telemetry payload wrapper so every BENCH_*.json row carries one schema
    # shape ({schema, backend, rows}) for cross-PR diffs
    from repro.runtime.telemetry import bench_payload

    trajectories = {
        "kernel_": ("BENCH_kernels.json", "bench-kernels-v1"),
        "moe_": ("BENCH_moe.json", "bench-moe-v1"),
        "serve_": ("BENCH_serve.json", "bench-serve-v1"),
        "engine_": ("BENCH_engine.json", "bench-engine-v1"),
        "attn_": ("BENCH_attention.json", "bench-attention-v1"),
        "artifact_": ("BENCH_artifact.json", "bench-artifact-v1"),
    }
    for prefix, (fname, schema) in trajectories.items():
        rows = [r for r in all_rows if r["bench_group"].startswith(prefix)]
        if not rows:
            continue
        with open(fname, "w") as f:
            json.dump(bench_payload(schema, rows), f, indent=1, default=str)
        print(f"wrote {fname}", file=sys.stderr)


if __name__ == "__main__":
    main()
