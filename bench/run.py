"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell on one chip, warms up (set-up), serves the cell's traffic
through ``PVQEngine`` for ``--seconds``, checks what was served against
the plain reference, and prints as the last line of standard output one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer ones with
``--trace 1``), ``device`` and, traced, ``breakdown``, then ``checks``:
each number compared, with its limit.  The same numbers are the last
lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent


def _args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result(spec, out, trace: bool) -> dict:
    """The result line from a finished run (``harness.cell.run``)."""
    from harness import spec as spec_lib

    run = out["run"]
    run.peaks = spec_lib.peaks(out["device"]["kind"]) if out["device"]["platform"] == "tpu" else spec.get("peaks")
    metrics = {}
    for m, reader in spec["per_layer" if trace else "end_to_end"]:
        v = reader.read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {
        "correct": all(c["ok"] for c in out["checks"].values()),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": dict(out["device"]),
    }
    if trace and run.trace is not None:
        t = run.trace
        line["device"]["busy_s"] = t["busy_ns"] / 1e9
        line["device"]["window_s"] = t["window_ns"] / 1e9
        line["breakdown"] = {
            "device_ops": [[f"{k} {t['op_stats'].get(k, {}).get('opcode', '')}".strip(), v / 1e9]
                           for k, v in t["top_ops"][:10]],
            "idle_gaps": [[k, v / 1e9] for k, v in t["idle_by_host"][:10]],
        }
    line["checks"] = {k: {kk: vv for kk, vv in c.items() if kk != "ok"} for k, c in out["checks"].items()}
    return line


def main(argv=None) -> int:
    args = _args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    sys.path.insert(0, str(BENCH))
    from harness import cell, spec as spec_lib

    spec = spec_lib.cell(args.workload)
    try:
        out = cell.run(spec, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    except cell.NoChip as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    line = result(spec, out, bool(args.trace))
    for name, c in line["checks"].items():
        print(f"check {name}: " + ", ".join(f"{k} {v}" for k, v in c.items()), file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
