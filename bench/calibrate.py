"""Readings that the limits of ``bench/limits`` are set from, on the chip:
for each seed, one run of the cell as ``run.py`` makes it, then the
program's widest gap and the control's (the reference at int4
activations, at the same positions) on the same sampled requests, each
judged by the cell's limits as a run judges the program (``correct`` and
``control_correct``).  All seeds run in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3

Prints one JSON line per seed.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    sys.path.insert(0, str(BENCH))
    from harness import cell, judge, spec as spec_lib

    spec = spec_lib.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = cell.run(spec, seed, args.seconds, False, t_start=time.perf_counter(), control=True)
        reads = out["readings"]
        program = judge.checks(reads, spec["limits"])
        control = judge.checks(reads, spec["limits"], key="control_gap")
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": program["widest_gap"]["value"],
            "control": control["widest_gap"]["value"],
            "correct": all(c["ok"] for c in program.values()),
            "control_correct": all(c["ok"] for c in control.values()),
            "tokens": program["tokens_compared"]["value"],
            "per_request": reads, "device": out["device"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
