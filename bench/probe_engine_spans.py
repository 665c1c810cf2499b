"""One traced run of a cell, as ``run.py --trace 1`` makes it, with the
telemetry registry on for the window, and what the engine's own spans and
the ``kv_page_encode`` scope give there.

    python3 bench/probe_engine_spans.py --workload <cell> --seed <n> [--seconds 40] [--out FILE]

Prints one ``probe`` JSON line (also written to ``--out``), then the run's
result line.  Not a cell, and no metric reads it: the benchmark's own
runs leave the registry off, and ``harness.trace.load`` keeps only the
``bench/`` spans.  This script wraps the profiler's start and stop and the
loading of the trace, in its own process, to read inside the window:

- the union of the decode program's ops under ``kv_page_encode`` (the map
  from instruction names to scopes comes from the compiled decode
  program, ``telemetry.hlo_op_scopes``) per page that the
  ``engine/decode_step`` spans count in ``pages_completed``, and that
  union over the time of the conditional op that ``kv_encode_ms.batch``
  reads;
- the host time of a decode step, ``engine/decode_step`` less its
  ``engine/decode/wait``, and the mean time of each of its children;
- the slots' occupancy, the mean ``active / n_slots`` of those spans;
- the device's idle gaps, each put on the innermost span (``bench/`` or
  ``engine/``) at its midpoint, and by time, the share of the idle inside
  ``bench/decode_step`` that lies inside an ``engine/decode/*`` span.

The decode program is lowered before warm-up and compiled for the scope
map after the run, so the run is the harness's own up to the window; the
probe line gives the device's peak memory when the window opens and at
the end.  Without a TPU it exits 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Sequence, Tuple  # noqa: E402

BENCH = Path(__file__).resolve().parent
DECODE = "jit__decode_fn"
SCOPE = "kv_page_encode"
CHILD = "engine/decode/"
WINDOW = "bench/window"


def _end(span: Dict) -> float:
    return span["start"] + span["dur"]


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _overlap(gaps: Sequence[Tuple[float, float]], spans: Sequence[Dict]) -> float:
    """Time of ``gaps`` inside ``spans`` (spans that do not overlap)."""
    return sum(max(0.0, min(b, _end(s)) - max(a, s["start"])) for a, b in gaps for s in spans)


class Innermost:
    """Nested spans: the innermost one covering a time."""

    def __init__(self, spans: Sequence[Dict]):
        self.spans = sorted(spans, key=lambda s: (s["start"], -s["dur"]))
        self.starts = [s["start"] for s in self.spans]
        self.parent: List[int] = []
        stack: List[int] = []
        for i, s in enumerate(self.spans):
            while stack and _end(self.spans[stack[-1]]) < _end(s):
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0:
            if t <= _end(self.spans[i]):
                return self.spans[i]["name"]
            i = self.parent[i]
        return "(no span)"


def readings(events: Dict, engine: Sequence[Dict], scopes: Dict[str, str]) -> Dict:
    """The probe's readings from ``harness.trace.load``'s events of one
    chip (or none, as on the CPU), the ``engine/`` spans (``telemetry.read_host_spans``) and the
    scope map; times in the result are in ms or s as named."""
    from harness.trace import module_name
    from repro.runtime.telemetry import in_scope

    [win] = [h for h in events["host"] if h["name"] == WINDOW]
    lo, hi = win["start"], _end(win)
    bench = [h for h in events["host"] if h["name"] != WINDOW]
    dev = next(iter(events["devices"].values()), {"modules": [], "ops": []})
    mods = sorted(dev["modules"], key=lambda m: m["start"])
    starts = [m["start"] for m in mods]
    opcode = {op["name"]: op["stats"]["opcode"] for op in dev["ops"] if "stats" in op}
    busy, scoped, cond_ns = [], [], 0.0
    for op in dev["ops"]:
        a, b = max(op["start"], lo), min(_end(op), hi)
        if b <= a:
            continue
        busy.append((a, b))
        i = bisect.bisect_right(starts, op["start"]) - 1
        if i < 0 or op["start"] >= _end(mods[i]) or module_name(mods[i]["name"]) != DECODE:
            continue
        if in_scope(scopes.get(op["name"], ""), SCOPE):
            scoped.append((a, b))
        if opcode.get(op["name"]) == "conditional":
            cond_ns += b - a
    scoped_ns = sum(b - a for a, b in _union(scoped))

    steps = [s for s in engine if s["name"] == "engine/decode_step" and lo <= s["start"] < hi]
    kids = [s for s in engine if s["name"].startswith(CHILD)]
    parts: Dict[str, List[float]] = defaultdict(list)
    host_ms = []
    for st in steps:
        inside = [k for k in kids if st["start"] <= k["start"] < _end(st)]
        for k in inside:
            parts[k["name"][len(CHILD):]].append(k["dur"] / 1e6)
        wait = sum(k["dur"] for k in inside if k["name"] == CHILD + "wait")
        host_ms.append((st["dur"] - wait) / 1e6)
    pages = sum(st["args"].get("pages_completed", 0) for st in steps)

    edges = [lo] + [x for ab in _union(busy) for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    innermost = Innermost(bench + list(engine))
    by_span: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        by_span[innermost.at((a + b) / 2)] += (b - a) / 1e9
    in_step = _overlap(gaps, [h for h in bench if h["name"] == "bench/decode_step"])
    return {
        "window_s": (hi - lo) / 1e9,
        "decode_calls": sum(1 for m in mods if module_name(m["name"]) == DECODE and lo <= m["start"] < hi),
        "decode_steps": len(steps),
        "pages_completed": pages,
        "kv_encode_ms_per_page": scoped_ns / pages / 1e6 if pages else None,
        "scoped_over_conditional": scoped_ns / cond_ns if cond_ns else None,
        "decode_host_ms": sum(host_ms) / len(host_ms) if host_ms else None,
        "decode_host_ms_max": max(host_ms) if host_ms else None,
        "step_parts_ms": {k: sum(v) / len(v) for k, v in parts.items()},
        "slot_occupancy": (100.0 * sum(s["args"]["active"] / s["args"]["n_slots"] for s in steps) / len(steps)
                           if steps else None),
        "idle_s": sum(b - a for a, b in gaps) / 1e9,
        "idle_by_innermost_span_s": sorted(by_span.items(), key=lambda kv: -kv[1]),
        "idle_in_bench_decode_step_s": in_step / 1e9,
        "of_which_in_engine_decode_children": _overlap(gaps, kids) / in_step if in_step else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", default=None, help="write the probe line to this file too")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]
    import jax
    import numpy as np

    import run as run_lib
    from harness import cell, spec as spec_lib, trace as trace_lib
    from repro.runtime import obs, telemetry

    spec = spec_lib.cell(args.workload)
    dev = jax.devices()[0]
    got: Dict = {}

    def peak() -> int:
        return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    def on_engine(engine):
        # lowered here, where the engine's shapes are at hand, and compiled
        # after the run: a compile before warm-up left a higher peak of
        # device memory than the run alone
        z = np.zeros((engine.n_slots,), np.int32)
        wp = np.full((engine.n_slots,), engine.alloc.trash, np.int32)
        got["lowered"] = engine._decode.lower(engine.params, engine.cache, z[:, None], z,
                                              engine._page_table.copy(), wp)

    start, stop, load = jax.profiler.start_trace, jax.profiler.stop_trace, trace_lib.load

    def start_trace(*a, **kw):
        got["peak_bytes_at_window_open"] = peak()
        start(*a, **kw)
        obs.set_enabled(True)

    def stop_trace():
        obs.set_enabled(False)
        stop()

    def load_and_keep(path):
        got["events"] = load(path)
        got["engine"] = telemetry.read_host_spans(path)
        return got["events"]

    jax.profiler.start_trace, jax.profiler.stop_trace, trace_lib.load = start_trace, stop_trace, load_and_keep
    try:
        out = cell.run(spec, args.seed, args.seconds, True, t_start=T_START, on_engine=on_engine)
    except cell.NoChip as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    run = out["run"]
    # the compile cache's key then holds the metadata: an executable cached
    # from a tree without the scope cannot stand in for this one
    key = "jax_compilation_cache_include_metadata_in_key"
    prev = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        scopes = telemetry.hlo_op_scopes(got["lowered"].compile().as_text())
    finally:
        jax.config.update(key, prev)
    probe = {"workload": args.workload, "seed": args.seed,
             **{m["name"]: r.read(run) for m, r in spec["end_to_end"]},
             "scoped_ops": sum(1 for v in scopes.values() if telemetry.in_scope(v, SCOPE)),
             **{k: v for k, v in got.items() if k.startswith("peak_bytes")},
             "memory_peak_bytes": out["device"]["memory_peak_bytes"],
             **readings(got["events"], got["engine"], scopes)}
    text = json.dumps(probe)
    if args.out:
        Path(args.out).write_text(text + "\n")
    print("probe " + text, flush=True)
    print(json.dumps(run_lib.result(spec, out, True)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
