"""Reduction of a profiler trace to device busy time, program and op
times, and idle gaps named by the benchmark's host spans.

``load`` reads an ``.xplane.pb`` into plain event lists; ``reduce`` does
the arithmetic on those lists, so tests can feed it a recorded trace or
hand-made events.  Device planes are those named ``/device:<KIND>:<i>``;
on each, the ``XLA Modules`` line holds one event per program execution
(named after the jitted function, e.g. ``jit__decode_fn(...)``) and the
``XLA Ops`` line one event per operation.  Host spans are the
``bench/...`` annotations the harness writes around each call into the
engine.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"


def _stats(ev) -> Dict:
    out = {}
    for k, v in ev.stats:
        if isinstance(v, (int, float, str)):
            out[k] = v
    return out


def op_name(text: str) -> Tuple[str, str]:
    """An op event's name and opcode from the HLO instruction a TPU trace
    names it by: ``%pvq_attn_q.8 = (f32[...], ...) custom-call(...), ...``
    -> ``("pvq_attn_q.8", "custom-call")``.  A name with no ``=`` is kept
    as it is, with no opcode."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    i, depth = 0, 0
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
    else:
        i = rest.find(" ")
    return head.lstrip("%"), rest[i + 1:].lstrip().split("(", 1)[0]


def load(path: str) -> Dict:
    """``{"devices": {plane: {"modules": [...], "ops": [...]}}, "host":
    [...]}``; each event ``{"name", "start", "dur"[, "stats"]}`` in ns,
    an op named by :func:`op_name`, with its opcode among its stats."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List]] = {}
    host: List[Dict] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            d = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                seen = set()
                for ev in line.events:
                    rec = {"name": ev.name, "start": float(ev.start_ns), "dur": float(ev.duration_ns)}
                    if key == "ops":
                        rec["name"], opcode = op_name(ev.name)
                        if rec["name"] not in seen:
                            # stats are read once per op name (``reduce`` keeps the first)
                            seen.add(rec["name"])
                            rec["stats"] = dict(_stats(ev), opcode=opcode)
                    d[key].append(rec)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append({"name": ev.name, "start": float(ev.start_ns), "dur": float(ev.duration_ns)})
    return {"devices": devices, "host": host}


def _clip(start: float, dur: float, lo: float, hi: float) -> Tuple[float, float]:
    return max(start, lo), min(start + dur, hi)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


class _HostSpans:
    """The harness's spans inside the window, which follow one another
    without nesting: the one covering a time is the last to start before
    it."""

    def __init__(self, host: Sequence[Dict]):
        self.spans = sorted(
            (h for h in host if h["name"] != WINDOW_SPAN), key=lambda h: h["start"]
        )
        self.starts = [h["start"] for h in self.spans]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t <= self.spans[i]["start"] + self.spans[i]["dur"]:
            return self.spans[i]["name"]
        return "(no span)"


def module_name(name: str) -> str:
    """``jit__decode_fn(123)`` -> ``jit__decode_fn``."""
    return name.split("(", 1)[0]


def reduce(events: Dict, window: Optional[Tuple[float, float]] = None) -> Dict:
    """Busy and idle time, program and op times, inside the window.

    The window is the ``bench/window`` host span unless given.  Every
    device time is averaged over the device planes.  Returns ``window_ns``,
    ``busy_ns``, ``modules`` (program -> ``{"calls", "ns"}``, calls that
    start in the window), ``ops`` (program -> op name -> ns, each op
    assigned to the program execution that contains it), ``op_stats`` (op
    name -> the stats of its first event), ``top_ops`` and ``idle_by_host``
    (host span -> idle ns), longest first.
    """
    host = events["host"]
    spans = _HostSpans(host)
    if window is None:
        win = [h for h in host if h["name"] == WINDOW_SPAN]
        if not win:
            raise ValueError("trace holds no bench/window span")
        window = (win[0]["start"], win[0]["start"] + win[0]["dur"])
    lo, hi = window
    devs = events["devices"]
    n_dev = max(len(devs), 1)
    busy = 0.0
    modules: Dict[str, Dict[str, float]] = defaultdict(lambda: {"calls": 0, "ns": 0.0})
    ops: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    op_total: Dict[str, float] = defaultdict(float)
    op_stats: Dict[str, Dict] = {}
    idle: Dict[str, float] = defaultdict(float)
    for d in devs.values():
        mods = sorted(d["modules"], key=lambda e: e["start"])
        for m in mods:
            if lo <= m["start"] < hi:
                rec = modules[module_name(m["name"])]
                rec["calls"] += 1 / n_dev
                a, b = _clip(m["start"], m["dur"], lo, hi)
                rec["ns"] += (b - a) / n_dev
        starts = [m["start"] for m in mods]
        intervals = []
        for op in d["ops"]:
            if "stats" in op:
                op_stats.setdefault(op["name"], op["stats"])
            a, b = _clip(op["start"], op["dur"], lo, hi)
            if b <= a:
                continue
            intervals.append((a, b))
            owner = "(no program)"
            i = bisect.bisect_right(starts, op["start"]) - 1
            if i >= 0 and op["start"] < mods[i]["start"] + mods[i]["dur"]:
                owner = module_name(mods[i]["name"])
            ops[owner][op["name"]] += (b - a) / n_dev
            op_total[op["name"]] += (b - a) / n_dev
        merged = _union(intervals)
        busy += sum(b - a for a, b in merged) / n_dev
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle[spans.at((a + b) / 2)] += (b - a) / n_dev
    return {
        "devices": len(devs),
        "window_ns": hi - lo,
        "busy_ns": busy,
        "modules": {k: dict(v) for k, v in modules.items()},
        "ops": {k: dict(v) for k, v in ops.items()},
        "op_stats": op_stats,
        "top_ops": sorted(op_total.items(), key=lambda kv: -kv[1]),
        "idle_by_host": sorted(idle.items(), key=lambda kv: -kv[1]),
    }
