"""The engine's spans and the ``kv_page_encode`` scope on a recorded trace.

``bench/testdata/tiny_engine_spans.json.gz`` holds what a traced window on
a TPU v5e gave, with the telemetry registry on: the engine at
smollm-360m's widths with 2 layers and 4 slots, 10 iterations of the
harness's loop (decode steps, one of them completing a KV page, and one
whole-prompt admission).  Beside ``harness.trace.load``'s events
(``devices`` and the ``bench/`` spans in ``host``) it holds the engine's
own spans with their args (``engine``, read by
``repro.runtime.telemetry.read_host_spans``) and the scope path of each
op in it (``scopes``, ``telemetry.hlo_op_scopes`` of the compiled decode
and graft programs).  These are what per-layer metrics of the KV page
encode per completed page, the host time of a decode step and the slots'
occupancy would read."""

import bisect
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import readers, spec, trace
from harness.cell import Run, Step
import probe_engine_spans as probe
from repro.core.packed import KV_ENCODE_SCOPE
from repro.runtime.telemetry import in_scope

DATA = Path(__file__).resolve().parents[1] / "testdata" / "tiny_engine_spans.json.gz"
DECODE = "jit__decode_fn"
CHILDREN = ["engine/decode/prepare", "engine/decode/launch", "engine/decode/wait",
            "engine/decode/commit"]


@pytest.fixture(scope="module")
def events():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def device(events):
    [dev] = events["devices"].values()
    return dev


def _end(s):
    return s["start"] + s["dur"]


def _named(events, name):
    return [s for s in events["engine"] if s["name"] == name]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return sum(b - a for a, b in out)


def _decode_calls(device):
    """Each execution of the decode program with its ops."""
    mods = sorted(device["modules"], key=lambda m: m["start"])
    starts = [m["start"] for m in mods]
    calls = {m["start"]: (m, []) for m in mods if m["name"].split("(")[0] == DECODE}
    for op in device["ops"]:
        i = bisect.bisect_right(starts, op["start"]) - 1
        if i >= 0 and mods[i]["start"] in calls and op["start"] < _end(mods[i]):
            calls[mods[i]["start"]][1].append(op)
    return [calls[k] for k in sorted(calls)]


def test_engine_spans_nest_inside_the_harness_spans(events):
    bench = {n: [h for h in events["host"] if h["name"] == n]
             for n in ("bench/decode_step", "bench/admit")}
    steps = _named(events, "engine/decode_step")
    kids = [s for s in events["engine"] if s["name"].startswith("engine/decode/")]
    assert len(steps) >= 8 and len(kids) == 4 * len(steps)
    for st in steps:
        assert any(b["start"] <= st["start"] and _end(st) <= _end(b) for b in bench["bench/decode_step"])
        inside = [k for k in kids if st["start"] <= k["start"] < _end(st)]
        assert [k["name"] for k in inside] == CHILDREN
        assert all(_end(a) <= b["start"] for a, b in zip(inside, inside[1:]))
        assert _end(inside[-1]) <= _end(st)
        assert {"active", "n_slots", "queue", "free_pages", "pages_completed"} <= set(st["args"])
    for name in ("engine/prefill", "engine/graft"):
        [s] = _named(events, name)
        assert any(a["start"] <= s["start"] and _end(s) <= _end(a) for a in _named(events, "engine/admit"))


def test_the_decode_conditional_is_the_scoped_encode(events, device):
    """The decode program's one conditional lies under ``kv_page_encode``,
    and the union of the scoped ops per decode call is 90-100.5% of the
    conditional's time, which ``kv_encode_ms.batch`` reads by opcode."""
    scopes = events["scopes"]
    opcode = {op["name"]: op["stats"]["opcode"] for op in device["ops"] if "stats" in op}
    calls = _decode_calls(device)
    conds = {op["name"] for _, ops in calls for op in ops if opcode[op["name"]] == "conditional"}
    assert len(conds) == 1 and in_scope(scopes[conds.pop()], KV_ENCODE_SCOPE)
    scoped = sum(_union([(op["start"], _end(op)) for op in ops
                         if in_scope(scopes.get(op["name"], ""), KV_ENCODE_SCOPE)])
                 for _, ops in calls)
    mod = spec._module(spec.BENCH / "metrics" / "kv_encode_ms.batch.py")
    run = SimpleNamespace(trace=trace.reduce(events))
    cond_ms = mod.read(run)
    assert 0.90 <= scoped / len(calls) / 1e6 / cond_ms <= 1.005
    # the ops inside the conditional (the bisection's loops) are scoped too
    assert {opcode[n] for n in scopes if in_scope(scopes[n], KV_ENCODE_SCOPE)} >= {"conditional", "while"}


def test_pages_completed_are_the_steps_that_encode(events, device):
    """A decode step whose span counts a completed page is the one whose
    conditional takes the encode branch: many times longer than the rest."""
    opcode = {op["name"]: op["stats"]["opcode"] for op in device["ops"] if "stats" in op}
    steps = _named(events, "engine/decode_step")
    cond = {}
    for m, ops in _decode_calls(device):
        st = [s for s in steps if s["start"] <= m["start"] < _end(s)]
        if st:
            cond[st[0]["start"]] = (st[0]["args"]["pages_completed"],
                                    sum(op["dur"] for op in ops if opcode[op["name"]] == "conditional"))
    encode = [ns for pages, ns in cond.values() if pages]
    skip = [ns for pages, ns in cond.values() if not pages]
    assert encode and skip and min(encode) > 10 * max(skip)


def test_the_graft_encodes_under_the_scope(events, device):
    mods = [m for m in device["modules"] if m["name"].startswith("jit__graft_fn")]
    assert mods
    lo, hi = mods[0]["start"], _end(mods[0])
    scoped = [op for op in device["ops"] if lo <= op["start"] < hi
              and in_scope(events["scopes"].get(op["name"], ""), KV_ENCODE_SCOPE)]
    assert scoped


def test_idle_in_a_decode_step_lies_in_its_children(events, device):
    """Of the device's idle time inside ``bench/decode_step``, 90% or more
    lies inside one of the ``engine/decode/*`` spans, which therefore name
    what the host was doing in it."""
    w = [h for h in events["host"] if h["name"] == "bench/window"][0]
    lo, hi = w["start"], _end(w)
    busy = sorted((max(op["start"], lo), min(_end(op), hi)) for op in device["ops"]
                  if min(_end(op), hi) > max(op["start"], lo))
    merged = []
    for a, b in busy:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]

    def idle_in(spans):
        return sum(max(0.0, min(b, _end(s)) - max(a, s["start"])) for a, b in gaps for s in spans)

    in_step = idle_in([h for h in events["host"] if h["name"] == "bench/decode_step"])
    in_kids = idle_in([s for s in events["engine"] if s["name"] in CHILDREN])
    assert in_step > 0 and in_kids >= 0.9 * in_step


def test_the_proposed_readings(events, device):
    """Host time of a decode step (its span less its wait), the slots'
    occupancy and the encode per completed page all have something to read."""
    steps = _named(events, "engine/decode_step")
    waits = _named(events, "engine/decode/wait")
    host = [st["dur"] - sum(w["dur"] for w in waits if st["start"] <= w["start"] < _end(st))
            for st in steps]
    assert all(0 < h < 10e6 for h in host)
    assert all(st["args"]["active"] == st["args"]["n_slots"] for st in steps)
    assert sum(st["args"]["pages_completed"] for st in steps) >= 1
    run = SimpleNamespace(trace=trace.reduce(events))
    assert readers.module_ms(run, DECODE) > 0


def _steps_from_spans(events, page):
    """The harness's ``Step`` record of each decode step in the window,
    from its span's args: one length per active slot, a multiple of the
    page for each page completed."""
    w = [h for h in events["host"] if h["name"] == "bench/window"][0]
    out = []
    for st in _named(events, "engine/decode_step"):
        if w["start"] <= st["start"] < _end(w):
            a = st["args"]
            lengths = [page] * a["pages_completed"] + [page + 1] * (a["active"] - a["pages_completed"])
            out.append(Step(st["start"], _end(st), lengths))
    return out


def test_the_step_readers_on_the_recorded_trace(events):
    """``kv_encode_ms_per_page.batch`` is ``kv_encode_ms.batch`` times the
    decode calls over the pages completed, and lies within 90-100.5% of
    the scoped union per page; ``slot_occupancy.batch`` reads 100%."""
    page = 32
    run = Run(arch={}, config={"engine": {"page": page, "n_slots": 4}}, records=[], t0=0.0,
              t1=1.0, setup_s=0.0, steps=_steps_from_spans(events, page), trace=trace.reduce(events))

    def read(name):
        return spec._module(spec.BENCH / "metrics" / f"{name}.py").read(run)

    pages = sum(n % page == 0 for s in run.steps for n in s.lengths)
    calls = run.trace["modules"][DECODE]["calls"]
    assert pages >= 1 and len(run.steps) == calls
    per_page = read("kv_encode_ms_per_page.batch")
    assert per_page == pytest.approx(read("kv_encode_ms.batch") * calls / pages)
    scoped = probe.readings(events, events["engine"], events["scopes"])["kv_encode_ms_per_page"]
    assert 0.90 <= scoped / per_page <= 1.005
    assert read("slot_occupancy.batch") == pytest.approx(100.0)


def test_the_probe_on_the_recorded_trace(events):
    r = probe.readings(events, events["engine"], events["scopes"])
    assert r["decode_steps"] == r["decode_calls"] >= 8 and r["pages_completed"] >= 1
    assert 0.90 <= r["scoped_over_conditional"] <= 1.005
    assert 0 < r["decode_host_ms"] < 10 and r["slot_occupancy"] == pytest.approx(100.0)
    assert set(r["step_parts_ms"]) == {c.rsplit("/", 1)[1] for c in CHILDREN}
    assert r["of_which_in_engine_decode_children"] >= 0.9
    # the longest idle lies in a decode step's child, by its gaps' midpoints
    assert r["idle_by_innermost_span_s"][0][0] in CHILDREN
