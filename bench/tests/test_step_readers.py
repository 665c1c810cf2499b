"""The readers of the decode steps' pages and slots, and the engine-span
probe's arithmetic, on hand-made runs and events."""

import pytest

from harness import spec
from harness.cell import Run, Step
import probe_engine_spans as probe


def _read(name, run):
    return spec._module(spec.BENCH / "metrics" / f"{name}.py").read(run)


def _run(steps, trace=None, n_slots=4):
    return Run(arch={}, config={"engine": {"page": 4, "n_slots": n_slots}}, records=[],
               t0=10.0, t1=20.0, setup_s=3.5, steps=list(steps), trace=trace)


#: lengths after each step; a multiple of the page (4) completes one: the
#: 4 of the first step and both 8s of the second, three pages in all
STEPS = [Step(11.0, 12.0, [4, 7, 9]), Step(12.0, 13.0, [5, 8, 8, 10])]
TRACE = {"devices": 1, "window_ns": 10e9, "busy_ns": 9e9,
         "modules": {"jit__decode_fn": {"calls": 2, "ns": 8e6}},
         "ops": {"jit__decode_fn": {"cond.28": 6e6, "fusion.3": 1e6},
                 "jit__graft_fn": {"cond.4": 50e6}},
         "op_stats": {"cond.28": {"opcode": "conditional"}, "fusion.3": {"opcode": "fusion"},
                      "cond.4": {"opcode": "conditional"}}}


def test_encode_per_completed_page():
    """The decode program's conditional (not the graft's) over the pages
    the steps completed; the same time as ``kv_encode_ms.batch`` per call
    times the calls over the pages."""
    run = _run(STEPS, TRACE)
    assert _read("kv_encode_ms_per_page.batch", run) == pytest.approx(2.0)
    per_call = _read("kv_encode_ms.batch", run)
    assert _read("kv_encode_ms_per_page.batch", run) == pytest.approx(per_call * 2 / 3)


def test_encode_per_page_is_silent_without_pages_or_trace():
    assert _read("kv_encode_ms_per_page.batch", _run([Step(11.0, 12.0, [5, 7])], TRACE)) is None
    assert _read("kv_encode_ms_per_page.batch", _run(STEPS)) is None
    no_cond = dict(TRACE, ops={"jit__decode_fn": {"fusion.3": 1e6}})
    assert _read("kv_encode_ms_per_page.batch", _run(STEPS, no_cond)) is None


def test_slot_occupancy():
    assert _read("slot_occupancy.batch", _run(STEPS, TRACE)) == pytest.approx(100.0 * 7 / 8)
    assert _read("slot_occupancy.batch", _run(STEPS, n_slots=8)) == pytest.approx(100.0 * 7 / 16)
    assert _read("slot_occupancy.batch", _run([])) is None


def _span(name, start, dur, **args):
    return {"name": name, "start": float(start), "dur": float(dur), "args": args}


def test_innermost_span_of_nested_spans():
    spans = [_span("bench/decode_step", 0, 100), _span("engine/decode_step", 5, 90),
             _span("engine/decode/launch", 10, 20), _span("engine/decode/wait", 40, 50),
             _span("bench/stamp", 101, 5)]
    at = probe.Innermost(spans).at
    assert at(15) == "engine/decode/launch"
    assert at(35) == "engine/decode_step"  # between two children
    assert at(60) == "engine/decode/wait"
    assert at(97) == "bench/decode_step"  # after the engine's step ended
    assert at(103) == "bench/stamp"
    assert at(200) == "(no span)"


def test_probe_readings_by_hand():
    """One decode step of 100 ns whose program runs from 20 to 60 and
    whose wait is 40-90, then an admission whose graft runs from 110 to
    120: the idle is 0-20 (in the launch), 60-110 (its midpoint in the
    wait) and 120-150 (in no span), and the host time is 100 - 50."""
    host = [_span("bench/window", 0, 150), _span("bench/decode_step", 0, 100),
            _span("bench/admit", 100, 30)]
    engine = [_span("engine/decode_step", 0, 100, active=3, n_slots=4, pages_completed=2),
              _span("engine/decode/prepare", 1, 9), _span("engine/decode/launch", 10, 30),
              _span("engine/decode/wait", 40, 50), _span("engine/decode/commit", 90, 9)]
    ops = [{"name": "cond.28", "start": 20.0, "dur": 30.0, "stats": {"opcode": "conditional"}},
           {"name": "while.5", "start": 25.0, "dur": 20.0, "stats": {"opcode": "while"}},
           {"name": "fusion.1", "start": 50.0, "dur": 10.0, "stats": {"opcode": "fusion"}},
           {"name": "cond.2", "start": 110.0, "dur": 10.0, "stats": {"opcode": "conditional"}}]
    events = {"host": host, "devices": {"/device:TPU:0": {
        "modules": [{"name": "jit__decode_fn(1)", "start": 20.0, "dur": 40.0},
                    {"name": "jit__graft_fn(2)", "start": 110.0, "dur": 10.0}], "ops": ops}}}
    scopes = {"cond.28": "jit(_decode_fn)/kv_page_encode/cond",
              "while.5": "jit(_decode_fn)/vmap(kv_page_encode)/while",
              "fusion.1": "jit(_decode_fn)/dot_general",
              "cond.2": "jit(_graft_fn)/kv_page_encode/cond"}
    r = probe.readings(events, engine, scopes)
    assert (r["decode_calls"], r["decode_steps"], r["pages_completed"]) == (1, 1, 2)
    assert r["kv_encode_ms_per_page"] == pytest.approx(30 / 2 / 1e6)
    assert r["scoped_over_conditional"] == pytest.approx(1.0)
    assert r["decode_host_ms"] == pytest.approx(50 / 1e6)
    assert r["step_parts_ms"] == pytest.approx({"prepare": 9e-6, "launch": 30e-6, "wait": 50e-6,
                                                "commit": 9e-6})
    assert r["slot_occupancy"] == pytest.approx(75.0)
    assert r["idle_s"] == pytest.approx(100e-9)
    assert dict(r["idle_by_innermost_span_s"]) == pytest.approx(
        {"engine/decode/launch": 20e-9, "engine/decode/wait": 50e-9, "(no span)": 30e-9})
    # inside bench/decode_step: 0-20 and 60-100; of that, all but 0-1 and 99-100
    assert r["idle_in_bench_decode_step_s"] == pytest.approx(60e-9)
    assert r["of_which_in_engine_decode_children"] == pytest.approx(58 / 60)
