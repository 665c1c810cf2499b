"""Trace reduction against hand-computed values."""

import pytest

from harness import trace

MS = 1e6  # ns


def _events():
    """One device, a 100 ms window [0, 100 ms):
    decode program at [10, 40) ms holding ops [10, 20) k3, [20, 30) k4,
    [25, 35) g (overlapping k4 by 5 ms); chunk program at [60, 90) ms with
    op [60, 90) k3; an op [95, 105) ms outside any program that the window
    clips to 5 ms.  Host spans: decode_step [5, 45), admit [45, 60),
    chunk_step [60, 92), idle [92, 100)."""
    dev = {
        "modules": [
            {"name": "jit__decode_fn(7)", "start": 10 * MS, "dur": 30 * MS},
            {"name": "jit__chunk_fn(9)", "start": 60 * MS, "dur": 30 * MS},
        ],
        "ops": [
            {"name": "k3", "start": 10 * MS, "dur": 10 * MS, "stats": {"tf_op": "pallas"}},
            {"name": "k4", "start": 20 * MS, "dur": 10 * MS, "stats": {}},
            {"name": "g", "start": 25 * MS, "dur": 10 * MS, "stats": {}},
            {"name": "k3", "start": 60 * MS, "dur": 30 * MS, "stats": {}},
            {"name": "stray", "start": 95 * MS, "dur": 10 * MS, "stats": {}},
        ],
    }
    host = [
        {"name": "bench/window", "start": 0.0, "dur": 100 * MS},
        {"name": "bench/decode_step", "start": 5 * MS, "dur": 40 * MS},
        {"name": "bench/admit", "start": 45 * MS, "dur": 15 * MS},
        {"name": "bench/chunk_step", "start": 60 * MS, "dur": 32 * MS},
        {"name": "bench/idle", "start": 92 * MS, "dur": 8 * MS},
    ]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_busy_union_and_idle():
    r = trace.reduce(_events())
    assert r["window_ns"] == 100 * MS
    # busy: [10, 35) + [60, 90) + [95, 100) = 25 + 30 + 5 ms
    assert r["busy_ns"] == pytest.approx(60 * MS)
    # each idle gap goes to the host span at its midpoint: [0, 10) at 5 ms
    # to decode_step, [35, 60) at 47.5 ms to admit, [90, 95) at 92.5 ms to idle
    assert r["idle_by_host"] == [
        ("bench/admit", pytest.approx(25 * MS)),
        ("bench/decode_step", pytest.approx(10 * MS)),
        ("bench/idle", pytest.approx(5 * MS)),
    ]


def test_program_and_op_times():
    r = trace.reduce(_events())
    assert r["modules"]["jit__decode_fn"] == {"calls": 1, "ns": 30 * MS}
    assert r["modules"]["jit__chunk_fn"] == {"calls": 1, "ns": 30 * MS}
    assert r["ops"]["jit__decode_fn"] == {"k3": 10 * MS, "k4": 10 * MS, "g": 10 * MS}
    assert r["ops"]["jit__chunk_fn"] == {"k3": 30 * MS}
    assert r["ops"]["(no program)"] == {"stray": 5 * MS}
    assert r["top_ops"][0] == ("k3", 40 * MS)
    assert r["op_stats"]["k3"] == {"tf_op": "pallas"}


def test_devices_are_averaged():
    ev = _events()
    ev["devices"]["/device:TPU:1"] = {"modules": [], "ops": []}
    r = trace.reduce(ev)
    assert r["devices"] == 2
    assert r["busy_ns"] == pytest.approx(30 * MS)
    # the empty device is idle the whole window: its one gap, midpoint
    # 50 ms, goes to admit at half weight
    assert sum(v for _, v in r["idle_by_host"]) == pytest.approx(70 * MS)
    assert dict(r["idle_by_host"])["bench/admit"] == pytest.approx(12.5 * MS + 50 * MS)


@pytest.mark.parametrize("text, want", [
    # as a TPU trace names ops (the shapes shortened)
    ("%pvq_attn_q.8 = (f32[320,8,64]{2,1,0:T(8,128)S(1)}, f32[320,8,1]{2,1,0:T(8,128)S(1)}) "
     "custom-call(%copy-done.60, %copy-done.45), custom_call_target=\"tpu_custom_call\"",
     ("pvq_attn_q.8", "custom-call")),
    ("%cond.28 = (s8[4097,32,5,64]{3,1,2,0:T(8,128)(4,1)}, f32[4097,32,5,2]{1,3,2,0:T(2,128)}) "
     "conditional(s32[]{:T(128)} %convert_element_type.639, (s8[4097,32,5,64]) %tuple.360)",
     ("cond.28", "conditional")),
    ("%fusion.12 = f32[64,1,960]{2,0,1:T(8,128)} fusion(%param.1), kind=kLoop", ("fusion.12", "fusion")),
    ("copy.3", ("copy.3", "")),
])
def test_op_name(text, want):
    assert trace.op_name(text) == want
