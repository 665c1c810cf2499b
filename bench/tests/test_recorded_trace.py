"""The reduction and the readers' names on a recorded trace.

``bench/testdata/tiny_engine_trace.json.gz`` holds the events that
``harness.trace.load`` read from a traced window on a TPU v5e: the engine
at smollm-360m's widths with 2 layers and 4 slots, cut to 40 ms that hold
decode steps (one of them encoding a completed KV page), a whole-prompt
prefill and its graft.  The busy time and the program calls are counted
here a second way, by sweeping the events' edges."""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import readers, spec, trace

DATA = Path(__file__).resolve().parents[1] / "testdata" / "tiny_engine_trace.json.gz"


@pytest.fixture(scope="module")
def events():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(events):
    return trace.reduce(events)


def _window(events):
    w = [h for h in events["host"] if h["name"] == "bench/window"][0]
    return w["start"], w["start"] + w["dur"]


def test_busy_by_sweeping_edges(events, reduced):
    lo, hi = _window(events)
    edges = []
    for op in events["devices"]["/device:TPU:0"]["ops"]:
        a, b = max(op["start"], lo), min(op["start"] + op["dur"], hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(edges):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert reduced["window_ns"] == hi - lo
    assert reduced["busy_ns"] == pytest.approx(busy, rel=1e-9)
    assert 0 < busy < hi - lo
    idle = sum(v for _, v in reduced["idle_by_host"])
    assert idle == pytest.approx(hi - lo - busy, rel=1e-9)
    assert {k for k, _ in reduced["idle_by_host"]} <= {h["name"] for h in events["host"]} | {"(no span)"}


def test_program_calls(events, reduced):
    lo, hi = _window(events)
    mods = events["devices"]["/device:TPU:0"]["modules"]
    for prog in ("jit__decode_fn", "jit__prefill_fn", "jit__graft_fn"):
        n = sum(1 for m in mods if m["name"].split("(")[0] == prog and lo <= m["start"] < hi)
        assert n >= 1 and reduced["modules"][prog]["calls"] == n


@pytest.mark.parametrize("metric", ["v3_roofline", "v4_roofline", "kv_encode_ms.batch"])
def test_readers_find_their_ops(reduced, metric):
    """Each reader's names match ops of the decode program, and no op of
    another program."""
    mod = spec._module(spec.BENCH / "metrics" / f"{metric}.py")
    pattern = getattr(mod, "KERNEL", None) or mod.OPCODE
    run = SimpleNamespace(trace=reduced)
    assert readers.ops_ns(run, mod.PROGRAM, pattern) > 0
    assert readers.ops_ns(run, "jit__graft_fn", pattern) == 0


def test_kv_encode_within_the_decode_step(reduced):
    mod = spec._module(spec.BENCH / "metrics" / "kv_encode_ms.batch.py")
    run = SimpleNamespace(trace=reduced)
    assert 0 < mod.read(run) < readers.module_ms(run, "jit__decode_fn")
