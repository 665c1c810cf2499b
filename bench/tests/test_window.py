"""Window accounting on a synthetic token timeline, and the traced readers."""

from types import SimpleNamespace

import pytest

from harness import spec
from harness.cell import Rec, Run, Step


def _rec(due, times, setup=False):
    req = SimpleNamespace(generated=[1] * len(times))
    return Rec(req=req, due=due, released=due, setup=setup, times=list(times))


def _run(records, steps=(), trace=None):
    return Run(arch={}, config={"engine": {"page": 4}}, records=records,
               t0=10.0, t1=20.0, setup_s=3.5, steps=list(steps), trace=trace)


def _read(name, run):
    return spec._module(spec.BENCH / "metrics" / f"{name}.py").read(run)


RECORDS = [
    _rec(5.0, [9.0, 11.0, 12.0], setup=True),  # set-up request: tokens at 11, 12 count
    _rec(10.5, [11.5, 12.0, 12.6]),
    _rec(12.0, [14.0]),
    _rec(15.0, [16.5, 21.0]),  # its second token after the close
    _rec(19.5, [22.5]),  # due in the window, its token after the close
    _rec(20.5, [21.0]),  # due after the close
]


def test_tokens_per_second_counts_the_window_only():
    # in [10, 20]: 11, 12 | 11.5, 12, 12.6 | 14 | 16.5 -> 7 tokens over 10 s
    assert _read("output_tokens_per_s", _run(RECORDS)) == pytest.approx(0.7)


def test_setup_and_traced_readers():
    tr = {"devices": 1, "window_ns": 10e9, "busy_ns": 7.5e9,
          "modules": {"jit__decode_fn": {"calls": 4, "ns": 2e9}}, "ops": {}, "op_stats": {}}
    run = _run(RECORDS, steps=[Step(11.0, 11.5, [40, 8])], trace=tr)
    assert _read("setup_s", run) == 3.5
    assert _read("decode_step_ms.batch", run) == pytest.approx(500.0)
    assert _read("device_idle_share.batch", run) == pytest.approx(25.0)
    # no kernel events to read: the rooflines stay silent, never 0
    assert _read("v3_roofline", run) is None and _read("v4_roofline", run) is None
    # untraced: every traced reader is silent
    untraced = _run(RECORDS, steps=[Step(11.0, 11.5, [40, 8])])
    for name in ("decode_step_ms.batch", "device_idle_share.batch", "decode_mfu"):
        assert _read(name, untraced) is None, name


def test_rooflines_read_the_kernels_by_their_compiled_names():
    """v3 and v4 are matched by the custom calls' names in the compiled
    decode program (``pvq_matmul_q.58``, ``pvq_attn_q.8``); other ops of
    the program, and the same kernels in another program, do not count."""
    ref = spec.reference({"reference": "llama"})
    arch = {"n_layers": 2, "d_model": 960, "n_heads": 15, "n_kv_heads": 5, "head_dim": 64,
            "d_ff": 2560, "vocab_size": 4096}
    peaks = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    tr = {"devices": 1, "window_ns": 10e9, "busy_ns": 5e9, "modules": {}, "op_stats": {},
          "ops": {"jit__decode_fn": {"pvq_matmul_q.58": 2e6, "pvq_matmul_q.61": 2e6,
                                     "pvq_attn_q.8": 1e6, "fusion.12": 9e6},
                  "jit__chunk_fn": {"pvq_matmul_q.3": 50e6, "pvq_attn_q.1": 50e6}}}
    run = _run(RECORDS, steps=[Step(11.0, 11.5, [40, 70])], trace=tr)
    run.arch, run.peaks, run.ref = arch, peaks, ref
    run.config = {"engine": {"page": 32, "kv_group": 32}, "weights": {"group": 256}}
    v3 = ref.v3_step(arch, 2, 256, peaks)
    v4 = ref.v4_step(arch, [32, 64], 32, peaks)
    assert _read("v3_roofline", run) == pytest.approx(100.0 * v3 / 4e-3)
    assert _read("v4_roofline", run) == pytest.approx(100.0 * v4 / 1e-3)
    ops = ref.decode_token_ops(arch, 40) + ref.decode_token_ops(arch, 70)
    assert _read("decode_mfu", run) == pytest.approx(100.0 * ops / (10.0 * 393e12))
