"""One run of one cell: build the served model from the seed, warm up,
serve the cell's traffic through ``PVQEngine`` for the window, then check
what the window served against the reference.

The loop mirrors ``PVQEngine.run``'s (admit, one chunk of chunked
prefill, one decode step) but is driven from here, so that every token is
stamped at the return of the call that produced it, and so that each call
into the engine sits in a host span of its own on the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from harness import judge, trace as trace_lib, weights


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Rec:
    """One request as the benchmark sees it."""

    req: Any  # repro.launch.engine.Request
    due: float  # host clock: when it was due
    released: float  # host clock: when it joined the engine's queue
    setup: bool  # released during set-up
    times: List[float] = dataclasses.field(default_factory=list)  # per token


@dataclasses.dataclass
class Step:
    """One decode step inside the window."""

    t0: float
    t1: float
    lengths: List[int]  # each active slot's positions after the step


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    arch: Dict  # ref.arch of the configuration
    config: Dict
    records: List[Rec]
    t0: float  # window open (host clock)
    t1: float  # window close
    setup_s: float
    steps: List[Step]
    trace: Optional[Dict] = None  # harness.trace.reduce of the window
    peaks: Optional[Dict] = None
    ref: Optional[ModuleType] = None  # the configuration's reference module

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Loop:
    """The serving loop around one engine and one generator."""

    def __init__(self, engine, gen):
        self.engine = engine
        self.gen = gen
        self.records: List[Rec] = []
        self.live: List[Rec] = []
        self.steps: List[Step] = []
        self.lateness: List[float] = []
        self.record_steps = False
        self._rid = 0

    def release(self, items, now: float, due_base: Optional[float], setup: bool) -> None:
        from repro.launch.engine import Request

        for it in items:
            req = Request(rid=self._rid, prompt=[int(x) for x in it["prompt"]],
                          max_new_tokens=int(it["max_new"]))
            self._rid += 1
            self.engine.validate(req)
            due = now if due_base is None or "due" not in it else due_base + it["due"]
            if not setup:
                self.lateness.append(now - due)
            rec = Rec(req=req, due=due, released=now, setup=setup)
            self.engine.pending.append(req)
            self.records.append(rec)
            self.live.append(rec)

    def stamp(self) -> None:
        now = time.perf_counter()
        keep = []
        for rec in self.live:
            n = len(rec.req.generated)
            if n > len(rec.times):
                rec.times.extend([now] * (n - len(rec.times)))
            if not rec.req.done:
                keep.append(rec)
        self.live = keep

    def iterate(self) -> bool:
        """One engine iteration; False when there was nothing to do."""
        eng = self.engine
        with TraceAnnotation("bench/admit"):
            admitted = eng.admit_pending()
        self.stamp()
        with TraceAnnotation("bench/chunk_step"):
            chunked = eng._prefill_step()
        self.stamp()
        lengths = [st.length + 1 for st in eng.slots if st is not None and st.phase == "decode"]
        t0 = time.perf_counter()
        with TraceAnnotation("bench/decode_step"):
            n = eng.step()
        t1 = time.perf_counter()
        if n and self.record_steps:
            self.steps.append(Step(t0, t1, lengths))
        with TraceAnnotation("bench/stamp"):
            self.stamp()
        return bool(admitted or chunked or n)

    def serve(self, until: Callable[[], bool], due_base: Optional[float], timeout: float) -> bool:
        """Serve until ``until()`` holds, or for ``timeout`` seconds at most
        (returns whether it held); releases due requests as it goes when
        ``due_base`` (the window's open) is given."""
        deadline = time.perf_counter() + timeout
        while not until():
            now = time.perf_counter()
            if now > deadline:
                return False
            if due_base is not None:
                with TraceAnnotation("bench/generator"):
                    items = self.gen.due(now - due_base, len(self.engine.pending))
                    self.release(items, now, due_base, setup=False)
            if not self.iterate():
                with TraceAnnotation("bench/idle"):
                    time.sleep(0.0005)
        return True


def run(spec: Dict, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_tpu: bool = True, on_engine: Callable = None, control: bool = False) -> Dict:
    """One run; returns the pieces of the result line (see ``run.py``).
    Set-up is timed from ``t_start``, the process's own start.
    ``control`` also reads the control on the same samples (``readings``
    in the result)."""
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < spec["chips"]:
        raise NoChip(f"the cell needs {spec['chips']} chips, JAX found {len(devices)}")

    from repro.core.quantize import ActQuant, KVQuant, set_default_act_quant, set_default_kv_quant
    from repro.launch.engine import PVQEngine
    from repro.nn.models import build_model
    from repro.runtime.caches import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    config, traffic, limits, ref = spec["config"], spec["traffic"], spec["limits"], spec["reference"]
    arch = ref.arch(weights.model_config(config))
    e = config["engine"]

    t_w = time.perf_counter()
    params, raw = weights.make(arch, config, seed, ref)
    jax.block_until_ready(params)
    log(f"weights made in {time.perf_counter() - t_w:.3f} s")
    set_default_act_quant(ActQuant(mode="per_row"))
    set_default_kv_quant(KVQuant(block=e["page"], group=e["kv_group"], k=e["kv_pulses"]))
    engine = PVQEngine(
        build_model(weights.model_config(config)), params, n_slots=e["n_slots"],
        max_len=e["max_len"], n_pages=e["n_pages"], prefill_chunk=e["prefill_chunk"],
        prefix_cache=e["prefix_cache"],
    )
    if on_engine is not None:
        on_engine(engine)
    ctx = {"seed": seed, "vocab": arch["vocab_size"], "n_slots": e["n_slots"], "page": e["page"]}
    gen = spec["generator"].Generator(traffic["params"], ctx)
    t_w = time.perf_counter()
    engine.warmup(gen.warm_prompt_lens())
    jax.block_until_ready(engine.cache)
    log(f"warm-up in {time.perf_counter() - t_w:.3f} s; trace counts {engine.trace_counts}")
    loop = Loop(engine, gen)
    t_w = time.perf_counter()
    loop.release(gen.setup_items(), time.perf_counter(), None, setup=True)
    if not loop.serve(lambda: gen.ready(engine), None, timeout=600):
        raise TimeoutError("set-up did not bring the engine to the traffic's starting state")
    log(f"starting state in {time.perf_counter() - t_w:.3f} s")
    setup_s = time.perf_counter() - t_start
    counts0 = dict(engine.trace_counts)
    log(f"set-up {setup_s:.3f} s; trace counts before the window {counts0}")

    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    if trace:
        # device ops and the harness's own spans only: no Python call
        # tracing and no runtime events, which would swell the trace
        # manyfold and slow the host
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    loop.record_steps = True
    t0 = time.perf_counter()
    with TraceAnnotation("bench/window"):
        loop.serve(lambda: time.perf_counter() >= t0 + seconds, t0, timeout=seconds + 60)
        t1 = time.perf_counter()
    loop.record_steps = False
    counts1 = dict(engine.trace_counts)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        path = sorted(Path(tmp.name).rglob("*.xplane.pb"))[0]
        t_tr = time.perf_counter()
        reduced = trace_lib.reduce(trace_lib.load(str(path)))
        log(f"trace of {path.stat().st_size} bytes reduced in {time.perf_counter() - t_tr:.1f} s")
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    mosaic = engine.decode_hlo().count("tpu_custom_call")
    late = sorted(loop.lateness)
    log(f"trace counts after the window {counts1}"
        f" ({'none' if counts1 == counts0 else 'SOME'} compiled inside it)")
    log(f"decode step: {mosaic} Mosaic calls (tpu_custom_call); {len(loop.steps)} steps in the window,"
        f" {sum(s.t1 - s.t0 for s in loop.steps):.3f} s in them")
    log(f"peak device memory {mem} bytes")
    if late:
        log(f"generator lateness p50 {late[len(late) // 2]:.6f} s, max {late[-1]:.6f} s over {len(late)}")

    run_ = Run(arch=arch, config=config, records=loop.records,
               t0=t0, t1=t1, setup_s=setup_s, steps=loop.steps, trace=reduced, ref=ref)
    samples = [
        {"prompt": np.asarray(r.req.prompt), "served": np.asarray(r.req.generated)}
        for r in judge.pick_samples(loop.records, int(limits["sample_tokens"]), seed)
    ]
    attempted = sum(1 for r in loop.records if r.released <= t1)
    engine.cache = None
    del engine, params, loop
    gc.collect()
    if tmp is not None:
        tmp.cleanup()
    t_ref = time.perf_counter()
    reads = judge.readings(ref, arch, raw, samples, int(e["max_len"]), control=control)
    checks = judge.checks(reads, limits)
    log(f"reference over {len(samples)} requests, {sum(r['served'] for r in reads)} tokens,"
        f" in {time.perf_counter() - t_ref:.1f} s")
    return {
        "run": run_, "checks": checks, "attempted": attempted, "failed": 0,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(mem)},
        "compiled_in_window": counts1 != counts0,
        "readings": reads,
    }
