"""Observability layer tests: instrument semantics (counters, gauges,
exact-reservoir histogram percentiles), the disabled registry's true-no-op
contract (NOOP identity + zero allocations in the engine decode-step guard
pattern), spans as profiler annotations (their args in the profiler's
trace and its Chrome-format perfetto file), the scope map of a compiled
program, metrics-JSONL schema round-trip and the validator, trace-count
metric parity with the ``TRACE_COUNTS`` compile regressions, autotune
hit/miss lookup counters, and the quant-quality probes' eager-only
(never-inside-jit) behavior."""

import gc
import glob
import gzip
import json
import os
import subprocess
import sys
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import obs, telemetry
from repro.runtime.telemetry import (
    ENGINE_REQUIRED_METRICS,
    ENGINE_REQUIRED_SPANS,
    HISTOGRAM_FIELDS,
    METRICS_SCHEMA,
    Histogram,
    MetricsRegistry,
    hlo_op_scopes,
    profiler_options,
    read_host_spans,
    snr_db,
    validate_dir,
    validate_metrics_jsonl,
)


@pytest.fixture()
def enabled_registry():
    """Flip the module registry on for one test, restore + clear after."""
    prev = obs.set_enabled(True)
    obs.registry().clear()
    yield obs.registry()
    obs.set_enabled(prev)
    obs.registry().clear()


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------


def test_histogram_exact_percentiles_match_numpy():
    vals = list(np.random.default_rng(0).normal(size=513))
    h = Histogram.from_values(vals, name="x")
    assert h.exact
    assert h.count == len(vals)
    assert h.total == pytest.approx(sum(vals))
    assert h.min == min(vals) and h.max == max(vals)
    for q in (50, 90, 99):
        assert h.percentile(q) == pytest.approx(float(np.percentile(vals, q)))
    snap = h.snapshot()
    assert snap["kind"] == "histogram"
    for field in HISTOGRAM_FIELDS:
        assert field in snap


def test_histogram_reservoir_caps_storage_keeps_exact_aggregates():
    h = Histogram("y", max_samples=128)
    vals = list(range(1000))
    h.record_many(vals)
    assert not h.exact  # past the cap: percentiles become sampled
    assert len(h._values) == 128
    assert h.count == 1000  # ...but count/sum/min/max stay exact
    assert h.total == sum(vals)
    assert h.min == 0 and h.max == 999
    # reservoir keeps a uniform sample: p50 should be roughly central
    assert 250 < h.percentile(50) < 750
    # deterministic: same inputs reproduce the same reservoir
    h2 = Histogram("y", max_samples=128)
    h2.record_many(vals)
    assert h._values == h2._values


def test_histogram_empty_percentile_is_zero():
    assert Histogram("z").percentile(99) == 0.0


def test_counter_gauge_labels_and_snapshot():
    reg = MetricsRegistry(enabled=True)
    reg.counter("hits").inc()
    reg.counter("hits").inc(2)
    reg.counter("hits", {"codec": "zlib"}).inc()  # distinct label set
    reg.gauge("depth").set(3)
    reg.gauge("depth").set(1)
    snaps = {((r["name"],) + tuple(sorted(r["labels"].items()))): r
             for r in reg.snapshot()}
    assert snaps[("hits",)]["value"] == 3
    assert snaps[("hits", ("codec", "zlib"))]["value"] == 1
    g = snaps[("depth",)]
    assert g["value"] == 1 and g["min"] == 1 and g["max"] == 3 and g["n"] == 2
    assert all(r["schema"] == METRICS_SCHEMA for r in snaps.values())


def test_snr_db():
    x = np.ones(64)
    assert snr_db(x, x) == 99.0  # exact reconstruction hits the cap
    assert snr_db(x, x * 0.9) == pytest.approx(20.0)
    assert snr_db(np.zeros(4), np.ones(4)) == 0.0


# ---------------------------------------------------------------------------
# disabled registry: a true no-op
# ---------------------------------------------------------------------------


def test_disabled_registry_returns_noop_singleton():
    reg = MetricsRegistry(enabled=False)
    assert reg.counter("a") is telemetry.NOOP
    assert reg.gauge("b") is telemetry.NOOP
    assert reg.histogram("c") is telemetry.NOOP
    assert reg.span("d") is telemetry.NOOP
    with reg.span("d", args={"a": 1}) as sp:  # NOOP doubles as a context manager
        sp.set_metadata(b=2)
    assert reg.snapshot() == []


def test_disabled_decode_step_guard_pattern_allocates_nothing():
    """The exact instrumentation shape PVQEngine.step uses: when the
    registry is disabled, repeated steps must not accumulate memory (no
    instruments, no events, no per-step garbage retained)."""
    assert not obs.enabled()

    def step_hook():
        span = obs.span("engine/decode_step")
        with span:
            with obs.span("engine/decode/prepare"):
                pass
            if obs.enabled():
                span.set_metadata(active=1, n_slots=2)
            with obs.span("engine/decode/launch"):
                pass
            with obs.span("engine/decode/wait"):
                pass
            with obs.span("engine/decode/commit"):
                if obs.enabled():
                    obs.gauge("engine.queue_depth").set(0)

    step_hook()  # warm any lazy import/attribute state
    gc.collect()
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(5000):
        step_hook()
    gc.collect()
    grown = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()
    # even one retained object per step would be tens of KB over 5000 steps
    assert grown < 2048, f"disabled telemetry retained {grown} bytes"


# ---------------------------------------------------------------------------
# export round-trips
# ---------------------------------------------------------------------------


def _profile_spans(outdir, reg):
    """Spans of an enabled registry written under ``outdir`` by the JAX
    profiler, as ``serve --metrics-out`` runs it."""
    with jax.profiler.trace(str(outdir), create_perfetto_trace=True,
                            profiler_options=profiler_options()):
        with reg.span("engine/decode_step", args={"active": 2}) as step:
            with reg.span("engine/decode/wait"):
                jnp.ones(8).block_until_ready()
            step.set_metadata(pages_completed=1)


def test_chrome_trace_well_formed(tmp_path):
    """Spans are profiler annotations: the perfetto file the profiler
    writes is Chrome trace-event JSON holding them, args included, and
    the ``.xplane.pb`` beside it holds them on the device ops' clock."""
    reg = MetricsRegistry(enabled=True)
    _profile_spans(tmp_path, reg)
    [path] = glob.glob(str(tmp_path / "**" / "perfetto_trace.json.gz"), recursive=True)
    with gzip.open(path, "rt") as f:
        doc = json.load(f)  # plain JSON, perfetto-loadable
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    by_name = {e.get("name"): e for e in events}
    step = by_name["engine/decode_step"]
    assert step["ph"] == "X" and step["dur"] >= 0
    assert {"active", "pages_completed"} <= set(step.get("args", {}))
    spans = read_host_spans(str(tmp_path))
    assert [s["name"] for s in spans] == ["engine/decode_step", "engine/decode/wait"]
    outer, inner = spans
    assert outer["args"] == {"active": 2, "pages_completed": 1}
    assert outer["start"] <= inner["start"]
    assert inner["start"] + inner["dur"] <= outer["start"] + outer["dur"]
    assert reg.snapshot() == []  # spans are not registry instruments


def test_metrics_jsonl_schema_round_trip(tmp_path):
    reg = MetricsRegistry(enabled=True)
    reg.counter("engine.decode_steps").inc(14)
    reg.gauge("engine.page_pool_free").set(9)
    reg.histogram("engine.request_latency_s").record_many([0.1, 0.2, 0.4])
    files = reg.write(str(tmp_path))
    recs = validate_metrics_jsonl(files["metrics"])
    by_name = {r["name"]: r for r in recs}
    assert by_name["engine.decode_steps"]["value"] == 14
    assert by_name["engine.page_pool_free"]["value"] == 9.0
    hist = by_name["engine.request_latency_s"]
    assert hist["count"] == 3 and hist["exact"] is True
    assert hist["p50"] == pytest.approx(0.2)
    assert validate_dir(str(tmp_path)) == {"metrics": 3}


def _engine_metrics(reg):
    for name in ENGINE_REQUIRED_METRICS:
        reg.counter(name).inc()


def test_validators_reject_malformed(tmp_path):
    bad_metrics = tmp_path / "metrics.jsonl"
    bad_metrics.write_text(json.dumps({"schema": "wrong", "kind": "counter",
                                       "name": "x", "labels": {}, "value": 1}) + "\n")
    with pytest.raises(ValueError, match="bad schema"):
        validate_metrics_jsonl(str(bad_metrics))
    reg = MetricsRegistry(enabled=True)
    _engine_metrics(reg)
    reg.write(str(tmp_path))
    with pytest.raises(ValueError, match="no profiler trace"):
        validate_dir(str(tmp_path), require_engine=True)
    _profile_spans(tmp_path, reg)  # a trace without the admission/prefill spans
    with pytest.raises(ValueError, match="engine/admit"):
        validate_dir(str(tmp_path), require_engine=True)


def test_validate_cli(tmp_path):
    reg = MetricsRegistry(enabled=True)
    _engine_metrics(reg)
    reg.write(str(tmp_path))
    with jax.profiler.trace(str(tmp_path), profiler_options=profiler_options()):
        for name in ENGINE_REQUIRED_SPANS:
            with reg.span(name):
                pass
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.getcwd(), "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.runtime.telemetry", "--validate", str(tmp_path),
         "--require-engine"],
        capture_output=True, text=True, env=env,
    )
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    assert out["ok"] is True
    assert out["metrics"] == len(ENGINE_REQUIRED_METRICS)
    assert out["engine_spans"] == len(ENGINE_REQUIRED_SPANS)


def test_serve_metrics_out_writes_metrics_and_a_profiler_trace(tmp_path):
    """``serve --metrics-out DIR`` on the engine path: metrics.jsonl and a
    profiler trace (with its perfetto file) that ``--validate DIR
    --require-engine`` accepts."""
    out = tmp_path / "obs"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.getcwd(), "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "smollm-360m", "--reduced",
         "--prompt-len", "12", "--gen", "4", "--engine", "--engine-slots", "2",
         "--requests", "3", "--rate", "0", "--pvq", "--act-int8", "--kv-pvq",
         "--kv-block", "8", "--kv-group", "16", "--metrics-out", str(out)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert glob.glob(str(out / "**" / "perfetto_trace.json.gz"), recursive=True)
    counts = validate_dir(str(out), require_engine=True)
    assert counts["engine_spans"] > len(ENGINE_REQUIRED_SPANS)
    recs = {r["name"]: r for r in validate_metrics_jsonl(str(out / "metrics.jsonl"))}
    steps = [s for s in read_host_spans(str(out)) if s["name"] == "engine/decode_step"]
    assert recs["engine.decode_steps"]["value"] == len(steps)
    assert recs["engine.admissions"]["value"] == 3


def test_hlo_op_scopes_of_a_compiled_program():
    """A named scope reaches the compiled instructions' metadata, and the
    map puts the conditional and the ops of its branch under it."""
    def f(x, p):
        with jax.named_scope("kv_page_encode"):
            y = jax.lax.cond(p, lambda z: z + jnp.sin(z), lambda z: z, x)
        return y * 3

    x = jnp.ones((8,), jnp.float32)
    text = jax.jit(f).lower(x, True).compile().as_text()
    scopes = hlo_op_scopes(text)
    conds = [n for n in scopes if n.startswith("cond")]
    assert conds and all(scopes[n].endswith("kv_page_encode/cond") for n in conds)
    assert any("kv_page_encode/cond/branch_1_fun/" in v for v in scopes.values())
    assert not any("kv_page_encode" in v for n, v in scopes.items() if n.startswith("multiply"))


# ---------------------------------------------------------------------------
# trace-count metric parity with TRACE_COUNTS
# ---------------------------------------------------------------------------


def test_decode_step_trace_counter_parity(enabled_registry):
    """The ``serve.decode_step_traces`` metric moves in lockstep with the
    ``TRACE_COUNTS['decode_step']`` regression counter: +1 per fresh
    compile, +0 on cache hits (same shapes), +1 again on a new batch
    shape — same contract test_engine's compile-count regressions pin."""
    from repro.launch import serve

    class _Toy:
        def decode_step(self, params, cache, tok, pos):
            del pos
            logits = jnp.zeros((tok.shape[0], 1, 8), jnp.float32) + params
            return logits, cache

    step = serve._jit_step(_Toy())
    params = jnp.float32(1.0)
    cache = jnp.zeros((1,), jnp.float32)
    before = serve.TRACE_COUNTS["decode_step"]

    step(params, cache, jnp.zeros((1, 1), jnp.int32), jnp.int32(0))
    step(params, cache, jnp.zeros((1, 1), jnp.int32), jnp.int32(1))  # cache hit
    step(params, cache, jnp.zeros((2, 1), jnp.int32), jnp.int32(0))  # new shape

    delta = serve.TRACE_COUNTS["decode_step"] - before
    assert delta == 2
    assert obs.counter("serve.decode_step_traces").value == delta


# ---------------------------------------------------------------------------
# autotune lookup counters
# ---------------------------------------------------------------------------


def test_autotune_hit_miss_counters(tmp_path, monkeypatch, enabled_registry):
    from repro.kernels import autotune

    backend = jax.default_backend()
    key = autotune.cache_key(8, 64, 32, 32, jnp.float32, backend)
    cache_file = tmp_path / "tune.json"
    cache_file.write_text(json.dumps({key: {"bm": 8, "bn": 32, "bk": 32, "us": 1.0}}))
    monkeypatch.setenv("REPRO_PVQ_TUNE_CACHE", str(cache_file))
    monkeypatch.delenv("REPRO_PVQ_AUTOTUNE", raising=False)
    autotune.clear_memory_cache()
    autotune.reset_tune_stats()
    try:
        assert autotune.get_tiles(8, 64, 32, group=32, search=False) == (8, 32, 32)
        autotune.get_tiles(8, 128, 32, group=32, search=False)  # miss -> heuristic
        st = autotune.tune_stats()
        assert st["hits"] == 1 and st["misses"] == 1 and st["searches"] == 0
        assert st["by_key"][key]["hits"] == 1
        assert obs.counter("autotune.hit").value == 1
        assert obs.counter("autotune.miss").value == 1
        assert obs.counter("autotune.lookups").value == 2
    finally:
        autotune.clear_memory_cache()
        autotune.reset_tune_stats()


# ---------------------------------------------------------------------------
# quant-quality probes: eager-only, never inside jit traces
# ---------------------------------------------------------------------------


def test_act_quant_probe_eager_only(enabled_registry):
    from repro.core.quantize import ActQuant, quantize_activations

    aq = ActQuant()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 32)), jnp.float32)
    quantize_activations(x, aq)
    assert obs.counter("quant.act_quant_calls").value == 1
    assert obs.registry().histogram("quant.act_clamp_frac").count == 1

    jitted = jax.jit(lambda y: quantize_activations(y, aq)[0])
    jitted(x)
    jitted(x)  # tracer path: the probe must stay silent
    assert obs.counter("quant.act_quant_calls").value == 1


def test_weight_pack_probe_records_snr(enabled_registry):
    from repro.core.packed import quantize_params
    from repro.core.quantize import QuantPolicy

    w = jnp.asarray(np.random.default_rng(2).normal(size=(8, 32)), jnp.float32)
    policy = QuantPolicy(rules=(("embedding", 1.0, 16),), scale_mode="ls")
    quantize_params({"embedding": w}, policy)
    assert obs.counter("quant.weight_leaves_packed").value == 1
    h = obs.registry().histogram("quant.weight_snr_db")
    assert h.count == 1
    assert h.percentile(50) > 0.0  # reconstruction beats zero-signal
    assert obs.counter("quant.weight_bytes_packed").value < \
        obs.counter("quant.weight_bytes_dense").value
