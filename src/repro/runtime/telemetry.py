"""Process-wide observability: metrics registry + profiler spans.

One registry serves the whole serve stack — the continuous-batching
engine, the fixed-batch ``serve.py`` legs, the kernel autotuner, the
``.pvqz`` artifact codecs, and the quantization-quality probes — so a
regression in speed *or* numerics shows up as data in one place instead
of ad-hoc ``perf_counter`` prints scattered per layer.

Instruments
-----------
* :class:`Counter` — monotonically increasing value (``inc``/``add``).
* :class:`Gauge` — last-value instrument with min/max/n tracking.
* :class:`Histogram` — value distribution with **exact** percentiles:
  every recorded value is kept verbatim up to ``max_samples`` and
  ``percentile(q)`` is ``np.percentile`` over the stored values; beyond
  the cap a deterministic reservoir keeps a uniform sample and the
  snapshot flags ``exact: false``.  This is THE percentile type — the
  engine report and the benchmark latency helpers all route through it
  (no more inline ``pct`` copies).

All three are keyed by ``(name, labels)`` in the registry; labels are an
optional flat ``{str: str}`` dict (e.g. ``{"codec": "golomb"}``).

Tracing
-------
``registry.span(name, args=...)`` enters a ``jax.profiler.TraceAnnotation``
when the registry is enabled, so a span lands in the JAX profiler's own
trace, on the same clock as the device's ops, with ``args`` as its stats
(``span.set_metadata(**args)`` adds more before the span closes).  The
profiler is the only trace store: ``serve --metrics-out DIR`` runs the
serve under ``jax.profiler.trace(DIR, create_perfetto_trace=True,
profiler_options=profiler_options())``, and :func:`read_host_spans`
reads the spans back from the ``.xplane.pb`` with ``ProfileData``.
:func:`hlo_op_scopes` maps a compiled program's instructions to the
``jax.named_scope`` path in their metadata, which is how a trace's op
events (named by instruction) are put under a scope such as
``kv_page_encode``.

Hot-path contract
-----------------
A **disabled** registry is a true no-op: ``counter()``/``gauge()``/
``histogram()``/``span()`` all return the shared :data:`NOOP` singleton
and allocate nothing.  Call sites on hot loops additionally guard with
``obs.enabled()`` so not even argument dicts are built.  Nothing in
this module is ever traced into a jit body — instrumentation lives in
host-side driver loops, and the eager-only quantization probes bail out
when handed a tracer.

Export
------
* ``export_metrics_jsonl(path)`` — one JSON object per line, schema
  ``repro-metrics-v1`` (see :data:`METRICS_SCHEMA`); round-trips through
  :func:`read_metrics_jsonl` / :func:`validate_metrics_jsonl`.
* ``write(outdir)`` — ``metrics.jsonl`` into a directory (the
  ``serve --metrics-out DIR`` exit hook; the profiler writes the trace
  under ``DIR/plugins/profile/``).

``python -m repro.runtime.telemetry --validate DIR`` checks
``metrics.jsonl`` (the CI schema gate); ``--require-engine`` also reads
the profiler trace under ``DIR`` and asserts the engine spans there and
the gauges/autotune counters/quant probes the serve smoke must emit.
"""

from __future__ import annotations

import glob
import json
import os
import random
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation

METRICS_SCHEMA = "repro-metrics-v1"

#: snapshot keys every histogram line carries (the JSONL schema contract)
HISTOGRAM_FIELDS = ("count", "sum", "min", "max", "p50", "p90", "p99", "exact")


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------


class Counter:
    """Monotonic counter."""

    __slots__ = ("name", "labels", "value")

    def __init__(self, name: str, labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.labels = dict(labels or {})
        self.value = 0.0

    def inc(self, n: float = 1) -> None:
        self.value += n

    add = inc

    def snapshot(self) -> Dict[str, Any]:
        v = self.value
        return {
            "kind": "counter", "name": self.name, "labels": self.labels,
            "value": int(v) if float(v).is_integer() else v,
        }


class Gauge:
    """Last-value instrument (plus min/max/n over the run)."""

    __slots__ = ("name", "labels", "value", "min", "max", "n")

    def __init__(self, name: str, labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.labels = dict(labels or {})
        self.value: Optional[float] = None
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self.n = 0

    def set(self, v: float) -> None:
        v = float(v)
        self.value = v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        self.n += 1

    def snapshot(self) -> Dict[str, Any]:
        return {
            "kind": "gauge", "name": self.name, "labels": self.labels,
            "value": self.value, "min": self.min, "max": self.max, "n": self.n,
        }


class Histogram:
    """Distribution with exact reservoir percentiles.

    Values are stored verbatim up to ``max_samples``; past the cap a
    deterministic reservoir (seeded RNG, so runs reproduce) keeps a
    uniform sample and ``exact`` flips to False.  ``count``/``sum``/
    ``min``/``max`` stay exact regardless.
    """

    __slots__ = ("name", "labels", "max_samples", "count", "total",
                 "min", "max", "_values", "_rng")

    def __init__(
        self, name: str = "", labels: Optional[Dict[str, str]] = None,
        *, max_samples: int = 65536,
    ):
        self.name = name
        self.labels = dict(labels or {})
        self.max_samples = int(max_samples)
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self._values: List[float] = []
        self._rng = random.Random(0)

    @classmethod
    def from_values(cls, values, name: str = "") -> "Histogram":
        h = cls(name)
        for v in values:
            h.record(v)
        return h

    def record(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        if len(self._values) < self.max_samples:
            self._values.append(v)
        else:  # reservoir sampling: uniform over everything seen so far
            j = self._rng.randrange(self.count)
            if j < self.max_samples:
                self._values[j] = v

    def record_many(self, values) -> None:
        for v in values:
            self.record(v)

    @property
    def exact(self) -> bool:
        return self.count == len(self._values)

    def percentile(self, q: float) -> float:
        """Exact percentile over the stored values (0.0 when empty)."""
        if not self._values:
            return 0.0
        return float(np.percentile(np.asarray(self._values), q))

    def snapshot(self) -> Dict[str, Any]:
        return {
            "kind": "histogram", "name": self.name, "labels": self.labels,
            "count": self.count, "sum": self.total,
            "min": self.min, "max": self.max,
            "p50": self.percentile(50), "p90": self.percentile(90),
            "p99": self.percentile(99), "exact": self.exact,
        }


class _Noop:
    """Shared do-nothing instrument AND context manager returned by a
    disabled registry — one singleton, so the disabled path never
    allocates."""

    __slots__ = ()

    def inc(self, n: float = 1) -> None:
        pass

    add = inc

    def set(self, v: float) -> None:
        pass

    def record(self, v: float) -> None:
        pass

    def record_many(self, values) -> None:
        pass

    def set_metadata(self, **args) -> None:
        pass

    def __enter__(self) -> "_Noop":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NOOP = _Noop()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def _key(name: str, labels: Optional[Dict[str, str]]) -> Tuple:
    if not labels:
        return (name,)
    return (name,) + tuple(sorted(labels.items()))


class MetricsRegistry:
    """Process-wide metric store; spans go to the JAX profiler.

    ``enabled=False`` (the default for the module registry) turns every
    accessor into a :data:`NOOP` return — zero instrument allocation,
    zero recording, nothing on the decode hot path.
    """

    def __init__(self, *, enabled: bool = False):
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._counters: Dict[Tuple, Counter] = {}
        self._gauges: Dict[Tuple, Gauge] = {}
        self._histograms: Dict[Tuple, Histogram] = {}

    # ------------------------------------------------------------ lifecycle

    def clear(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()

    # ----------------------------------------------------------- instruments

    def counter(self, name: str, labels: Optional[Dict[str, str]] = None):
        if not self.enabled:
            return NOOP
        k = _key(name, labels)
        c = self._counters.get(k)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(k, Counter(name, labels))
        return c

    def gauge(self, name: str, labels: Optional[Dict[str, str]] = None):
        if not self.enabled:
            return NOOP
        k = _key(name, labels)
        g = self._gauges.get(k)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(k, Gauge(name, labels))
        return g

    def histogram(self, name: str, labels: Optional[Dict[str, str]] = None):
        if not self.enabled:
            return NOOP
        k = _key(name, labels)
        h = self._histograms.get(k)
        if h is None:
            with self._lock:
                h = self._histograms.setdefault(k, Histogram(name, labels))
        return h

    # --------------------------------------------------------------- tracing

    def span(self, name: str, args: Optional[dict] = None):
        """A ``jax.profiler.TraceAnnotation`` carrying ``args`` as its
        stats; :data:`NOOP` when disabled.  Outside a profiler session the
        annotation records nothing."""
        if not self.enabled:
            return NOOP
        return TraceAnnotation(name, **(args or {}))

    # ---------------------------------------------------------------- export

    def snapshot(self) -> List[Dict[str, Any]]:
        """All instruments as schema-stamped dicts (one JSONL line each)."""
        with self._lock:
            instruments = (
                list(self._counters.values())
                + list(self._gauges.values())
                + list(self._histograms.values())
            )
        out = []
        for inst in instruments:
            rec = {"schema": METRICS_SCHEMA}
            rec.update(inst.snapshot())
            out.append(rec)
        return out

    def export_metrics_jsonl(self, path: str) -> str:
        with open(path, "w") as f:
            for rec in self.snapshot():
                f.write(json.dumps(rec) + "\n")
        return str(path)

    def write(self, outdir: str) -> Dict[str, str]:
        """Write ``metrics.jsonl`` into ``outdir``."""
        os.makedirs(outdir, exist_ok=True)
        return {
            "metrics": self.export_metrics_jsonl(
                os.path.join(outdir, "metrics.jsonl")
            ),
        }


# ---------------------------------------------------------------------------
# module registry (the `obs` facade delegates here)
# ---------------------------------------------------------------------------

_REGISTRY = MetricsRegistry(enabled=False)


def get_registry() -> MetricsRegistry:
    return _REGISTRY


def set_enabled(on: bool) -> bool:
    """Flip the module registry; returns the previous state."""
    prev = _REGISTRY.enabled
    _REGISTRY.enabled = bool(on)
    return prev


# ---------------------------------------------------------------------------
# shared helpers (benchmarks, probes)
# ---------------------------------------------------------------------------


def time_call_us(fn: Callable[[], Any], reps: int = 5) -> float:
    """us/call of a jax-producing thunk: one warmup call (trace + compile
    outside the timed region), then ``reps`` timed calls with a final
    ``block_until_ready``.  The shared timing helper the benchmark files
    use instead of hand-rolled copies."""
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return 1e6 * (time.perf_counter() - t0) / reps


def snr_db(ref: np.ndarray, approx: np.ndarray, cap: float = 99.0) -> float:
    """Reconstruction signal-to-noise ratio in dB (capped for exact hits)."""
    ref = np.asarray(ref, np.float64).ravel()
    err = np.asarray(approx, np.float64).ravel() - ref
    sig = float(np.sum(ref * ref))
    noise = float(np.sum(err * err))
    if noise <= 0.0:
        return cap
    if sig <= 0.0:
        return 0.0
    return min(10.0 * np.log10(sig / noise), cap)


def bench_payload(schema: str, rows: List[dict], *, backend: Optional[str] = None) -> dict:
    """The one BENCH_*.json wrapper every benchmark file shares."""
    if backend is None:
        try:
            import jax

            backend = jax.default_backend()
        except Exception:
            backend = "unknown"
    return {"schema": schema, "backend": backend, "rows": rows}


# ---------------------------------------------------------------------------
# validation (tests + the CI schema gate)
# ---------------------------------------------------------------------------


def read_metrics_jsonl(path: str) -> List[Dict[str, Any]]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def validate_metrics_jsonl(path: str) -> List[Dict[str, Any]]:
    """Schema-check a metrics JSONL file; returns the records or raises."""
    recs = read_metrics_jsonl(path)
    for i, rec in enumerate(recs):
        where = f"{path}:{i + 1}"
        if rec.get("schema") != METRICS_SCHEMA:
            raise ValueError(f"{where}: bad schema {rec.get('schema')!r}")
        kind = rec.get("kind")
        if kind not in ("counter", "gauge", "histogram"):
            raise ValueError(f"{where}: bad kind {kind!r}")
        if not isinstance(rec.get("name"), str) or not rec["name"]:
            raise ValueError(f"{where}: missing metric name")
        if not isinstance(rec.get("labels"), dict):
            raise ValueError(f"{where}: labels must be a dict")
        if kind == "counter" and not isinstance(rec.get("value"), (int, float)):
            raise ValueError(f"{where}: counter needs a numeric value")
        if kind == "histogram":
            for field in HISTOGRAM_FIELDS:
                if field not in rec:
                    raise ValueError(f"{where}: histogram missing {field!r}")
    return recs


# ---------------------------------------------------------------------------
# the profiler trace
# ---------------------------------------------------------------------------


def profiler_options() -> ProfileOptions:
    """Device ops and host annotations only: no Python call tracing, no
    runtime events and no HLO protos, which would swell the trace
    manyfold and slow the host."""
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def find_profile(path: str) -> str:
    """The newest ``.xplane.pb`` under ``path`` (or ``path`` itself)."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise ValueError(f"{path}: no profiler trace (*.xplane.pb)")
    return max(found, key=os.path.getmtime)


def read_host_spans(path: str, prefix: str = "engine/") -> List[Dict[str, Any]]:
    """Host annotations named ``prefix...`` in a profiler trace, by start:
    ``{"name", "start", "dur", "args"}`` with times in ns on the trace's
    clock (the device ops' clock) and the span's args as ``args``."""
    out = []
    for plane in ProfileData.from_file(find_profile(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append({
                        "name": ev.name, "start": float(ev.start_ns),
                        "dur": float(ev.duration_ns), "args": dict(ev.stats),
                    })
    return sorted(out, key=lambda e: e["start"])


_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')


def hlo_op_scopes(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata (``jit(f)/scope/.../op``)
    in a compiled program's HLO text (``Compiled.as_text()``).  A device
    trace names each op by its instruction, so this puts trace events
    under the ``jax.named_scope`` that made them."""
    out = {}
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def in_scope(op_name: str, scope: str) -> bool:
    """Whether an ``op_name`` path lies under ``scope``: one of its parts is
    the scope, or the scope under a transform (``vmap(kv_page_encode)``)."""
    return any(
        part == scope or part.endswith(f"({scope})") for part in op_name.split("/")
    )


#: what the engine serve smoke must cover (``--require-engine``): engine
#: spans in the profiler trace; page-pool/queue gauges, autotune counters
#: and quant-quality probes in metrics.jsonl
ENGINE_REQUIRED_SPANS = (
    "engine/admit", "engine/prefill", "engine/graft", "engine/decode_step",
    "engine/decode/prepare", "engine/decode/launch", "engine/decode/wait",
    "engine/decode/commit",
)
ENGINE_REQUIRED_METRICS = (
    "engine.page_pool_free", "engine.queue_depth",
    "autotune.lookups", "quant.weight_snr_db", "quant.kv_snr_db",
)


def validate_dir(outdir: str, *, require_engine: bool = False) -> Dict[str, int]:
    """Validate ``metrics.jsonl`` in ``outdir``; with ``require_engine``,
    also require the engine serve-smoke metrics there and the engine spans
    in the profiler trace under ``outdir``."""
    recs = validate_metrics_jsonl(os.path.join(outdir, "metrics.jsonl"))
    if not require_engine:
        return {"metrics": len(recs)}
    spans = read_host_spans(outdir)
    names = {r["name"] for r in recs}
    missing = [m for m in ENGINE_REQUIRED_METRICS if m not in names]
    span_names = {e["name"] for e in spans}
    missing += [s for s in ENGINE_REQUIRED_SPANS if s not in span_names]
    if missing:
        raise ValueError(f"{outdir}: engine telemetry incomplete, missing {missing}")
    return {"metrics": len(recs), "engine_spans": len(spans)}


def _main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description="validate telemetry exports")
    ap.add_argument("--validate", metavar="DIR", required=True,
                    help="directory holding metrics.jsonl and the profiler "
                    "trace (serve --metrics-out DIR)")
    ap.add_argument("--require-engine", action="store_true",
                    help="additionally require the engine serve-smoke "
                    "spans (in the profiler trace) and metrics")
    args = ap.parse_args()
    counts = validate_dir(args.validate, require_engine=args.require_engine)
    print(json.dumps({"ok": True, "dir": args.validate, **counts}))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
