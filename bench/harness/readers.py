"""Arithmetic shared by the metric readers in ``bench/metrics``.  Each
reader keeps the names it matches (programs, kernels) in its own file and
returns None where the run holds nothing to read."""

from __future__ import annotations

import re
from typing import Optional


def tokens_in_window(run) -> int:
    return sum(1 for r in run.records for t in r.times if run.in_window(t))


def module_ms(run, module: str) -> Optional[float]:
    """Mean device time of one execution of ``module`` in the trace."""
    if run.trace is None:
        return None
    m = run.trace["modules"].get(module)
    if not m or m["calls"] <= 0:
        return None
    return m["ns"] / m["calls"] / 1e6


def ops_ns(run, module: str, pattern: str) -> float:
    """Device ns of the ops inside ``module`` whose name, or any text stat
    of theirs, matches ``pattern``."""
    if run.trace is None:
        return 0.0
    rx = re.compile(pattern)
    total = 0.0
    for name, ns in run.trace["ops"].get(module, {}).items():
        stats = run.trace["op_stats"].get(name, {})
        if rx.search(name) or any(isinstance(v, str) and rx.search(v) for v in stats.values()):
            total += ns
    return total


def idle_share(run) -> Optional[float]:
    if run.trace is None or not run.trace["devices"] or run.trace["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_ns"] / run.trace["window_ns"])
