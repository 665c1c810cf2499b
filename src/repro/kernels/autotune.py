"""Autotuner for the PVQ Pallas kernels: matmul tiles + encoder knobs.

``pvq_matmul`` takes (bm, bn, bk) tile sizes; the best choice depends on the
GEMM shape (an m=8 decode step wants a skinny bm, a 236B-config FFN block
wants full MXU 128x128 tiles), the dtype, and the backend.  This module
searches a small MXU/VPU-aligned candidate grid, times each candidate with
``block_until_ready``, and persists the winner in a JSON cache so the search
runs once per (shape, dtype, backend) — ever.  ``pvq_encode``'s
(bg, delta_max) knobs go through the same cache (ROADMAP "autotune the
encoder too"): ``get_encode_params`` mirrors ``get_tiles`` dispatch.

Cache
-----
* location: ``$REPRO_PVQ_TUNE_CACHE`` if set, else
  ``<repo>/.pvq_tune_cache.json`` (``repro.runtime.caches``: inside the
  checkout, next to the compile cache)
* matmul key: ``"m x k x n : g<group> : <dtype> : <backend> : kv<N> : v3"``
  (no spaces) — ``kv<N>`` is ``pvq_matmul.KERNEL_VERSION``, so a material
  kernel body change (e.g. the v3 quantized-activation contraction)
  invalidates every tile timing measured against the old body instead of
  silently serving it.  ``<dtype>`` is the *activation* dtype: ``int8`` keys
  time the int8 x int8 kernel v3 body (``launch/serve.py --tune --act-int8``
  pre-tunes them), float keys the f32-activation body.
* matmul value: ``{"bm":…, "bn":…, "bk":…, "us":…, "candidates":…}``
* attention key (kernel v4 decode): ``"attn m x hd x s : g<group> : <dtype>
  : <backend> : kv<N> : v3"`` with value ``{"bs":…, "us":…, "candidates":…}``
  — the v3->v4 ``KERNEL_VERSION`` bump means every entry tuned against the
  pre-attention kernel body misses for v4 dispatch.
* encoder key: ``"enc g x n : k<K> : <dtype> : <backend> : ekv<N> : v2"``
  with ``ekv<N>`` = ``pvq_encode.ENCODE_KERNEL_VERSION``; value
  ``{"bg":…, "delta_max":…, "us":…, "candidates":…}``.  ``delta_max``
  candidates never drop below the heuristic default — tuning may only make
  the encoder *more* exact, never less.

Dispatch contract (used by ``kernels.ops.pvq_matmul``):

* explicit tiles from the caller always win;
* else a cache hit wins (never re-times);
* else, if searching is enabled (``search=True`` or ``REPRO_PVQ_AUTOTUNE=1``),
  run the search and persist;
* else fall back to :func:`heuristic_tiles` (no timing, no I/O).

Delete the cache file (or point the env var somewhere fresh) to regenerate —
see ``src/repro/kernels/README.md``.
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
import time
from pathlib import Path
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from .pvq_encode import ENCODE_KERNEL_VERSION, pvq_encode_batch
from .pvq_matmul import (
    KERNEL_VERSION,
    normalize_tiles,
    pvq_attn_q,
    pvq_matmul,
    pvq_matmul_q,
)

# v2: keys carry the kernel-body version tag (ROADMAP "tuned-tile
# invalidation") — entries tuned against an older kernel body miss.
# v3: the activation dtype in the key is now load-bearing (int8 keys time
# the quantized-activation kernel v3 body, float keys the f32-act v2 body),
# so the schema bump guarantees v2-era tiles can never collide with v3
# dispatch even for entries whose kv tag a hand-edited cache got wrong.
_SCHEMA = "v3"
# process-local mirror of the JSON file: avoids re-reading per dispatch
_MEM: Dict[str, dict] = {}
_MEM_LOADED_FROM: Optional[str] = None

# keep the interpret-mode (CPU proxy) search cheap; Mosaic search can afford
# a wider sweep since compile+run is milliseconds per candidate
MAX_CANDIDATES_INTERPRET = 6
MAX_CANDIDATES_COMPILED = 24
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024  # leave headroom below the ~16MB/core


def cache_path() -> Path:
    env = os.environ.get("REPRO_PVQ_TUNE_CACHE", "").strip()
    if env:
        return Path(env)
    from repro.runtime.caches import TUNE_CACHE_FILE

    return TUNE_CACHE_FILE


def cache_key(m: int, k: int, n: int, group: int, dtype, backend: str) -> str:
    return (
        f"{m}x{k}x{n}:g{group}:{jnp.dtype(dtype).name}:{backend}"
        f":kv{KERNEL_VERSION}:{_SCHEMA}"
    )


def _load() -> Dict[str, dict]:
    """Read-through memory cache of the JSON file."""
    global _MEM, _MEM_LOADED_FROM
    path = cache_path()
    if _MEM_LOADED_FROM == str(path):
        return _MEM
    entries: Dict[str, dict] = {}
    try:
        with open(path) as f:
            raw = json.load(f)
        if isinstance(raw, dict):
            entries = {k: v for k, v in raw.items() if isinstance(v, dict)}
    except (OSError, json.JSONDecodeError):
        entries = {}
    _MEM = entries
    _MEM_LOADED_FROM = str(path)
    return _MEM


def _persist(key: str, entry: dict) -> None:
    """Read-modify-write with an atomic replace (tuning may run concurrently)."""
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    current: Dict[str, dict] = {}
    try:
        with open(path) as f:
            current = json.load(f)
        if not isinstance(current, dict):
            current = {}
    except (OSError, json.JSONDecodeError):
        current = {}
    current[key] = entry
    fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(current, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
    _MEM.update({key: entry})
    global _MEM_LOADED_FROM
    _MEM_LOADED_FROM = str(path)


def clear_memory_cache() -> None:
    """Forget the in-process mirror (tests point REPRO_PVQ_TUNE_CACHE around)."""
    global _MEM, _MEM_LOADED_FROM
    _MEM = {}
    _MEM_LOADED_FROM = None


# ---------------------------------------------------------------------------
# tuning observability: hit/miss/search counts + search wall-time, per key
# ---------------------------------------------------------------------------

_LOG = logging.getLogger("repro.autotune")
_PLURAL = {"hit": "hits", "miss": "misses", "search": "searches"}


def _fresh_stats() -> Dict[str, object]:
    return {"hits": 0, "misses": 0, "searches": 0, "search_s": 0.0, "by_key": {}}


_TUNE_STATS: Dict[str, object] = _fresh_stats()


def tune_stats() -> Dict[str, object]:
    """Copy of the process tuning stats: total/per-key hit, miss, and
    completed-search counts plus accumulated search wall-time (seconds)."""
    out = dict(_TUNE_STATS)
    out["search_s"] = round(float(out["search_s"]), 4)
    out["by_key"] = {k: dict(v) for k, v in _TUNE_STATS["by_key"].items()}
    return out


def reset_tune_stats() -> None:
    global _TUNE_STATS
    _TUNE_STATS = _fresh_stats()


def _note(key: str, outcome: str, search_s: float = 0.0) -> None:
    """Record one cache lookup outcome (``hit``/``miss``) or completed
    ``search``; logs it and mirrors into the telemetry registry.  Runs on
    dispatch paths that execute at trace time — host-side only, cheap."""
    word = _PLURAL[outcome]
    _TUNE_STATS[word] += 1
    if search_s:
        _TUNE_STATS["search_s"] += search_s
    per = _TUNE_STATS["by_key"].setdefault(
        key, {"hits": 0, "misses": 0, "searches": 0}
    )
    per[word] += 1
    if outcome == "search":
        _LOG.info("search done for %s in %.3fs", key, search_s)
    else:
        _LOG.debug("cache %s: %s", outcome, key)

    from repro.runtime import obs

    if obs.enabled():
        if outcome != "search":
            obs.counter("autotune.lookups").inc()
        obs.counter(f"autotune.{outcome}").inc()
        if outcome == "search":
            obs.histogram("autotune.search_s").record(search_s)


def heuristic_tiles(m: int, k: int, n: int, group: int) -> Tuple[int, int, int]:
    """Static MXU-aligned guess: full 128 tiles clamped to the problem, with a
    deeper bk when the k extent dwarfs the MXU (fewer grid steps, same VMEM
    order) and a skinny bm for decode-like m; ``normalize_tiles`` then
    widens bk to a block Mosaic accepts."""
    bk = 128 if k <= 1024 else 256
    return normalize_tiles(m, k, n, group, bm=128, bn=128, bk=bk)


def candidate_tiles(
    m: int, k: int, n: int, group: int, max_candidates: int
) -> Tuple[Tuple[int, int, int], ...]:
    """MXU/VPU-aligned (bm, bn, bk) grid, deduped after clamping to the shape.

    bm sweeps sublane-aligned powers of two (8..256) — decode steps live at
    the small end; bn sweeps lane multiples (128..512); bk sweeps group
    multiples (group..512), which ``normalize_tiles`` widens to the blocks
    Mosaic accepts.  Candidates whose VMEM working set exceeds the
    budget are dropped.  The heuristic default is always candidate #0 so a
    truncated search can never be worse than no search.
    """
    cands: list[Tuple[int, int, int]] = [heuristic_tiles(m, k, n, group)]
    for bm in (8, 16, 32, 64, 128, 256):
        for bn in (128, 256, 512):
            for bk in (group, 2 * group, 4 * group, 128, 256, 512):
                t = normalize_tiles(m, k, n, group, bm, bn, bk)
                bm_, bn_, bk_ = t
                vmem = (
                    bm_ * bk_ * 4  # x tile f32
                    + bk_ * bn_  # int8 pulses
                    + (bk_ // group) * bn_ * 4  # scales
                    + 2 * bm_ * bn_ * 4  # out + acc
                )
                if vmem > _VMEM_BUDGET_BYTES:
                    continue
                if t not in cands:
                    cands.append(t)
    return tuple(cands[:max_candidates])


def _time_candidate(
    x, w, s, group: int, tiles: Tuple[int, int, int], reps: int, interpret: bool,
    act_scale=None,
) -> float:
    bm, bn, bk = tiles
    if act_scale is not None:
        # int8 activation dtype: time the quantized-activation kernel v3
        # body — the body these tiles will actually dispatch to
        def call():
            return pvq_matmul_q(
                x, w, s, act_scale, group=group, bm=bm, bn=bn, bk=bk,
                interpret=interpret,
            )
    else:
        def call():
            return pvq_matmul(
                x, w, s, group=group, bm=bm, bn=bn, bk=bk, interpret=interpret
            )
    call().block_until_ready()  # warmup: trace + compile outside the timed region
    t0 = time.perf_counter()
    for _ in range(reps):
        call().block_until_ready()
    return (time.perf_counter() - t0) / reps


def autotune(
    m: int,
    k: int,
    n: int,
    *,
    group: int = 128,
    dtype=jnp.float32,
    reps: int = 3,
    interpret: Optional[bool] = None,
    max_candidates: Optional[int] = None,
) -> dict:
    """Search the candidate grid for (m,k,n,group,dtype); persist + return the
    winning entry ``{"bm","bn","bk","us","candidates"}``.  A cache hit skips
    the search entirely."""
    backend = jax.default_backend()
    if interpret is None:
        interpret = backend != "tpu"
    key = cache_key(m, k, n, group, dtype, backend)
    hit = _load().get(key)
    if hit is not None:
        _note(key, "hit")
        return hit
    _note(key, "miss")
    t_search = time.perf_counter()

    if max_candidates is None:
        max_candidates = (
            MAX_CANDIDATES_INTERPRET if interpret else MAX_CANDIDATES_COMPILED
        )
    cands = candidate_tiles(m, k, n, group, max_candidates)

    kx, kw, ks = jax.random.split(jax.random.PRNGKey(0), 3)
    act_scale = None
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        # int8 activation key: quantized operands + per-row scales (v3 body)
        x = jax.random.randint(kx, (m, k), -127, 128, jnp.int8)
        act_scale = jnp.full((m, 1), 0.01, jnp.float32)
    else:
        x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
    w = jax.random.randint(kw, (k, n), -3, 4, jnp.int8)
    s = (jnp.abs(jax.random.normal(ks, (k // group, n))) * 0.05).astype(jnp.float32)

    best: Optional[Tuple[int, int, int]] = None
    best_t = float("inf")
    for t in cands:
        dt = _time_candidate(x, w, s, group, t, reps, interpret, act_scale=act_scale)
        if dt < best_t:
            best, best_t = t, dt
    assert best is not None
    entry = {
        "bm": best[0],
        "bn": best[1],
        "bk": best[2],
        "us": round(1e6 * best_t, 2),
        "candidates": len(cands),
    }
    _persist(key, entry)
    _note(key, "search", time.perf_counter() - t_search)
    return entry


# ---------------------------------------------------------------------------
# encoder autotune: pvq_encode's (bg, delta_max) knobs (ROADMAP satellite)
# ---------------------------------------------------------------------------

#: heuristic defaults (the kernel's historical hardcoded values)
ENCODE_DEFAULTS: Tuple[int, int] = (8, 32)
#: bg sweeps the VMEM sublane-tile height; delta_max never drops below the
#: default so a tuned encoder is at least as accurate as an untuned one
#: (delta_max bounds the exact-greedy correction, i.e. output quality).
ENCODE_BG_CANDIDATES = (4, 8, 16, 32)
ENCODE_DELTA_CANDIDATES = (32, 64)
MAX_ENCODE_CANDIDATES_INTERPRET = 4
MAX_ENCODE_CANDIDATES_COMPILED = 8


def encode_cache_key(g: int, n: int, k_pulses: int, dtype, backend: str) -> str:
    """Same store/schema as the matmul tiles; ``ekv<N>`` tags the encoder
    kernel body so a version bump invalidates stale (bg, delta_max) timings."""
    return (
        f"enc{g}x{n}:k{k_pulses}:{jnp.dtype(dtype).name}:{backend}"
        f":ekv{ENCODE_KERNEL_VERSION}:{_SCHEMA}"
    )


def encode_candidates(
    g: int, n: int, max_candidates: int
) -> Tuple[Tuple[int, int], ...]:
    """(bg, delta_max) grid, deduped after clamping bg to the group count.
    The heuristic default is always candidate #0 (so a truncated search can
    never be worse than no search); VMEM gating applies to the *clamped* bg
    actually dispatched."""
    cands: list[Tuple[int, int]] = [(min(ENCODE_DEFAULTS[0], g), ENCODE_DEFAULTS[1])]
    for delta in ENCODE_DELTA_CANDIDATES:
        for bg in ENCODE_BG_CANDIDATES:
            t = (min(bg, g), delta)
            if t[0] * n * 4 * 6 > _VMEM_BUDGET_BYTES:  # ~6 (bg, n) f32 live arrays
                continue
            if t not in cands:
                cands.append(t)
    return tuple(cands[:max_candidates])


def _time_encode_candidate(
    w, k_pulses: int, cand: Tuple[int, int], reps: int, interpret: bool
) -> float:
    bg, delta_max = cand
    p, _ = pvq_encode_batch(
        w, k_pulses=k_pulses, bg=bg, delta_max=delta_max, interpret=interpret
    )
    p.block_until_ready()  # warmup: trace + compile outside the timed region
    t0 = time.perf_counter()
    for _ in range(reps):
        pvq_encode_batch(
            w, k_pulses=k_pulses, bg=bg, delta_max=delta_max, interpret=interpret
        )[0].block_until_ready()
    return (time.perf_counter() - t0) / reps


def autotune_encode(
    g: int,
    n: int,
    k_pulses: int,
    *,
    dtype=jnp.float32,
    reps: int = 3,
    interpret: Optional[bool] = None,
    max_candidates: Optional[int] = None,
) -> dict:
    """Search (bg, delta_max) for a (g, n, K) encode shape; persist + return
    ``{"bg","delta_max","us","candidates"}``.  A cache hit skips the search."""
    backend = jax.default_backend()
    if interpret is None:
        interpret = backend != "tpu"
    key = encode_cache_key(g, n, k_pulses, dtype, backend)
    hit = _load().get(key)
    if hit is not None:
        _note(key, "hit")
        return hit
    _note(key, "miss")
    t_search = time.perf_counter()
    if max_candidates is None:
        max_candidates = (
            MAX_ENCODE_CANDIDATES_INTERPRET
            if interpret
            else MAX_ENCODE_CANDIDATES_COMPILED
        )
    cands = encode_candidates(g, n, max_candidates)
    w = jax.random.laplace(jax.random.PRNGKey(0), (g, n), jnp.float32).astype(dtype)
    best: Optional[Tuple[int, int]] = None
    best_t = float("inf")
    for c in cands:
        dt = _time_encode_candidate(w, k_pulses, c, reps, interpret)
        if dt < best_t:
            best, best_t = c, dt
    assert best is not None
    entry = {
        "bg": best[0],
        "delta_max": best[1],
        "us": round(1e6 * best_t, 2),
        "candidates": len(cands),
    }
    _persist(key, entry)
    _note(key, "search", time.perf_counter() - t_search)
    return entry


def get_encode_params(
    g: int,
    n: int,
    k_pulses: int,
    *,
    dtype=jnp.float32,
    search: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[int, int]:
    """(bg, delta_max) dispatch for ``ops.pvq_encode``: cache hit > search >
    heuristic default.  ``search=None`` defers to ``REPRO_PVQ_AUTOTUNE``,
    exactly like the matmul tile dispatch."""
    backend = jax.default_backend()
    key = encode_cache_key(g, n, k_pulses, dtype, backend)
    hit = _load().get(key)
    if hit is not None:
        _note(key, "hit")
        return (hit["bg"], hit["delta_max"])
    if search is None:
        search = os.environ.get("REPRO_PVQ_AUTOTUNE", "") not in ("", "0", "false")
    if search:
        # autotune_encode records the miss + search itself
        e = autotune_encode(g, n, k_pulses, dtype=dtype, interpret=interpret)
        return (e["bg"], e["delta_max"])
    _note(key, "miss")
    return (min(ENCODE_DEFAULTS[0], g), ENCODE_DEFAULTS[1])


# ---------------------------------------------------------------------------
# attention decode autotune: pvq_attn_q's sequence-block size (kernel v4)
# ---------------------------------------------------------------------------

#: bs sweeps lane-aligned KV block widths; 128 is the MXU-native floor
ATTN_BS_CANDIDATES = (128, 256, 512)


def attn_cache_key(m: int, hd: int, s: int, group: int, dtype, backend: str) -> str:
    """Key for the kernel-v4 attention decode contraction.  Carries
    ``kv{KERNEL_VERSION}`` exactly like the matmul keys, so the v3->v4 bump
    structurally invalidates every pre-v4 entry — a kv3-tagged tile can never
    be served for v4 dispatch (the kv3 suffix simply never matches)."""
    return (
        f"attn{m}x{hd}x{s}:g{group}:{jnp.dtype(dtype).name}:{backend}"
        f":kv{KERNEL_VERSION}:{_SCHEMA}"
    )


def heuristic_attn_bs(s: int) -> int:
    """Lane-aligned default KV block: one 128 block, or the whole (short)
    padded sequence when it fits a single grid step."""
    return 128 if s >= 128 else max(s, 8)


def attn_candidates(s: int, max_candidates: int) -> Tuple[int, ...]:
    """bs grid clamped to the padded sequence; heuristic first (a truncated
    search can never be worse than no search)."""
    cands: list[int] = [heuristic_attn_bs(s)]
    for bs in ATTN_BS_CANDIDATES:
        if bs <= max(s, 128) and bs not in cands:
            cands.append(bs)
    return tuple(cands[:max_candidates])


def autotune_attn(
    m: int,
    hd: int,
    s: int,
    *,
    group: int = 32,
    dtype=jnp.int8,
    reps: int = 3,
    interpret: Optional[bool] = None,
    max_candidates: Optional[int] = None,
) -> dict:
    """Search the KV-block grid for a (m, hd, s) decode-attention shape;
    persist + return ``{"bs","us","candidates"}``.  ``m`` is query rows per
    kv head (q_len * group_size), ``s`` the packed cache extent."""
    backend = jax.default_backend()
    if interpret is None:
        interpret = backend != "tpu"
    key = attn_cache_key(m, hd, s, group, dtype, backend)
    hit = _load().get(key)
    if hit is not None:
        _note(key, "hit")
        return hit
    _note(key, "miss")
    t_search = time.perf_counter()
    if max_candidates is None:
        max_candidates = (
            MAX_CANDIDATES_INTERPRET if interpret else MAX_CANDIDATES_COMPILED
        )
    cands = attn_candidates(s, max_candidates)

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    bh = 2
    ng = max(hd // group, 1)
    q = jax.random.randint(kq, (bh, m, hd), -127, 128, jnp.int8)
    a = jnp.full((bh, m, 1), 0.01, jnp.float32)
    kp = jax.random.randint(kk, (bh, s, hd), -5, 6, jnp.int8)
    vp = jax.random.randint(kv, (bh, s, hd), -5, 6, jnp.int8)
    ks = jnp.full((bh, s, ng), 0.05, jnp.float32)
    vs = jnp.full((bh, s, ng), 0.05, jnp.float32)
    kv_len = jnp.full((bh,), s, jnp.int32)

    best: Optional[int] = None
    best_t = float("inf")
    for bs in cands:
        def call():
            return pvq_attn_q(
                q, a, kp, ks, vp, vs, kv_len,
                group=min(group, hd), sm_scale=1.0, bs=bs, interpret=interpret,
            )
        call()[0].block_until_ready()  # warmup
        t0 = time.perf_counter()
        for _ in range(reps):
            call()[0].block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        if dt < best_t:
            best, best_t = bs, dt
    assert best is not None
    entry = {"bs": best, "us": round(1e6 * best_t, 2), "candidates": len(cands)}
    _persist(key, entry)
    _note(key, "search", time.perf_counter() - t_search)
    return entry


def get_attn_tiles(
    m: int,
    hd: int,
    s: int,
    *,
    group: int = 32,
    dtype=jnp.int8,
    search: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> int:
    """KV block-size dispatch for ``ops.pvq_attn_decode``: cache hit >
    search (``REPRO_PVQ_AUTOTUNE=1``) > heuristic, mirroring ``get_tiles``."""
    backend = jax.default_backend()
    key = attn_cache_key(m, hd, s, group, dtype, backend)
    hit = _load().get(key)
    if hit is not None:
        _note(key, "hit")
        return int(hit["bs"])
    if search is None:
        search = os.environ.get("REPRO_PVQ_AUTOTUNE", "") not in ("", "0", "false")
    if search:
        # autotune_attn records the miss + search itself
        return int(
            autotune_attn(m, hd, s, group=group, dtype=dtype, interpret=interpret)["bs"]
        )
    _note(key, "miss")
    return heuristic_attn_bs(s)


def get_tiles(
    m: int,
    k: int,
    n: int,
    *,
    group: int = 128,
    dtype=jnp.float32,
    search: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[int, int, int]:
    """Tile dispatch for ``ops.pvq_matmul``: cache hit > search > heuristic.

    ``search=None`` defers to the ``REPRO_PVQ_AUTOTUNE`` env var, so a whole
    serving/training job can opt in to first-call tuning without threading a
    flag through every layer."""
    backend = jax.default_backend()
    key = cache_key(m, k, n, group, dtype, backend)
    hit = _load().get(key)
    if hit is not None:
        _note(key, "hit")
        return (hit["bm"], hit["bn"], hit["bk"])
    if search is None:
        search = os.environ.get("REPRO_PVQ_AUTOTUNE", "") not in ("", "0", "false")
    if search:
        # autotune records the miss + search itself
        e = autotune(m, k, n, group=group, dtype=dtype, interpret=interpret)
        return (e["bm"], e["bn"], e["bk"])
    _note(key, "miss")
    return heuristic_tiles(m, k, n, group)


def tune_shapes(
    shapes: Iterable[Tuple[int, int, int]],
    *,
    group: int = 128,
    dtype=jnp.float32,
    reps: int = 3,
    interpret: Optional[bool] = None,
) -> Dict[str, dict]:
    """Pre-tune a batch of GEMM shapes (serve/train warmup). Returns key->entry."""
    out = {}
    for m, k, n in shapes:
        out[cache_key(m, k, n, group, dtype, jax.default_backend())] = autotune(
            m, k, n, group=group, dtype=dtype, reps=reps, interpret=interpret
        )
    return out


def tune_attn_shapes(
    shapes: Iterable[Tuple[int, int, int]],
    *,
    group: int = 32,
    dtype=jnp.int8,
    interpret: Optional[bool] = None,
) -> Dict[str, dict]:
    """Pre-tune a batch of ``(m, hd, s)`` decode-attention shapes.

    The continuous-batching engine keys its kernel-v4 dispatch on the
    SLOT-POOL geometry, not the per-request one: ``m`` is query rows per kv
    head and ``s`` the pool extent ``max_pages_per_slot * page`` — every
    decode step of the engine hits the same (m, hd, s) entry regardless of
    how many requests are in flight.  Returns key->entry like
    :func:`tune_shapes`.
    """
    out = {}
    backend = jax.default_backend()
    for m, hd, s in shapes:
        out[attn_cache_key(m, hd, s, group, dtype, backend)] = autotune_attn(
            m, hd, s, group=group, dtype=dtype, interpret=interpret
        )
    return out
