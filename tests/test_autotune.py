"""Autotuner tests: candidate generation, persistent JSON cache semantics,
cache-hit dispatch, and tuned-kernel correctness vs the oracle."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops
from repro.kernels.pvq_matmul import normalize_tiles
from repro.kernels.ref import pvq_matmul_ref


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    """Point the persistent cache at a fresh file, reset the memory mirror."""
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_PVQ_TUNE_CACHE", str(path))
    autotune.clear_memory_cache()
    yield path
    autotune.clear_memory_cache()


def test_candidates_aligned_and_bounded():
    cands = autotune.candidate_tiles(8, 512, 512, group=128, max_candidates=24)
    assert cands, "no candidates"
    assert cands[0] == autotune.heuristic_tiles(8, 512, 512, 128)
    for bm, bn, bk in cands:
        assert bk % 128 == 0  # group multiple
        assert bm <= 8 and bn <= 512 and bk <= 512  # clamped to the problem
    assert len(set(cands)) == len(cands)  # deduped


def _mosaic_block_ok(block, array) -> bool:
    """The TPU block-shape rule: each of the last two block dims is (8, 128)
    aligned or spans the whole (padded) array dim."""
    (br, bc), (ar, ac) = block, array
    return (br % 8 == 0 or br == ar) and (bc % 128 == 0 or bc == ac)


@pytest.mark.parametrize(
    "m,k,n,group",
    [
        (8, 1024, 960, 256),  # smollm-360m decode, d_model GEMMs
        (8, 2560, 960, 256),  # smollm-360m decode, FFN down
        (128, 1024, 2560, 256),  # smollm-360m chunked prefill
        (8, 960, 960, 64),
        (3, 256, 64, 32),
        (16, 512, 300, 16),
        (5, 64, 96, 8),
    ],
)
def test_every_tile_is_a_legal_tpu_block(m, k, n, group):
    """normalize_tiles, heuristic_tiles and candidate_tiles only hand out
    tiles whose x / pulse / scales blocks Mosaic accepts, so the CPU tests
    run the tiles the chip compiles."""
    raw = (1, 8, 100, 128, 256, 512, 4096)
    tiles = {autotune.heuristic_tiles(m, k, n, group)}
    tiles |= set(autotune.candidate_tiles(m, k, n, group, max_candidates=100))
    tiles |= {
        normalize_tiles(m, k, n, group, bm, bn, bk)
        for bm in raw for bn in raw for bk in raw
    }
    for bm, bn, bk in tiles:
        assert bk % group == 0 and bk <= k, (bm, bn, bk)
        mp, kp, np_ = (-(-v // b) * b for v, b in ((m, bm), (k, bk), (n, bn)))
        assert _mosaic_block_ok((bm, bk), (mp, kp)), ("x", bm, bn, bk)
        assert _mosaic_block_ok((bk, bn), (kp, np_)), ("pulses", bm, bn, bk)
        assert _mosaic_block_ok((bk // group, bn), (kp // group, np_)), (
            "scales", bm, bn, bk,
        )


def test_autotune_persists_cache_file(tune_cache):
    entry = autotune.autotune(8, 128, 128, group=128, reps=1, interpret=True)
    assert {"bm", "bn", "bk", "us", "candidates"} <= set(entry)
    assert tune_cache.exists()
    on_disk = json.loads(tune_cache.read_text())
    key = autotune.cache_key(8, 128, 128, 128, jnp.float32, jax.default_backend())
    assert on_disk[key] == entry  # JSON round-trip preserves the entry


def test_second_call_skips_search(tune_cache, monkeypatch):
    entry1 = autotune.autotune(8, 128, 128, group=128, reps=1, interpret=True)

    def boom(*a, **k):  # any timing attempt after the first call is a bug
        raise AssertionError("search ran despite cache hit")

    monkeypatch.setattr(autotune, "_time_candidate", boom)
    entry2 = autotune.autotune(8, 128, 128, group=128, reps=1, interpret=True)
    assert entry2 == entry1
    # dispatch side: get_tiles must serve the tuned tiles without timing
    tiles = autotune.get_tiles(8, 128, 128, group=128, search=True, interpret=True)
    assert tiles == (entry1["bm"], entry1["bn"], entry1["bk"])


def test_cache_survives_memory_reset(tune_cache, monkeypatch):
    """A fresh process (simulated by clearing the mirror) reads the JSON."""
    entry = autotune.autotune(8, 128, 128, group=128, reps=1, interpret=True)
    autotune.clear_memory_cache()
    monkeypatch.setattr(
        autotune, "_time_candidate", lambda *a, **k: pytest.fail("re-searched")
    )
    tiles = autotune.get_tiles(8, 128, 128, group=128, search=True, interpret=True)
    assert tiles == (entry["bm"], entry["bn"], entry["bk"])


def test_get_tiles_heuristic_without_search(tune_cache):
    tiles = autotune.get_tiles(16, 256, 256, group=128, search=False, interpret=True)
    assert tiles == autotune.heuristic_tiles(16, 256, 256, 128)
    assert not tune_cache.exists()  # no search -> no I/O


@pytest.mark.parametrize(
    "m,k,n,group,dtype",
    [
        (8, 128, 128, 128, jnp.float32),
        (16, 256, 128, 64, jnp.float32),
        (8, 128, 128, 128, jnp.bfloat16),
        (32, 512, 256, 128, jnp.float32),
    ],
)
def test_tuned_dispatch_matches_ref(tune_cache, m, k, n, group, dtype):
    """ops.pvq_matmul with autotuned tiles stays correct across a grid."""
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(m + n), 3)
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
    pulses = jax.random.randint(kw, (k, n), -3, 4, jnp.int8)
    scales = jnp.abs(jax.random.normal(ks, (k // group, n))) * 0.05
    got = ops.pvq_matmul(x, pulses, scales, group=group, tune=True, interpret=True)
    want = pvq_matmul_ref(x, pulses, scales, group=group)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=1e-1 if dtype == jnp.bfloat16 else 1e-4,
    )


# ---------------------------------------------------------------------------
# kernel-version invalidation (ISSUE 5 satellite: the kv{N} tag had no test)
# ---------------------------------------------------------------------------


def test_kernel_version_bump_changes_every_cache_key(monkeypatch):
    """Bumping KERNEL_VERSION must change EVERY matmul cache key — no shape,
    group, dtype, or backend combination may survive a kernel-body change."""
    combos = [
        (8, 128, 128, 128, jnp.float32, "cpu"),
        (16, 256, 512, 64, jnp.bfloat16, "tpu"),
        (8, 128, 128, 128, jnp.int8, "cpu"),
        (128, 4096, 11008, 128, jnp.float32, "tpu"),
    ]
    before = {autotune.cache_key(*c) for c in combos}
    monkeypatch.setattr(autotune, "KERNEL_VERSION", autotune.KERNEL_VERSION + 1)
    after = {autotune.cache_key(*c) for c in combos}
    assert len(before) == len(after) == len(combos)
    assert before.isdisjoint(after)


def test_v2_tagged_entries_never_served_for_v3_dispatch(tune_cache):
    """A cache file carrying kv2/v2-era entries (older kernel body AND older
    schema) must never satisfy current dispatch: get_tiles falls through to
    the heuristic instead of serving the stale tiles."""
    import json as json_lib

    from repro.kernels.pvq_matmul import KERNEL_VERSION

    assert KERNEL_VERSION >= 3  # the premise of the regression
    m, k, n, group = 8, 256, 256, 128
    poison = {"bm": 1, "bn": 1, "bk": 1, "us": 0.0, "candidates": 1}
    key_now = autotune.cache_key(m, k, n, group, jnp.float32, jax.default_backend())
    stale_keys = {
        # same shape, previous kernel body tag
        key_now.replace(f"kv{KERNEL_VERSION}", f"kv{KERNEL_VERSION - 1}"),
        # same shape, previous schema tag (hand-edited / pre-bump cache file)
        key_now.replace(":v3", ":v2"),
        key_now.replace(f"kv{KERNEL_VERSION}", "kv2").replace(":v3", ":v2"),
    }
    assert key_now not in stale_keys
    tune_cache.write_text(json_lib.dumps({kk: poison for kk in stale_keys}))
    autotune.clear_memory_cache()
    tiles = autotune.get_tiles(m, k, n, group=group, search=False, interpret=True)
    assert tiles == autotune.heuristic_tiles(m, k, n, group)
    assert tiles != (1, 1, 1)


def test_int8_act_dtype_gets_its_own_cache_entry(tune_cache):
    """The activation dtype is part of the key: int8 entries are timed
    against the v3 quantized-activation body and never collide with the
    f32-activation tiles for the same GEMM shape."""
    k_f32 = autotune.cache_key(8, 128, 128, 128, jnp.float32, "cpu")
    k_int8 = autotune.cache_key(8, 128, 128, 128, jnp.int8, "cpu")
    assert k_f32 != k_int8 and "int8" in k_int8
    entry = autotune.autotune(
        8, 128, 128, group=128, dtype=jnp.int8, reps=1, interpret=True
    )
    assert {"bm", "bn", "bk", "us"} <= set(entry)
    tiles = autotune.get_tiles(
        8, 128, 128, group=128, dtype=jnp.int8, search=False, interpret=True
    )
    assert tiles == (entry["bm"], entry["bn"], entry["bk"])
    # the f32 key is still a miss — the int8 search didn't pollute it
    assert autotune._load().get(
        autotune.cache_key(8, 128, 128, 128, jnp.float32, jax.default_backend())
    ) is None


# ---------------------------------------------------------------------------
# encoder autotune: pvq_encode's (bg, delta_max) knobs (ROADMAP satellite)
# ---------------------------------------------------------------------------


def test_encode_cache_key_carries_encoder_kernel_version():
    from repro.kernels.pvq_encode import ENCODE_KERNEL_VERSION

    key = autotune.encode_cache_key(16, 128, 32, jnp.float32, "cpu")
    assert f":ekv{ENCODE_KERNEL_VERSION}:" in key
    assert key.endswith(":v3")  # same schema/store as the matmul tiles
    # encoder and matmul keys can never collide
    assert key != autotune.cache_key(16, 128, 32, 128, jnp.float32, "cpu")


def test_encode_candidates_never_lower_delta_max():
    """Tuning may only make the encoder *more* exact: every candidate keeps
    delta_max at or above the heuristic default."""
    cands = autotune.encode_candidates(64, 256, max_candidates=16)
    assert cands[0] == autotune.ENCODE_DEFAULTS
    assert all(delta >= autotune.ENCODE_DEFAULTS[1] for _, delta in cands)
    assert all(bg <= 64 for bg, _ in cands)
    assert len(set(cands)) == len(cands)
    # bg clamps to tiny group counts
    assert all(bg <= 2 for bg, _ in autotune.encode_candidates(2, 64, 8))


def test_autotune_encode_persists_and_hits(tune_cache, monkeypatch):
    entry = autotune.autotune_encode(8, 64, 16, reps=1, interpret=True)
    assert {"bg", "delta_max", "us", "candidates"} <= set(entry)
    on_disk = json.loads(tune_cache.read_text())
    key = autotune.encode_cache_key(8, 64, 16, jnp.float32, jax.default_backend())
    assert on_disk[key] == entry
    monkeypatch.setattr(
        autotune,
        "_time_encode_candidate",
        lambda *a, **k: pytest.fail("re-searched despite cache hit"),
    )
    assert autotune.autotune_encode(8, 64, 16, reps=1, interpret=True) == entry
    # dispatch resolves to the tuned knobs without timing
    assert autotune.get_encode_params(8, 64, 16) == (entry["bg"], entry["delta_max"])


def test_get_encode_params_heuristic_without_search(tune_cache):
    assert autotune.get_encode_params(512, 256, 64, search=False) == autotune.ENCODE_DEFAULTS
    assert not tune_cache.exists()  # no search -> no I/O


def test_ops_encode_uses_tuned_knobs(tune_cache):
    """ops.pvq_encode with defaulted knobs resolves through the cache and
    stays correct (L1 = K exactly)."""
    autotune.autotune_encode(8, 128, 32, reps=1, interpret=True)
    w = jax.random.laplace(jax.random.PRNGKey(2), (8, 128))
    pulses, _ = ops.pvq_encode(w, k_pulses=32, interpret=True)
    np.testing.assert_array_equal(np.abs(np.asarray(pulses)).sum(-1), 32)
