"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler that ships with JAX compiles for a
topology it is only told about, and refuses what the chip would refuse
(block shapes, unsupported primitives, layouts) that interpret mode on the
CPU never checks.  Shapes are smollm-360m's published widths: d_model 960
(k_pad 1024 at group 256), d_ff 2560, 5 KV heads of 64 with 3 query heads
each, the embedding's group of 64.  Nothing runs, so these say nothing
about results or speed.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one that runs this file
loads the TPU library.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.pvq_encode import pvq_encode_batch
from repro.kernels.pvq_matmul import pvq_attn_q, pvq_matmul, pvq_matmul_q

GROUP = 256  # smollm-360m's weight PVQ group


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache (the
    # reset drops a cache this process may already have opened)
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args) -> str:
    """Compile ``fn`` for the described chip; returns the optimized HLO."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m", [8, 128], ids=["decode", "prefill"])
@pytest.mark.parametrize(
    "k,n", [(1024, 960), (1024, 320), (1024, 2560), (2560, 960)],
    ids=["q_o", "k_v", "gate_up", "down"],
)
def test_v3_compiles_with_heuristic_tiles(one_chip, m, k, n):
    bm, bn, bk = autotune.heuristic_tiles(m, k, n, GROUP)

    def f(x, w, s, a):
        return pvq_matmul_q(
            x, w, s, a, group=GROUP, bm=bm, bn=bn, bk=bk, dma_streaming=False
        )

    hlo = _compile(
        f,
        _spec(one_chip, (m, k), jnp.int8),
        _spec(one_chip, (k, n), jnp.int8),
        _spec(one_chip, (k // GROUP, n), jnp.float32),
        _spec(one_chip, (m, 1), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


def test_v3_dma_body_compiles(one_chip):
    """The hand-rolled DMA body needs >= 2 legal k-chunks; at group 256 the
    legal bk spans the whole smollm contraction, so it runs at group 64."""
    m, k, n, group = 8, 2560, 960, 64

    def f(x, w, s, a):
        return pvq_matmul_q(
            x, w, s, a, group=group, bm=8, bn=128, bk=512, dma_streaming=True
        )

    hlo = _compile(
        f,
        _spec(one_chip, (m, k), jnp.int8),
        _spec(one_chip, (k, n), jnp.int8),
        _spec(one_chip, (k // group, n), jnp.float32),
        _spec(one_chip, (m, 1), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


def test_v2_compiles_with_heuristic_tiles(one_chip):
    m, k, n = 8, 1024, 2560
    bm, bn, bk = autotune.heuristic_tiles(m, k, n, GROUP)

    def f(x, w, s):
        return pvq_matmul(x, w, s, group=GROUP, bm=bm, bn=bn, bk=bk)

    hlo = _compile(
        f,
        _spec(one_chip, (m, k), jnp.float32),
        _spec(one_chip, (k, n), jnp.int8),
        _spec(one_chip, (k // GROUP, n), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize(
    "bh,m", [(40, 3), (5, 384)], ids=["engine_decode", "chunk_128"]
)
def test_v4_compiles(one_chip, bh, m):
    """Engine decode: 8 slots x 5 kv heads, 3 query rows per kv head; chunk
    step: one slot's 128-token chunk x 3 query heads per kv head."""
    s, hd, group = 544, 64, 32
    bs = autotune.heuristic_attn_bs(s)

    def f(q, a, kp, ks, vp, vs, kv_len):
        return pvq_attn_q(
            q, a, kp, ks, vp, vs, kv_len, group=group, sm_scale=0.125, bs=bs
        )

    hlo = _compile(
        f,
        _spec(one_chip, (bh, m, hd), jnp.int8),
        _spec(one_chip, (bh, m, 1), jnp.float32),
        _spec(one_chip, (bh, s, hd), jnp.int8),
        _spec(one_chip, (bh, s, hd // group), jnp.float32),
        _spec(one_chip, (bh, s, hd), jnp.int8),
        _spec(one_chip, (bh, s, hd // group), jnp.float32),
        _spec(one_chip, (bh,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["v3", "v4"])
def test_int8_kernels_compile_under_highest_matmul_precision(one_chip, kernel):
    """An f32 reference computed under ``default_matmul_precision("highest")``
    around a kernel call must not push fp32 contract precision onto the
    kernels' int8 dots, which Mosaic refuses."""
    if kernel == "v3":
        m, k, n = 8, 1024, 960

        def f(x, w, s, a):
            return pvq_matmul_q(x, w, s, a, group=GROUP, dma_streaming=False)

        args = (
            _spec(one_chip, (m, k), jnp.int8),
            _spec(one_chip, (k, n), jnp.int8),
            _spec(one_chip, (k // GROUP, n), jnp.float32),
            _spec(one_chip, (m, 1), jnp.float32),
        )
    else:
        bh, m, s, hd, group = 40, 3, 544, 64, 32

        def f(q, a, kp, ks, vp, vs, kv_len):
            return pvq_attn_q(
                q, a, kp, ks, vp, vs, kv_len, group=group, sm_scale=0.125,
                bs=autotune.heuristic_attn_bs(s),
            )

        args = (
            _spec(one_chip, (bh, m, hd), jnp.int8),
            _spec(one_chip, (bh, m, 1), jnp.float32),
            _spec(one_chip, (bh, s, hd), jnp.int8),
            _spec(one_chip, (bh, s, hd // group), jnp.float32),
            _spec(one_chip, (bh, s, hd), jnp.int8),
            _spec(one_chip, (bh, s, hd // group), jnp.float32),
            _spec(one_chip, (bh,), jnp.int32),
        )
    with jax.default_matmul_precision("highest"):
        assert "tpu_custom_call" in _compile(f, *args)


@pytest.mark.parametrize(
    "g,n,k_pulses",
    [(10240, 256, 256), (737280, 64, 128)],
    ids=["ffn_group256", "embedding_group64"],
)
def test_encoder_compiles(one_chip, g, n, k_pulses):
    """Weight encode at the FFN gate/up shape (960 x 2560 -> 10240 groups
    of 256, K = 256) and the embedding (49152 x 960 -> groups of 64,
    K = 128)."""
    bg, delta_max = autotune.ENCODE_DEFAULTS

    def f(w):
        return pvq_encode_batch(w, k_pulses=k_pulses, bg=bg, delta_max=delta_max)

    hlo = _compile(f, _spec(one_chip, (g, n), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_engine_decode_step_lowers_to_mosaic(one_chip, monkeypatch):
    """The engine's whole jitted decode step (reduced smollm, full quantized
    stack) compiles for the chip with its kernels as Mosaic calls and one
    conditional, the KV page encode: at 1 slot the one-pass encode of its
    ring, at 9 (``encode_chunk`` 2) the loop over completing rings."""
    import re

    from repro.configs import get_config
    from repro.core.packed import quantize_params
    from repro.core.quantize import (
        ActQuant, KVQuant, QuantPolicy, act_quant_scope, kv_quant_scope,
    )
    from repro.kernels import ops
    from repro.launch.engine import PVQEngine
    from repro.nn.models import build_model

    # the code asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    policy = QuantPolicy(
        rules=(("embedding", 0.5, 64), ("kernel", 1.0, 64)), scale_mode="ls"
    )
    params = jax.eval_shape(
        lambda key: quantize_params(model.init(key, max_seq=64), policy),
        jax.random.PRNGKey(0),
    )
    spec = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), t
    )
    i32 = lambda *shape: _spec(one_chip, shape, jnp.int32)  # noqa: E731
    for n_slots, chunk in ((1, 1), (9, 2)):
        with act_quant_scope(ActQuant()), kv_quant_scope(KVQuant(block=8, group=16)):
            eng = PVQEngine(model, params, n_slots=n_slots, max_len=64, prefill_chunk=2)
            hlo = _compile(
                eng._decode_fn, spec(params), spec(eng.cache), i32(n_slots, 1),
                i32(n_slots), i32(n_slots, eng.max_pages), i32(n_slots),
            )
        assert eng.encode_chunk == chunk
        assert "tpu_custom_call" in hlo
        assert len(re.findall(r" conditional\(", hlo)) == 1, n_slots
