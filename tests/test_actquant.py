"""The ActQuant contract: symmetric int8 activation quantization, the exact
dequant error model, kernel v3 (int8 x int8, int32 MXU accumulation) against
its analytic bound, the double-buffered DMA pulse-streaming variant, and the
contract threaded through layers / sequential / MoE / serve."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.packed import dequantize_params, pack_matmul, quantize_params
from repro.core.quantize import (
    ActQuant,
    QuantPolicy,
    act_matmul_error_bound,
    act_quant_scope,
    default_act_quant,
    quantize_activations,
    set_default_act_quant,
)
from repro.kernels import ops
from repro.kernels.pvq_matmul import pvq_matmul, pvq_matmul_q
from repro.kernels.ref import pvq_matmul_ref


# ---------------------------------------------------------------------------
# quantize_activations: the exact roundtrip bound
# ---------------------------------------------------------------------------


def test_actquant_mode_validation():
    with pytest.raises(ValueError):
        ActQuant(mode="per_column")


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 2**31 - 1), st.sampled_from(["per_row", "per_tensor"]))
def test_prop_quantize_roundtrip_bound(seed, mode):
    """|x - q * scale| <= scale / 2 elementwise, q within the int8 range."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (9, 37)) * 3.0
    q, scale = quantize_activations(x, ActQuant(mode=mode))
    assert q.dtype == jnp.int8
    assert scale.shape == (9, 1)
    err = jnp.abs(x - q.astype(jnp.float32) * scale)
    assert bool(jnp.all(err <= scale / 2 + 1e-7))
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= 127


def test_quantize_zero_rows_are_exact():
    """All-pad rows (MoE empty capacity slots) get scale 0 / pulses 0 — no
    NaNs, exact zeros on dequant."""
    x = jnp.zeros((4, 16)).at[1].set(jax.random.normal(jax.random.PRNGKey(0), (16,)))
    q, scale = quantize_activations(x)
    assert bool(jnp.all(jnp.isfinite(scale)))
    assert float(scale[0, 0]) == 0.0 and float(scale[2, 0]) == 0.0
    assert bool(jnp.all(q[0] == 0)) and bool(jnp.all(q[3] == 0))


def test_per_tensor_shares_one_scale():
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 32))
    _, scale = quantize_activations(x, ActQuant(mode="per_tensor"))
    assert len(np.unique(np.asarray(scale))) == 1


# ---------------------------------------------------------------------------
# per-tile (row x k-group) scales: ActQuant(granularity="tile")
# ---------------------------------------------------------------------------


def test_actquant_granularity_maps_to_mode():
    assert ActQuant(granularity="tile").mode == "per_tile"
    assert ActQuant(granularity="row").mode == "per_row"
    with pytest.raises(ValueError):
        ActQuant(granularity="per_block")


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 2**31 - 1))
def test_prop_per_tile_roundtrip_bound(seed):
    """Per-tile roundtrip: |x - q * scale_tile| <= scale_tile / 2 within
    each (row, k-group) tile; scale shape is (m, k // tile)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (5, 128)) * 2.0
    # pathological dynamic range: one huge group per row defeats a per-row scale
    x = x.at[:, :32].multiply(100.0)
    q, scale = quantize_activations(x, ActQuant(granularity="tile"), tile=32)
    assert scale.shape == (5, 4)
    err = jnp.abs(x - q.astype(jnp.float32) * jnp.repeat(scale, 32, axis=-1))
    cap = jnp.repeat(scale, 32, axis=-1) / 2 + 1e-6
    assert bool(jnp.all(err <= cap))


@settings(deadline=None, max_examples=8)
@given(st.integers(0, 2**31 - 1))
def test_prop_kernel_v3_per_tile_within_error_bound(seed):
    """Satellite: per-tile int8 logits stay within the per-tile analytic
    bound (a @ weighted), which is strictly tighter than the per-row bound
    on high-dynamic-range rows."""
    m, k, n, group = 6, 256, 96, 64
    x, w, s = _problem(seed, m, k, n, group)
    x = x.at[:, :group].multiply(50.0)  # long-prefill-style outlier group
    xq, a = quantize_activations(x, ActQuant(granularity="tile"), tile=group)
    assert a.shape == (m, k // group)
    y_f = pvq_matmul_ref(x, w, s, group=group)
    y_q = pvq_matmul_q(xq, w, s, a, group=group, interpret=True)
    bound = act_matmul_error_bound(a, w, s, group)
    assert bound.shape == (m, n)
    assert bool(jnp.all(jnp.abs(y_q - y_f) <= bound + 1e-5))


def test_per_tile_beats_per_row_on_outlier_rows():
    """The motivating case: a row whose groups span 100x dynamic range loses
    most of its small-group signal to one per-row scale; per-tile scales
    recover it.  Compare actual kernel error, not just bounds."""
    m, k, n, group = 4, 256, 64, 64
    x, w, s = _problem(30, m, k, n, group)
    x = x.at[:, :group].multiply(100.0)
    y_f = pvq_matmul_ref(x, w, s, group=group)
    y_row = ops.pvq_matmul(x, w, s, group=group, act_quant=ActQuant())
    y_tile = ops.pvq_matmul(
        x, w, s, group=group, act_quant=ActQuant(granularity="tile")
    )
    e_row = float(jnp.linalg.norm(y_row - y_f))
    e_tile = float(jnp.linalg.norm(y_tile - y_f))
    assert e_tile < e_row


def test_ops_per_tile_dispatch_through_packed_matmul():
    """ops threads the weight group into the per-tile quantizer (the tile
    width IS the PVQ group) — the packed entry point works end to end, with
    padding applied before quantization so scale groups stay aligned."""
    w = jax.random.laplace(jax.random.PRNGKey(31), (96, 48)) * 0.1
    pk = pack_matmul(w, group=64, n_over_k=2.0)  # k_pad = 128 > d_in = 96
    x = jax.random.normal(jax.random.PRNGKey(32), (5, 96))
    y_f = ops.packed_matmul(x, pk)
    y_t = ops.packed_matmul(x, pk, act_quant=ActQuant(granularity="tile"))
    rel = float(jnp.linalg.norm(y_t - y_f) / jnp.linalg.norm(y_f))
    assert rel < 0.05


# ---------------------------------------------------------------------------
# kernel v3 vs the analytic error bound
# ---------------------------------------------------------------------------


def _problem(seed, m, k, n, group, pulse_lo=-3, pulse_hi=4):
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.randint(kw, (k, n), pulse_lo, pulse_hi, jnp.int8)
    s = (jnp.abs(jax.random.normal(ks, (k // group, n))) * 0.05).astype(jnp.float32)
    return x, w, s


@settings(deadline=None, max_examples=8)
@given(st.integers(0, 2**31 - 1))
def test_prop_kernel_v3_within_error_bound(seed):
    """Satellite: int8 x int8 logits stay within the analytic per-group
    bound vs the f32-activation kernel — the error model is EXACT, not a
    heuristic."""
    m, k, n, group = 9, 256, 130, 128
    x, w, s = _problem(seed, m, k, n, group)
    xq, a = quantize_activations(x)
    y_f = pvq_matmul_ref(x, w, s, group=group)
    y_q = pvq_matmul_q(xq, w, s, a, group=group, interpret=True)
    bound = act_matmul_error_bound(a, w, s, group)
    assert bool(jnp.all(jnp.abs(y_q - y_f) <= bound + 1e-5))


def test_kernel_v3_k_gt_127_clamped_pulses_within_bound():
    """Satellite: the K > 127 clamped-pulse regime — the bound is computed
    from the pulses actually stored (L1 <= K after the clamp), so it holds
    on the clamped artifact too."""
    w = jax.random.laplace(jax.random.PRNGKey(3), (64, 48)) * 0.1
    pk = pack_matmul(w, group=64, k=200)  # K > 127: coordinates may clamp
    assert pk.k > 127
    x = jax.random.normal(jax.random.PRNGKey(4), (7, 64))
    xq, a = quantize_activations(x)
    y_f = ops.packed_matmul(x, pk)
    y_q = ops.packed_matmul(x, pk, act_quant=ActQuant())
    bound = act_matmul_error_bound(a, pk.pulses, pk.scales, pk.group)
    assert bool(jnp.all(jnp.abs(y_q - y_f) <= bound + 1e-4))


def test_kernel_v3_zero_scale_rows_yield_exact_zero_logits():
    """Satellite: zero-scale (all-pad) rows — both paths produce exactly 0,
    the bound degrades to 0, nothing divides by the zero scale."""
    m, k, n, group = 6, 128, 64, 64
    x, w, s = _problem(5, m, k, n, group)
    x = x.at[2].set(0.0).at[4].set(0.0)
    xq, a = quantize_activations(x)
    y_q = pvq_matmul_q(xq, w, s, a, group=group, interpret=True)
    bound = act_matmul_error_bound(a, w, s, group)
    assert float(jnp.max(jnp.abs(y_q[2]))) == 0.0
    assert float(jnp.max(jnp.abs(y_q[4]))) == 0.0
    assert float(jnp.max(bound[2])) == 0.0
    assert bool(jnp.all(jnp.isfinite(y_q)))


def test_kernel_v3_epilogue_bias_activation():
    """bias + relu fuse into the v3 epilogue AFTER the act_scale multiply;
    relu is 1-Lipschitz so the pre-activation bound survives."""
    m, k, n, group = 8, 128, 96, 64
    x, w, s = _problem(6, m, k, n, group)
    bias = jax.random.normal(jax.random.PRNGKey(7), (n,))
    xq, a = quantize_activations(x)
    y_f = jax.nn.relu(pvq_matmul_ref(x, w, s, group=group) + bias)
    y_q = pvq_matmul_q(
        xq, w, s, a, bias, group=group, activation="relu", interpret=True
    )
    bound = act_matmul_error_bound(a, w, s, group)
    assert bool(jnp.all(jnp.abs(y_q - y_f) <= bound + 1e-5))


def test_kernel_v3_many_groups_batched_fallback():
    """Beyond _MAX_UNROLL_GROUPS per k-tile the body switches to one batched
    int8 x int8 dot_general — still integer feeds, same numbers."""
    m, k, n, group = 4, 1280, 64, 128  # 10 groups in one bk=1280 tile
    x, w, s = _problem(8, m, k, n, group)
    xq, a = quantize_activations(x)
    y_big = pvq_matmul_q(xq, w, s, a, group=group, bk=1280, interpret=True)
    y_ref = (xq.astype(jnp.float32) * a) @ (
        w.astype(jnp.float32) * jnp.repeat(s, group, axis=0)
    )
    np.testing.assert_allclose(np.asarray(y_big), np.asarray(y_ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# double-buffered DMA pulse streaming
# ---------------------------------------------------------------------------


def test_dma_streaming_matches_automatic_pipeline_bit_exact():
    """Satellite: the hand-rolled make_async_copy double-buffer path runs
    the same per-chunk contraction in the same order as the automatic
    k-grid pipeline — outputs are bit-identical, with and without the
    bias/activation epilogue."""
    # group 32: a legal bk of 8 groups leaves 4 k-chunks to stream
    m, k, n, group = 8, 1024, 256, 32
    x, w, s = _problem(9, m, k, n, group)
    bias = jax.random.normal(jax.random.PRNGKey(10), (n,))
    xq, a = quantize_activations(x)
    for kwargs in (
        {},
        {"bias": bias, "activation": "relu"},
        {"activation": "silu"},
    ):
        b = kwargs.pop("bias", None)
        y_dma = pvq_matmul_q(
            xq, w, s, a, b, group=group, bk=256, dma_streaming=True,
            interpret=True, **kwargs,
        )
        y_pipe = pvq_matmul_q(
            xq, w, s, a, b, group=group, bk=256, dma_streaming=False,
            interpret=True, **kwargs,
        )
        assert bool(jnp.array_equal(y_dma, y_pipe))


def test_dma_streaming_auto_gate(monkeypatch):
    """Auto-selection: big bk*bn tiles with >= 2 k-chunks stream via DMA,
    small tiles keep the automatic pipeline, REPRO_PVQ_DMA=0 kills it."""
    from repro.kernels.pvq_matmul import _dma_streaming_wanted

    monkeypatch.delenv("REPRO_PVQ_DMA", raising=False)
    assert _dma_streaming_wanted(8, 4096, 512, 8, 512, 256)  # big FFN shape
    assert not _dma_streaming_wanted(8, 256, 128, 8, 128, 128)  # small tile
    assert not _dma_streaming_wanted(8, 512, 512, 8, 512, 512)  # 1 chunk
    monkeypatch.setenv("REPRO_PVQ_DMA", "0")
    assert not _dma_streaming_wanted(8, 4096, 512, 8, 512, 256)


# ---------------------------------------------------------------------------
# ops dispatch: pre-quantized contract + batched expert entry
# ---------------------------------------------------------------------------


def test_ops_prequantized_act_scale_contract():
    """act_scale marks x as already-quantized: same result as act_quant,
    and a float x with act_scale is rejected."""
    m, k, n, group = 5, 128, 64, 64
    x, w, s = _problem(11, m, k, n, group)
    xq, a = quantize_activations(x)
    y1 = ops.pvq_matmul(x, w, s, group=group, act_quant=ActQuant())
    y2 = ops.pvq_matmul(xq, w, s, group=group, act_scale=a)
    assert bool(jnp.array_equal(y1, y2))
    with pytest.raises(ValueError, match="int8"):
        ops.pvq_matmul(x, w, s, group=group, act_scale=a)


def test_packed_matmul_stacked_act_quant_matches_per_slice():
    e, m, d, f, group = 3, 6, 64, 48, 64
    w = jax.random.laplace(jax.random.PRNGKey(12), (e, d, f)) * 0.1
    bank = pack_matmul(w, group=group, n_over_k=2.0)
    x = jax.random.normal(jax.random.PRNGKey(13), (e, m, d))
    y = ops.packed_matmul_stacked(x, bank, act_quant=ActQuant())
    for i in range(e):
        sl = type(bank)(
            pulses=bank.pulses[i], scales=bank.scales[i], group=bank.group,
            k=bank.k, shape=bank.shape, dtype=bank.dtype, layout=bank.layout,
            scale_mode=bank.scale_mode,
        )
        yi = ops.packed_matmul(x[i], sl, act_quant=ActQuant())
        np.testing.assert_allclose(
            np.asarray(y[i]), np.asarray(yi), rtol=1e-5, atol=1e-6
        )


# ---------------------------------------------------------------------------
# the contract through the layers
# ---------------------------------------------------------------------------


def test_default_act_quant_scope_sets_and_restores():
    assert default_act_quant() is None
    with act_quant_scope(ActQuant(mode="per_tensor")) as aq:
        assert default_act_quant() is aq
        with act_quant_scope(None):
            assert default_act_quant() is None
        assert default_act_quant() is aq
    assert default_act_quant() is None


def test_pvq_dense_act_quant_close_to_f32_path():
    from repro.nn.layers import dense, init_dense, pvq_quantize_dense

    p = init_dense(jax.random.PRNGKey(14), 96, 64, bias=True)
    q = pvq_quantize_dense(p, group=32, k_pulses=16)
    x = jax.random.normal(jax.random.PRNGKey(15), (5, 96))
    y_f = dense(q, x)
    with act_quant_scope(ActQuant()):
        y_q = dense(q, x)
    rel = float(jnp.linalg.norm(y_q - y_f) / jnp.linalg.norm(y_f))
    assert rel < 0.05
    # explicit kwarg wins over the (unset) process default
    y_kw = dense(q, x, act_quant=ActQuant())
    assert bool(jnp.array_equal(y_kw, y_q))


def test_unembed_act_quant_integer_logits_close():
    from repro.core.packed import pack_flat
    from repro.nn.layers import unembed

    table = jax.random.normal(jax.random.PRNGKey(16), (64, 32)) * 0.02
    p = {"embedding": pack_flat(table, group=32, k=16, row_align=32)}
    x = jax.random.normal(jax.random.PRNGKey(17), (2, 3, 32))
    lo_f = unembed(p, x)
    lo_q = unembed(p, x, act_quant=ActQuant())
    rel = float(jnp.linalg.norm(lo_q - lo_f) / jnp.linalg.norm(lo_f))
    assert rel < 0.05
    assert lo_q.dtype == jnp.float32


def test_sequential_kernel_apply_act_quant():
    from repro.nn.sequential import LayerSpec, SequentialConfig, SequentialNet

    cfg = SequentialConfig(
        name="tiny",
        input_shape=(64,),
        layers=(
            LayerSpec(kind="fc", out=48, activation="relu", n_over_k=2.0),
            LayerSpec(kind="fc", out=10, activation="none", n_over_k=2.0),
        ),
    )
    net = SequentialNet(cfg)
    params = net.init(jax.random.PRNGKey(18))
    kparams = net.pvq_kernel_encode(params, group=64)
    x = jax.random.normal(jax.random.PRNGKey(19), (4, 64))
    y_f = net.kernel_apply(params, kparams, x)
    y_q = net.kernel_apply(params, kparams, x, act_quant=ActQuant())
    rel = float(jnp.linalg.norm(y_q - y_f) / jnp.maximum(jnp.linalg.norm(y_f), 1e-9))
    assert rel < 0.1


# ---------------------------------------------------------------------------
# MoE: quantize the dispatch buffer once, reuse across the expert matmuls
# ---------------------------------------------------------------------------

MOE_POLICY = QuantPolicy(rules=(("kernel|experts", 2.0, 64),), scale_mode="ls")


def _moe_cfg():
    from repro.nn.moe import MoEConfig

    return MoEConfig(
        n_experts=4, top_k=2, n_shared=0, d_expert=32, capacity_factor=2.0,
        group_size=32, activation="swiglu",
    )


def test_moe_forward_packed_act_quant_matches_dense():
    """Acceptance: packed-vs-dense MoE forward equivalence with activation
    quantization enabled on the dispatch buffer (routing is identical; the
    only deltas are PVQ weights + int8 activations, both bounded)."""
    from repro.nn.moe import init_moe, moe_forward

    cfg = _moe_cfg()
    p = init_moe(jax.random.PRNGKey(20), 16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(21), (2, 32, 16))
    q = quantize_params(p, MOE_POLICY)
    out_dq, aux_dq = moe_forward(dequantize_params(q), x, cfg)
    with act_quant_scope(ActQuant()):
        out_q, aux_q = moe_forward(q, x, cfg)
    # routing consumes raw f32 logits — aux loss must be bit-comparable
    assert float(aux_q) == pytest.approx(float(aux_dq), rel=1e-6)
    np.testing.assert_allclose(
        np.asarray(out_q), np.asarray(out_dq), rtol=0.15, atol=0.05
    )
    # and the act-quant delta on top of the packed path is small
    out_pk, _ = moe_forward(q, x, cfg)
    rel = float(
        jnp.linalg.norm(out_q - out_pk) / jnp.maximum(jnp.linalg.norm(out_pk), 1e-9)
    )
    assert rel < 0.05


def test_moe_dispatch_buffer_quantized_once():
    """The quantize-once contract: up and gate reuse ONE (int8 buffer,
    scales) pair — quantize_activations runs twice per forward (dispatch
    buffer + hidden h), not three times."""
    from repro.core import quantize as qz
    from repro.nn.moe import init_moe, moe_forward

    cfg = _moe_cfg()
    p = init_moe(jax.random.PRNGKey(22), 16, cfg)
    q = quantize_params(p, MOE_POLICY)
    x = jax.random.normal(jax.random.PRNGKey(23), (2, 32, 16))
    calls = []
    orig = qz.quantize_activations

    def counting(xx, aq=ActQuant()):
        calls.append(xx.shape)
        return orig(xx, aq)

    qz.quantize_activations = counting
    try:
        with act_quant_scope(ActQuant()):
            moe_forward(q, x, cfg)
    finally:
        qz.quantize_activations = orig
    assert len(calls) == 2, calls  # dispatch buffer once + h once


def test_moe_dense_bank_ignores_act_quant():
    """Dense (unpacked) expert banks have no integer operand to pair with —
    the contract is a no-op there, bit-identical outputs."""
    from repro.nn.moe import init_moe, moe_forward

    cfg = _moe_cfg()
    p = init_moe(jax.random.PRNGKey(24), 16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(25), (2, 32, 16))
    out_f, _ = moe_forward(p, x, cfg)
    with act_quant_scope(ActQuant()):
        out_q, _ = moe_forward(p, x, cfg)
    assert bool(jnp.array_equal(out_f, out_q))


# ---------------------------------------------------------------------------
# serve-side agreement probe
# ---------------------------------------------------------------------------


def test_top1_agreement_metric():
    from repro.launch.serve import top1_agreement

    a = jnp.array([[[1.0, 0.5, 0.0], [1.0, 0.995, 0.0]]])
    # identical -> 1.0 strict
    ag = top1_agreement(a, a)
    assert ag["top1_agreement"] == 1.0 and ag["top1_agreement_strict"] == 1.0
    # second position flips a genuine near-tie (margin 0.005, within both
    # the measured noise and 5% of the logit spread) -> excused
    b = a.at[0, 1, 1].add(0.02)
    ag = top1_agreement(a, b)
    assert ag["top1_agreement_strict"] == 0.5
    assert ag["top1_agreement"] == 1.0 and ag["ties_excused"] == 1
    # a clearly-separated pick flipped by a gross perturbation is NEVER
    # excused, however large the perturbation (no laundering a broken kernel)
    c = a.at[0, 0, :].set(jnp.array([0.0, 2.0, 0.0]))
    ag = top1_agreement(a, c)
    assert ag["top1_agreement"] == 0.5 and ag["ties_excused"] == 0
