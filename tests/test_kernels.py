"""Per-kernel interpret=True validation against the pure-jnp oracles,
with explicit shape/dtype grids + hypothesis sweeps.

Encoder contract (since the sort-based rewrite): bit-exact vs the greedy
oracle whenever the floor pre-allocation leaves <= delta_max pulses (always
for K <= delta_max), else within 1e-3 cosine correlation; the L1 = K pyramid
constraint is always exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops
from repro.kernels.ref import pvq_encode_ref, pvq_matmul_ref


def _mk_pvq_weight(key, k_dim, n_dim, group, k_pulses):
    """A real PVQ-coded weight matrix: (pulses int8 (k,n), scales (k/group, n))."""
    w = jax.random.laplace(key, (k_dim, n_dim))
    wt = w.T.reshape(n_dim, k_dim // group, group)
    from repro.core.pvq import pvq_encode

    code = pvq_encode(wt, k_pulses, "ls")  # (n, k/group, group)
    pulses = jnp.transpose(code.pulses, (1, 2, 0)).reshape(k_dim, n_dim).astype(jnp.int8)
    scales = jnp.transpose(code.scale, (1, 0)).astype(jnp.float32)  # (k/group, n)
    return pulses, scales


def _row_corr(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    den = np.sqrt((a * a).sum(-1) * (b * b).sum(-1))
    den = np.where(den > 0, den, 1.0)
    return (a * b).sum(-1) / den


# ---------------------------------------------------------------------------
# pvq_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "m,k,n,group,bm,bn,bk",
    [
        (8, 128, 128, 128, 8, 128, 128),      # decode GEMV-ish tile
        (128, 256, 128, 128, 128, 128, 128),  # two k-tiles (accumulation)
        (16, 256, 512, 64, 16, 256, 128),     # group < bk, wide n
        (32, 512, 64, 128, 32, 64, 256),      # bk > group multiple tiles
    ],
)
def test_pvq_matmul_matches_ref(m, k, n, group, bm, bn, bk):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    pulses, scales = _mk_pvq_weight(kw, k, n, group, k_pulses=group // 2)
    got = ops.pvq_matmul(x, pulses, scales, group=group, bm=bm, bn=bn, bk=bk, interpret=True)
    want = pvq_matmul_ref(x, pulses, scales, group=group)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pvq_matmul_dtypes(dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(kx, (16, 128), jnp.float32).astype(dtype)
    pulses, scales = _mk_pvq_weight(kw, 128, 128, 128, k_pulses=64)
    got = ops.pvq_matmul(x, pulses, scales, group=128, bm=16, interpret=True)
    want = pvq_matmul_ref(x, pulses, scales, group=128)
    assert got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=1e-1
    )


@pytest.mark.parametrize(
    "m,k,n,group",
    [
        (5, 384, 257, 128),   # every dim ragged vs 128-tiles
        (3, 128, 100, 64),    # tiny decode batch, narrow n
        (17, 640, 130, 128),  # k not a bk multiple
    ],
)
def test_pvq_matmul_ragged_shapes(m, k, n, group):
    """Non-tile-divisible shapes pad internally instead of asserting."""
    kx, kw = jax.random.split(jax.random.PRNGKey(m * n))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    pulses, scales = _mk_pvq_weight(kw, k, n, group, k_pulses=group // 2)
    got = ops.pvq_matmul(x, pulses, scales, group=group, interpret=True)
    want = pvq_matmul_ref(x, pulses, scales, group=group)
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("activation", ["relu", "gelu", "silu", "relu2", "none"])
def test_pvq_matmul_fused_epilogue(activation):
    """bias + activation fused into the final store == unfused reference."""
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(13), 3)
    m, k, n, group = 16, 256, 128, 128
    x = jax.random.normal(kx, (m, k), jnp.float32)
    pulses, scales = _mk_pvq_weight(kw, k, n, group, k_pulses=64)
    bias = jax.random.normal(kb, (n,))
    got = ops.pvq_matmul(
        x, pulses, scales, group=group, bias=bias, activation=activation, interpret=True
    )
    pre = pvq_matmul_ref(x, pulses, scales, group=group) + bias
    from repro.kernels.pvq_matmul import _apply_activation

    want = _apply_activation(pre, activation)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    mt=st.integers(1, 3), kt=st.integers(1, 3), nt=st.integers(1, 2),
    seed=st.integers(0, 2**31 - 1),
)
def test_prop_pvq_matmul_tile_sweep(mt, kt, nt, seed):
    m, k, n = 8 * mt, 128 * kt, 128 * nt
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    pulses, scales = _mk_pvq_weight(kw, k, n, 128, k_pulses=32)
    got = ops.pvq_matmul(x, pulses, scales, group=128, bm=8, bn=128, bk=128, interpret=True)
    want = pvq_matmul_ref(x, pulses, scales, group=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# pvq_encode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("g,n,k_pulses,bg", [(8, 128, 32, 8), (4, 64, 16, 4)])
def test_pvq_encode_exact_small_k(g, n, k_pulses, bg):
    """K <= delta_max: the sorted encoder IS the exact greedy search."""
    w = jax.random.laplace(jax.random.PRNGKey(g * n), (g, n))
    got_p, got_rho = ops.pvq_encode(w, k_pulses=k_pulses, bg=bg, interpret=True)
    want_p, want_rho = pvq_encode_ref(w, k_pulses)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    np.testing.assert_allclose(np.asarray(got_rho), np.asarray(want_rho), rtol=1e-5)


@pytest.mark.parametrize("g,n,k_pulses", [(16, 256, 64), (64, 256, 128), (8, 1024, 256)])
def test_pvq_encode_matches_ref_correlation(g, n, k_pulses):
    """K > delta_max: within 1e-3 cosine of the exact greedy oracle."""
    w = jax.random.laplace(jax.random.PRNGKey(g * n), (g, n))
    got_p, got_rho = ops.pvq_encode(w, k_pulses=k_pulses, interpret=True)
    want_p, want_rho = pvq_encode_ref(w, k_pulses)
    corr = _row_corr(got_p, want_p)
    assert corr.min() > 1 - 1e-3, corr.min()
    np.testing.assert_allclose(np.asarray(got_rho), np.asarray(want_rho), rtol=2e-2)


def test_pvq_encode_exact_when_delta_max_covers_k():
    """delta_max >= K degenerates to the seed's exact greedy kernel."""
    w = jax.random.laplace(jax.random.PRNGKey(9), (8, 256))
    got_p, _ = ops.pvq_encode(w, k_pulses=96, delta_max=96, interpret=True)
    want_p, _ = pvq_encode_ref(w, 96)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))


def test_pvq_encode_l1_constraint():
    w = jax.random.laplace(jax.random.PRNGKey(3), (8, 128))
    pulses, _ = ops.pvq_encode(w, k_pulses=48, interpret=True)
    np.testing.assert_array_equal(np.abs(np.asarray(pulses)).sum(-1), 48)


def test_pvq_encode_l1_constraint_large_k():
    """The sort-based bulk allocation must land exactly on the pyramid."""
    w = jax.random.laplace(jax.random.PRNGKey(4), (16, 256))
    pulses, _ = ops.pvq_encode(w, k_pulses=192, interpret=True)
    np.testing.assert_array_equal(np.abs(np.asarray(pulses)).sum(-1), 192)


@pytest.mark.parametrize("k_pulses,delta_max", [(48, 8), (192, 16), (64, 64)])
def test_pvq_encode_kernel_bit_exact_vs_fast_twin(k_pulses, delta_max):
    """The Pallas encoder (threshold bisection + MXU prefix count in place of
    a sort) reproduces ``core.pvq.pvq_quantize_direction_fast`` bit-for-bit,
    including fractional-part ties, which quantized weights force below."""
    from repro.core.pvq import pvq_quantize_direction_fast
    from repro.kernels.pvq_encode import pvq_encode_batch

    for seed in range(3):
        w = jnp.round(jax.random.laplace(jax.random.PRNGKey(seed), (16, 128)) * 4) / 4
        got, _ = pvq_encode_batch(
            w, k_pulses=k_pulses, delta_max=delta_max, interpret=True
        )
        want = pvq_quantize_direction_fast(w, k_pulses, delta_max=delta_max)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [64, 256])
def test_bulk_mask_prefix_count_matches_stable_sort(n):
    """``_bulk_mask`` selects exactly the ``bulk`` largest fracs per row with
    ties going to the lower lane: the stable-descending-sort selection,
    checked against numpy on rows built to be full of ties."""
    from repro.kernels.pvq_encode import _bulk_mask

    rng = np.random.default_rng(n)
    frac = (rng.integers(0, 6, (8, n)) / 8).astype(np.float32)
    bulk = rng.integers(0, n + 1, (8, 1)).astype(np.int32)
    got = np.asarray(_bulk_mask(jnp.asarray(frac), jnp.asarray(bulk)))
    order = np.argsort(-frac, axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1, kind="stable")
    np.testing.assert_array_equal(got, (rank < bulk).astype(np.float32))


def test_pvq_encode_zero_rows():
    w = jnp.zeros((8, 128))
    pulses, rho = ops.pvq_encode(w, k_pulses=16, interpret=True)
    assert int(jnp.abs(pulses).sum()) == 0
    np.testing.assert_array_equal(np.asarray(rho), 0.0)


def test_pvq_encode_row_padding():
    """Group counts that don't tile by bg are padded, not asserted."""
    w = jax.random.laplace(jax.random.PRNGKey(5), (5, 128))
    pulses, rho = ops.pvq_encode(w, k_pulses=32, bg=8, interpret=True)
    assert pulses.shape == (5, 128) and rho.shape == (5,)
    np.testing.assert_array_equal(np.abs(np.asarray(pulses)).sum(-1), 32)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    k_pulses=st.integers(1, 96),
)
def test_prop_pvq_encode_sweep(seed, k_pulses):
    w = jax.random.laplace(jax.random.PRNGKey(seed), (8, 128))
    got_p, got_rho = ops.pvq_encode(w, k_pulses=k_pulses, interpret=True)
    want_p, want_rho = pvq_encode_ref(w, k_pulses)
    np.testing.assert_array_equal(np.abs(np.asarray(got_p)).sum(-1), k_pulses)
    if k_pulses <= 32:  # delta_max default: bit-exact regime
        np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
        np.testing.assert_allclose(np.asarray(got_rho), np.asarray(want_rho), rtol=1e-4)
    else:
        assert _row_corr(got_p, want_p).min() > 1 - 1e-3


# ---------------------------------------------------------------------------
# int8 pulse boundary + encode -> matmul round-trip
# ---------------------------------------------------------------------------


def test_pulses_to_int8_clamps():
    p = jnp.array([[-300, -128, -1, 0, 1, 127, 300]], jnp.int32)
    q = ops.pulses_to_int8(p)
    assert q.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q)[0], [-127, -127, -1, 0, 1, 127, 127])


@pytest.mark.parametrize("k_dim,n_dim,group", [(256, 64, 128), (200, 96, 64)])
def test_encode_matmul_roundtrip(k_dim, n_dim, group):
    """encode_weight_matrix -> pvq_matmul composes with no caller-side casts
    and equals the explicit dequantized matmul (incl. ragged k padding)."""
    w = jax.random.laplace(jax.random.PRNGKey(11), (k_dim, n_dim)) * 0.1
    pulses, scales, k_pad = ops.encode_weight_matrix(
        w, group=group, k_pulses=group // 4, interpret=True
    )
    assert pulses.dtype == jnp.int8
    assert pulses.shape == (k_pad, n_dim) and k_pad % group == 0
    x = jax.random.normal(jax.random.PRNGKey(12), (8, k_dim))
    xp = jnp.pad(x, ((0, 0), (0, k_pad - k_dim)))
    y = ops.pvq_matmul(xp, pulses, scales, group=group, interpret=True)
    w_deq = pulses.astype(jnp.float32) * jnp.repeat(scales, group, axis=0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(xp @ w_deq), rtol=1e-5, atol=1e-4)
    # padded tail rows never receive pulses
    assert int(jnp.abs(pulses[k_dim:]).sum()) == 0


# ---------------------------------------------------------------------------
# end-to-end: kernel-format weights == core dequantized matmul
# ---------------------------------------------------------------------------


def test_kernel_weights_equal_core_dequant():
    """pvq_matmul on kernel-format tensors must equal x @ dequant(core code)."""
    key = jax.random.PRNGKey(11)
    kx, kw = jax.random.split(key)
    k_dim, n_dim, group = 256, 128, 128
    x = jax.random.normal(kx, (8, k_dim))
    pulses, scales = _mk_pvq_weight(kw, k_dim, n_dim, group, k_pulses=64)
    y_kernel = ops.pvq_matmul(x, pulses, scales, group=group, bm=8, interpret=True)
    w_deq = pulses.astype(jnp.float32) * jnp.repeat(scales, group, axis=0)
    np.testing.assert_allclose(
        np.asarray(y_kernel), np.asarray(x @ w_deq), rtol=1e-5, atol=1e-4
    )


def test_sequential_kernel_apply_matches_dequant_forward():
    """SequentialNet.kernel_apply (fused Pallas fc path) == manual forward
    with the dequantized kernel-format weights."""
    from repro.nn.sequential import LayerSpec, SequentialConfig, SequentialNet

    cfg = SequentialConfig(
        name="tiny",
        input_shape=(100,),
        layers=(
            LayerSpec(kind="fc", out=72, activation="relu", n_over_k=2.0),
            LayerSpec(kind="fc", out=10, activation="none", n_over_k=1.0),
        ),
    )
    net = SequentialNet(cfg)
    params = net.init(jax.random.PRNGKey(0))
    group = 64
    kparams = net.pvq_kernel_encode(params, group=group)
    assert set(kparams) == {"layer0", "layer1"}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 100))
    got = net.kernel_apply(params, kparams, x, group=group)

    h = x
    for i, spec in enumerate(cfg.layers):
        packed = kparams[f"layer{i}"]["kernel"]  # the unified PackedPVQ artifact
        w_deq = packed.pulses.astype(jnp.float32) * jnp.repeat(
            packed.scales, packed.group, axis=0
        )
        hp = jnp.pad(h, ((0, 0), (0, w_deq.shape[0] - h.shape[-1])))
        pre = hp @ w_deq + params[f"layer{i}"]["bias"]
        h = jax.nn.relu(pre) if spec.activation == "relu" else pre
    np.testing.assert_allclose(np.asarray(got), np.asarray(h), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,k,n,group", [(8, 512, 384, 64), (16, 1024, 256, 256)])
def test_matmul_q_kernel_matches_ref_oracle(m, k, n, group):
    """Kernel v3 against ``ref.pvq_matmul_q_ref`` (the oracle the chip
    smoke uses): int8 x int8 dots are exact in int32, so only the f32
    scale multiplies differ."""
    from repro.kernels.pvq_matmul import pvq_matmul_q
    from repro.kernels.ref import pvq_matmul_q_ref

    kx, kw, ks = jax.random.split(jax.random.PRNGKey(m + k + n), 3)
    x_q = jax.random.randint(kx, (m, k), -127, 128, jnp.int8)
    a = jnp.full((m, 1), 0.01, jnp.float32)
    w = jax.random.randint(kw, (k, n), -4, 5, jnp.int8)
    s = jnp.abs(jax.random.normal(ks, (k // group, n))) * 0.05
    y = pvq_matmul_q(x_q, w, s, a, group=group, interpret=True)
    ref = pvq_matmul_q_ref(x_q, w, s, a, group=group)
    assert float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref))) <= 1e-4


@pytest.mark.parametrize("bh,m,s", [(6, 3, 96), (2, 24, 200)])
def test_attn_q_kernel_matches_ref_oracle(bh, m, s):
    """Kernel v4 against ``ref.pvq_attn_q_ref``: the running max and
    denominator to f32 rounding, the output within the int8 requantization
    of the probabilities (3% of max |V|), and an empty row (kv_len 0)
    giving m = -1e30, l = 0."""
    from repro.kernels.pvq_matmul import pvq_attn_q
    from repro.kernels.ref import pvq_attn_q_ref

    hd, group = 64, 32
    ks = jax.random.split(jax.random.PRNGKey(bh * m + s), 7)
    q = jax.random.randint(ks[0], (bh, m, hd), -127, 128, jnp.int8)
    a = jnp.abs(jax.random.normal(ks[1], (bh, m, 1))) * 0.02
    kp = jax.random.randint(ks[2], (bh, s, hd), -6, 7, jnp.int8)
    vp = jax.random.randint(ks[3], (bh, s, hd), -6, 7, jnp.int8)
    k_s = jnp.abs(jax.random.normal(ks[4], (bh, s, hd // group))) * 0.1
    v_s = jnp.abs(jax.random.normal(ks[5], (bh, s, hd // group))) * 0.1
    kv_len = jax.random.randint(ks[6], (bh,), 1, s + 1).at[0].set(0)
    args = (q, a, kp, k_s, vp, v_s, kv_len)
    acc, mx, l = pvq_attn_q(*args, group=group, sm_scale=0.125, interpret=True)
    r_acc, r_mx, r_l = pvq_attn_q_ref(*args, group=group, sm_scale=0.125)
    np.testing.assert_allclose(np.asarray(mx), np.asarray(r_mx), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(l), np.asarray(r_l), rtol=1e-4, atol=0)
    assert float(l[0].max()) == 0.0 and float(mx[0].max()) == np.float32(-1e30)
    v_max = float(jnp.max(jnp.abs(vp * jnp.repeat(v_s, group, -1))))
    live = slice(1, None)
    err = np.abs(acc[live] / l[live] - r_acc[live] / r_l[live]).max() / v_max
    assert err <= 3e-2, err
