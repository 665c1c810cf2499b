"""PVQ gradient compression: channel properties, error feedback, wire bytes,
and convergence parity on a toy problem."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import AdamW
from repro.optim.grad_compress import (
    CompressionConfig,
    compress_decompress,
    cross_pod_mean,
    make_ef_compressor,
    wire_bytes,
)


def test_channel_preserves_direction_energy():
    cfg = CompressionConfig(group=256, n_over_k=2.0)
    g = jax.random.laplace(jax.random.PRNGKey(0), (4096,))
    q = compress_decompress(g, cfg)
    cos = jnp.sum(g * q) / (jnp.linalg.norm(g) * jnp.linalg.norm(q))
    assert float(cos) > 0.85


def test_channel_exact_as_k_grows():
    g = jax.random.laplace(jax.random.PRNGKey(1), (2048,))
    errs = []
    for n_over_k in (8.0, 2.0, 0.25):
        cfg = CompressionConfig(group=256, n_over_k=n_over_k)
        q = compress_decompress(g, cfg)
        errs.append(float(jnp.linalg.norm(q - g) / jnp.linalg.norm(g)))
    assert errs == sorted(errs, reverse=True)
    assert errs[-1] < 0.08  # K = 4*group -> a few % error


def test_small_leaves_pass_through():
    cfg = CompressionConfig(min_size=1024)
    g = jnp.ones(10)
    np.testing.assert_array_equal(np.asarray(compress_decompress(g, cfg)), np.ones(10))


def test_error_feedback_unbiased_mean():
    """With EF, the time-average of the decoded gradients approaches the true
    gradient (compression error does not accumulate)."""
    cfg = CompressionConfig(group=128, n_over_k=8.0)  # aggressive compression
    init, apply = make_ef_compressor(cfg)
    g_true = {"w": jax.random.laplace(jax.random.PRNGKey(2), (1024,))}
    ef = init(g_true)
    acc = jnp.zeros(1024)
    n = 120
    for _ in range(n):
        dec, ef = apply(g_true, ef)
        acc = acc + dec["w"]
    mean_dec = acc / n
    rel = float(jnp.linalg.norm(mean_dec - g_true["w"]) / jnp.linalg.norm(g_true["w"]))
    assert rel < 0.05  # O(1/n): error feedback does not accumulate bias


def test_wire_bytes_ratio():
    cfg = CompressionConfig(group=256, n_over_k=2.0)
    grads = {"a": jnp.zeros((1024, 64)), "b": jnp.zeros(128)}
    comp, raw = wire_bytes(grads, cfg)
    assert raw == 4 * (1024 * 64 + 128)
    # large leaf ~1.016 B/val, small leaf uncompressed
    assert comp < 0.3 * raw


def test_cross_pod_mean_matches_pmean_at_high_k():
    """shard_map over a 1-axis mesh: compressed mean ~= exact mean."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices()[:1]).reshape(1)
    mesh = Mesh(devs, ("pod",))
    cfg = CompressionConfig(group=128, n_over_k=0.25, min_size=128)  # K=4N: near-exact
    g = jax.random.laplace(jax.random.PRNGKey(3), (1, 2048))

    f = shard_map(
        lambda x: cross_pod_mean({"g": x[0]}, cfg, axis="pod")["g"][None],
        mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
    )
    out = f(g)
    rel = float(jnp.linalg.norm(out[0] - g[0]) / jnp.linalg.norm(g[0]))
    assert rel < 0.08  # K=4N channel error, no extra loss from the gather path


def test_compressed_training_converges():
    """AdamW + EF-compressed grads reaches (near) the uncompressed loss."""
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (256, 32))
    w_true = jax.random.laplace(jax.random.PRNGKey(5), (32,))
    y = x @ w_true

    def loss_fn(w):
        return jnp.mean((x @ w - y) ** 2)

    def train(compressed: bool, steps=150):
        opt = AdamW(lr=3e-2, weight_decay=0.0)
        w = {"w": jnp.zeros(32)}
        st = opt.init(w)
        cfg = CompressionConfig(group=32, n_over_k=2.0, min_size=16)
        init, apply = make_ef_compressor(cfg)
        ef = init(w)
        for _ in range(steps):
            g = jax.grad(lambda p: loss_fn(p["w"]))(w)
            if compressed:
                g, ef = apply(g, ef)
            w, st, _ = opt.update(g, st, w)
        return float(loss_fn(w["w"]))

    l_plain = train(False)
    l_comp = train(True)
    assert l_comp < 10 * max(l_plain, 1e-6) + 1e-3
