"""Pure-jnp oracles for the Pallas kernels (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def pvq_matmul_ref(
    x: jax.Array,  # (m, k) activations
    w_pulses: jax.Array,  # (k, n) int8 PVQ pulses
    scales: jax.Array,  # (k // group, n) f32 per-group rho
    *,
    group: int,
) -> jax.Array:
    """y = x @ (scales-expanded * pulses). Groups tile the contraction dim."""
    k, n = w_pulses.shape
    assert k % group == 0
    w = w_pulses.astype(jnp.float32) * jnp.repeat(scales, group, axis=0)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def pvq_encode_ref(w: jax.Array, k_pulses: int) -> tuple[jax.Array, jax.Array]:
    """Batched exact greedy PVQ projection; returns (pulses i32 (g,n), rho_ls f32 (g,)).

    Same algorithm as repro.core.pvq (presearch + greedy top-up), kept
    dependency-free here as the kernel oracle.
    """
    from repro.core.pvq import _greedy_topup, _presearch, _scales

    absw = jnp.abs(w.astype(jnp.float32))
    y = _presearch(absw, k_pulses)
    y = _greedy_topup(absw, y, k_pulses)
    pulses = (jnp.sign(w) * y).astype(jnp.int32)
    rho = _scales(w, pulses, "ls")
    return pulses, rho


def pvq_matmul_q_ref(
    x_q: jax.Array,  # (m, k) int8 quantized activations
    w_pulses: jax.Array,  # (k, n) int8 PVQ pulses
    scales: jax.Array,  # (k // group, n) f32 per-group rho
    act_scale: jax.Array,  # (m, 1) f32 per-row activation scale
    *,
    group: int,
) -> jax.Array:
    """Kernel v3 oracle: ``act_scale * (x_q @ (pulses * rho))`` in f32."""
    x = x_q.astype(jnp.float32) * act_scale.astype(jnp.float32)
    return pvq_matmul_ref(x, w_pulses, scales, group=group)


def pvq_attn_q_ref(
    q_i8: jax.Array,  # (BH, m, hd) int8 quantized queries
    act_scale: jax.Array,  # (BH, m, 1) f32 per-row query scales
    k_pulses: jax.Array,  # (BH, S, hd) int8
    k_scales: jax.Array,  # (BH, S, hd // group) f32
    v_pulses: jax.Array,  # (BH, S, hd) int8
    v_scales: jax.Array,  # (BH, S, hd // group) f32
    kv_len: jax.Array,  # (BH,) int32 valid positions per row
    *,
    group: int,
    sm_scale: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel v4 oracle in f32: softmax attention over the dequantized
    planes, returned as the kernel's UNNORMALIZED ``(acc, m, l)`` triple.
    Rows with ``kv_len == 0`` give ``m = -1e30`` and ``l = acc = 0``."""
    def deq(p, s):
        return p.astype(jnp.float32) * jnp.repeat(s.astype(jnp.float32), group, axis=-1)

    q = q_i8.astype(jnp.float32) * act_scale.astype(jnp.float32)
    scores = jnp.einsum("bmd,bsd->bms", q, deq(k_pulses, k_scales)) * sm_scale
    valid = jnp.arange(k_pulses.shape[1])[None, None, :] < kv_len[:, None, None]
    scores = jnp.where(valid, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(scores - m), 0.0)
    acc = jnp.einsum("bms,bsd->bmd", p, deq(v_pulses, v_scales))
    return acc, m, jnp.sum(p, axis=-1, keepdims=True)
