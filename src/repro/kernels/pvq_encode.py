"""Batched PVQ encoding Pallas TPU kernel (largest-remainder + bounded greedy).

The paper needed a CUDA implementation to PVQ-encode million-dimensional
layers with the exact greedy O(NK) search; the follow-up work (PVQ for LLMs,
van der Ouderaa et al. 2024) observes that floor allocation + largest-
remainder completion reaches the same pyramid point up to a bounded
correction.  This kernel implements that fast path:

  1. floor-init:  y = floor(K * |w| / ||w||_1)              (O(N))
  2. largest-remainder: give all but the last ``delta_max`` missing pulses to
     the coordinates with the biggest fractional parts (a 32-step threshold
     bisection, O(32 N), in place of a sort)
  3. bounded greedy correction: place the final ``min(remaining, delta_max)``
     pulses with the exact cosine-maximizing argmax step (O(N * delta_max))

The L1 = K pyramid constraint is exact by construction; the output matches the
exact greedy search bit-for-bit whenever the floor allocation leaves at most
``delta_max`` pulses (always true for K <= delta_max, and the common case for
K >> N), and within ~1e-4 cosine correlation otherwise.  The exact oracle
stays in ``repro.kernels.ref`` / ``repro.core.pvq``.

The flattened weight vector is viewed as G groups of N dims, a tile of BG
groups is held in VMEM, and every step is vectorized across the N lanes and
BG sublanes.  Used by: offline weight encoding, the QAT projection step, and
the gradient compressor's hot path (via ``kernels.ops``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bump on material kernel-body changes — the encoder autotune cache keys
#: carry ``ekv{ENCODE_KERNEL_VERSION}`` so stale (bg, delta_max) timings miss.
ENCODE_KERNEL_VERSION = 2  # v2: bisect bulk mask, MXU prefix count


def _bulk_mask(frac, bulk):
    """0/1 mask of the ``bulk`` (bg, 1) largest fracs per row, ties -> lower
    lane (the stable-descending-sort selection), computed without a sort:
    threshold-count binary search over the IEEE bit patterns of ``frac``
    (>= 0, so int32 bit patterns order like the floats).

    Mosaic lowers neither ``sort`` nor ``cumsum`` nor integer lane
    reductions, so counts are f32 sums (exact below 2**24 lanes) and the
    tie ranks come from the MXU: ``eq @ U`` with ``U`` the upper-triangular
    ones matrix is the inclusive prefix count of ``eq`` along the lanes.
    """
    fb = jax.lax.bitcast_convert_type(frac.astype(jnp.float32), jnp.int32)
    r = bulk.astype(jnp.float32)
    lo = jnp.full_like(fb[:, :1], -1)
    hi = jnp.full_like(fb[:, :1], 0x7F7FFFFF)

    def count(mask):
        return jnp.sum(mask.astype(jnp.float32), axis=-1, keepdims=True)

    def body(_, state):
        # invariant: count(fb > lo) > r fails, count(fb > hi) <= r holds
        lo, hi = state
        mid = lo + (hi - lo) // 2
        ok = count(fb > mid) <= r
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    _, hi = jax.lax.fori_loop(0, 32, body, (lo, hi))
    gt = fb > hi
    extra = r - count(gt)
    eq = fb == hi
    n = frac.shape[-1]
    upper = (
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    ).astype(jnp.float32)
    eq_rank = jnp.dot(
        eq.astype(jnp.float32), upper, preferred_element_type=jnp.float32
    )
    return (gt | (eq & (eq_rank <= extra))).astype(jnp.float32)


def _kernel(w_ref, p_ref, rho_ref, *, k_pulses: int, delta_max: int):
    # every per-row quantity stays (bg, 1): Mosaic keeps 2-D layouts
    w = w_ref[...].astype(jnp.float32)  # (bg, n)
    bg, n = w.shape
    absw = jnp.abs(w)
    l1 = jnp.sum(absw, axis=-1, keepdims=True)
    safe = jnp.where(l1 > 0, l1, 1.0)
    target = absw * (k_pulses / safe)  # real-valued pyramid allocation
    y = jnp.where(l1 > 0, jnp.floor(target), 0.0)

    # ---- largest-remainder bulk allocation
    remaining = (k_pulses - jnp.sum(y, axis=-1, keepdims=True)).astype(jnp.int32)
    bulk = jnp.maximum(remaining - delta_max, 0)
    frac = target - y
    bump = _bulk_mask(frac, bulk)
    y = y + jnp.where(l1 > 0, bump, 0.0)

    # ---- bounded greedy correction: exact argmax placement of the last few
    corr = jnp.sum(absw * y, axis=-1, keepdims=True)
    energy = jnp.sum(y * y, axis=-1, keepdims=True)
    remaining = jnp.minimum(remaining, delta_max)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bg, n), 1).astype(jnp.float32)

    def body(_, state):
        y, corr, energy, remaining = state
        num = (corr + absw) ** 2
        den = energy + 2.0 * y + 1.0
        score = num / den
        best = jnp.max(score, axis=-1, keepdims=True)
        # first-lane-wins one-hot of the argmax (lane ids exact in f32)
        first = jnp.min(
            jnp.where(score == best, lanes, float(n)), axis=-1, keepdims=True
        )
        do = (remaining > 0).astype(jnp.float32)
        upd = (lanes == first).astype(jnp.float32) * do
        y = y + upd
        corr = corr + jnp.sum(absw * upd, axis=-1, keepdims=True)
        energy = energy + jnp.sum((2.0 * y - 1.0) * upd, axis=-1, keepdims=True)
        remaining = remaining - (remaining > 0).astype(jnp.int32)
        return (y, corr, energy, remaining)

    n_iter = min(delta_max, k_pulses)
    y, _, _, _ = jax.lax.fori_loop(0, n_iter, body, (y, corr, energy, remaining))
    pulses = jnp.sign(w) * y
    p_ref[...] = pulses.astype(jnp.int32)
    ynorm2 = jnp.sum(pulses * pulses, axis=-1, keepdims=True)
    rho = jnp.sum(w * pulses, axis=-1, keepdims=True) / jnp.where(
        ynorm2 > 0, ynorm2, 1.0
    )
    rho_ref[...] = jnp.where(ynorm2 > 0, jnp.maximum(rho, 0.0), 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("k_pulses", "bg", "delta_max", "interpret"),
)
def pvq_encode_batch(
    w: jax.Array,  # (g, n) f32/bf16 groups to encode
    *,
    k_pulses: int,
    bg: int = 8,
    delta_max: int = 32,
    interpret: bool = False,
):
    """Returns (pulses i32 (g, n), rho_ls f32 (g,)).

    ``delta_max`` bounds the exact greedy correction after the
    largest-remainder allocation; ``delta_max >= k_pulses`` degenerates to the exact greedy
    search.  Group counts that don't tile by ``bg`` are zero-padded (zero rows
    encode to zero pulses / zero rho) and sliced back.
    """
    g, n = w.shape
    bg = min(bg, g)
    pad = (-g) % bg
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad, n), w.dtype)], axis=0)
    gp = g + pad
    pulses, rho = pl.pallas_call(
        functools.partial(_kernel, k_pulses=k_pulses, delta_max=delta_max),
        grid=(gp // bg,),
        in_specs=[pl.BlockSpec((bg, n), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bg, n), lambda i: (i, 0)),
            pl.BlockSpec((bg, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((gp, n), jnp.int32),
            jax.ShapeDtypeStruct((gp, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
    )(w)
    return pulses[:g], rho[:g, 0]
