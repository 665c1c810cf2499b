"""Fused PVQ int8-native matmul Pallas TPU kernel.

Computes ``y = act(x @ (w_pulses * rho) + bias)`` where ``w_pulses`` is the
int8 PVQ pulse tensor (K-sparse per group, |pulse| small) and ``rho`` holds
one f32 scale per (contraction-group, output-column).  This is the TPU-native
form of the paper's "K-1 adds + ONE multiplication" dot product: the integer
pulse matrix streams from HBM at 1 byte/weight (2-4x less than bf16/f32 — the
win for weight-memory-bound decode/MoE ops) and feeds the MXU *as integers*.

Int8-native contraction (kernel v2): the old body materialized a dequantized
``(bk, bn)`` f32/bf16 weight tile in VMEM (``w * rho`` expanded per element)
before a single big dot.  The v2 body never builds that tile — it contracts
each ``(bm, group) x (group, bn)`` slice with the raw int8 pulses (the cast
to the MXU input dtype fuses into the matmul feed; on v5e+ the MXU consumes
int8 directly) and applies rho to the ``(bm, bn)`` f32 *accumulator*, i.e.
ONE multiply per group exactly as the paper counts it.  VMEM traffic per
tile drops by the dequantized-weight materialization (4 bytes/weight).

Quantized-activation body (kernel v3): :func:`pvq_matmul_q` takes the
activations *already quantized* to symmetric int8 (per-row scales — the
``ActQuant`` contract in ``repro.core.quantize``) and contracts int8 x tiles
against int8 pulse tiles with ``preferred_element_type=int32`` — the MXU
accumulates in int32, the paper's fully integer dot.  The group's rho then
multiplies the int32 group partial once (ONE multiply per group, unchanged
from v2) and the per-row activation scale is applied once per output element
in the epilogue (amortized over all k-groups, not per group).  No f32
activation tensor is ever fed to the MXU on this path.

Double-buffered DMA pulse streaming: for big-FFN tiles (large ``bk * bn``)
the v3 path hand-rolls the HBM->VMEM pulse transfer with
``pltpu.make_async_copy`` into a 2-deep int8 scratch — the next (bk, bn)
pulse tile lands while the MXU chews the current one.  Small tiles keep the
automatic Pallas pipeline (grid over k), which already double-buffers block
operands.

Epilogue fusion: an optional bias add and activation run inside the final
``@pl.when`` store, so a quantized dense layer costs one HBM round-trip for
the output instead of three (matmul out + bias + act).

Tiling: grid (m/bm, n/bn, k/bk); x tile (bm,bk) VMEM, w tile (bk,bn) int8
VMEM, rho tile (bk/group, bn) f32 VMEM, f32 accumulator scratch (bm,bn).
MXU-aligned defaults bm=bn=bk=128 (group must divide bk).  Non-tile-divisible
("ragged") shapes are zero-padded up to the tile grid and the output sliced
back — no caller-visible shape constraints beyond ``k % group == 0``.  Tile
sizes are normally chosen by ``repro.kernels.autotune`` via ``kernels.ops``.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bumped whenever the kernel body changes materially; feeds the autotune
#: cache key so stale tile timings from an older body never win dispatch.
KERNEL_VERSION = 4  # v4: pvq_attn_q flash decode + per-tile act scales (v3: int8 x int8 matmul body)

ACTIVATIONS = ("none", "relu", "relu2", "gelu", "silu")


def _apply_activation(y: jax.Array, activation: str) -> jax.Array:
    if activation == "none":
        return y
    if activation == "relu":
        return jax.nn.relu(y)
    if activation == "relu2":
        r = jax.nn.relu(y)
        return r * r
    if activation == "gelu":
        return jax.nn.gelu(y, approximate=True)
    if activation == "silu":
        return jax.nn.silu(y)
    raise ValueError(f"unknown activation {activation!r}; expected one of {ACTIVATIONS}")


#: beyond this many groups per k-tile the unrolled per-group dot chain costs
#: more than the dequantized-tile materialization it avoids (and bloats the
#: interpret-mode proxy); fall back to the v1 dequant-in-VMEM body there.
_MAX_UNROLL_GROUPS = 8


def _accumulate_int8(x, w, s, group: int, acc_ref) -> None:
    """Int8-native tile contraction: per group-slice, contract the raw int8
    pulses against x (the dtype convert fuses into the MXU feed — no
    dequantized (bk, bn) weight tile is ever materialized in VMEM) and apply
    the group's rho row to the f32 accumulator: ONE multiply per group."""
    bk, bn = w.shape
    n_groups = bk // group
    if n_groups > _MAX_UNROLL_GROUPS:
        # v1 fallback: one big dot on a dequantized tile — bounded unroll
        w_f = w.astype(jnp.float32).reshape(n_groups, group, bn) * s[:, None, :]
        acc_ref[...] += jax.lax.dot_general(
            x, w_f.reshape(bk, bn).astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return
    for g in range(n_groups):
        xg = x[:, g * group : (g + 1) * group]  # (bm, group)
        wg = w[g * group : (g + 1) * group, :]  # (group, bn) int8
        part = jax.lax.dot_general(
            xg, wg.astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] += part * s[g, :][None, :]


def _kernel(
    x_ref, w_ref, s_ref, o_ref, acc_ref, *, group: int, n_k: int, activation: str
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # x (bm, bk) / w (bk, bn) int8 / s (bk // group, bn) f32
    _accumulate_int8(x_ref[...], w_ref[...], s_ref[...], group, acc_ref)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = _apply_activation(acc_ref[...], activation).astype(o_ref.dtype)


def _kernel_bias(
    x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, *, group: int, n_k: int, activation: str
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate_int8(x_ref[...], w_ref[...], s_ref[...], group, acc_ref)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        y = acc_ref[...] + b_ref[...].astype(jnp.float32)  # (bm,bn) + (1,bn)
        o_ref[...] = _apply_activation(y, activation).astype(o_ref.dtype)


def normalize_tiles(
    m: int, k: int, n: int, group: int, bm: int, bn: int, bk: int
) -> tuple[int, int, int]:
    """Clamp and align a tile request to the (m,k,n,group) problem.

    bk is rounded to a multiple of ``group`` (the dequant reshape needs it);
    all tiles are clamped to the padded problem extent.  Any remainder is
    handled by zero-padding in :func:`pvq_matmul`, not by the caller.

    Every backend gets the tiles Mosaic accepts: the last two dims of a TPU
    block must be (8, 128)-aligned or span the whole array.  The scales
    block is ``(bk // group, bn)``, so a bk short of the whole contraction
    holds a multiple of 8 groups (and a multiple of 128 lanes for the x
    block) and divides ``k`` (no padded contraction); otherwise bk grows to
    the whole ``k``.
    """
    def _round_up(v: int, mult: int) -> int:
        return -(-v // mult) * mult

    # sublane (8) / lane (128) alignment: never tile wider than the padded
    # problem, never narrower than one aligned vector register row
    bm = min(_round_up(max(bm, 1), 8), _round_up(m, 8))
    bn = n if n < 128 else min(_round_up(max(bn, 1), 128), _round_up(n, 128))
    bk = max(min(bk, k), 1)
    # bk must be a group multiple for the (bk//group, group, bn) dequant view
    if bk % group:
        bk = max((bk // group) * group, min(group, k))
    step = math.lcm(8 * group, 128)
    bk = _round_up(bk, step) if bk < k else k
    while bk < k and k % bk:
        bk += step
    return bm, bn, min(bk, k)


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("group", "bm", "bn", "bk", "activation", "interpret"),
)
def pvq_matmul(
    x: jax.Array,  # (m, k)
    w_pulses: jax.Array,  # (k, n) int8
    scales: jax.Array,  # (k // group, n) f32
    bias: jax.Array | None = None,  # (n,) optional fused epilogue bias
    *,
    group: int = 128,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    activation: str = "none",
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n = w_pulses.shape
    assert k == k2, (k, k2)
    assert k % group == 0, f"contraction dim {k} must be a group ({group}) multiple"
    assert scales.shape == (k // group, n), (scales.shape, (k // group, n))
    assert activation in ACTIVATIONS, f"activation {activation!r} not in {ACTIVATIONS}"
    if bias is not None:
        assert bias.shape == (n,), (bias.shape, n)

    bm, bn, bk = normalize_tiles(m, k, n, group, bm, bn, bk)

    # Ragged shapes: zero-pad up to the tile grid, slice the output back.
    # Zero x-columns / zero pulse-rows contribute nothing to the contraction,
    # and padded n-columns are dead lanes sliced off below.
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w_pulses, 0, bk), 1, bn)
    sp = _pad_to(_pad_to(scales, 0, bk // group), 1, bn)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    n_k = kp // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((bk // group, bn), lambda i, j, kk: (kk, j)),
    ]
    operands = [xp, wp, sp]
    if bias is None:
        kernel = functools.partial(_kernel, group=group, n_k=n_k, activation=activation)
    else:
        kernel = functools.partial(
            _kernel_bias, group=group, n_k=n_k, activation=activation
        )
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(_pad_to(bias.astype(jnp.float32)[None, :], 1, bn))

    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(*operands)
    if (mp, np_) != (m, n):
        out = out[:m, :n]
    return out


@functools.partial(
    jax.jit,
    static_argnames=("group", "bm", "bn", "bk", "activation", "interpret"),
)
def pvq_matmul_batched(
    x: jax.Array,  # (B, m, k)
    w_pulses: jax.Array,  # (B, k, n) int8
    scales: jax.Array,  # (B, k // group, n) f32
    *,
    group: int = 128,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    activation: str = "none",
    interpret: bool = False,
) -> jax.Array:
    """Batched fused matmul over a shared leading stack axis (MoE experts).

    ``lax.scan`` over the batch axis invokes the 2-D kernel once per slice
    with ONE shared tile configuration — the kernel body is traced/compiled
    a single time regardless of the expert count, and every expert's
    ``(m, k) x (k, n)`` problem reuses the same (bm, bn, bk) tiles (callers
    key the autotune lookup on the per-expert shape).  Per-expert bias has
    no consumer (MoE expert FFNs are bias-free); activation still fuses
    into each slice's epilogue.
    """
    assert x.ndim == 3 and w_pulses.ndim == 3 and scales.ndim == 3, (
        x.shape, w_pulses.shape, scales.shape,
    )
    assert x.shape[0] == w_pulses.shape[0] == scales.shape[0], (
        x.shape, w_pulses.shape, scales.shape,
    )

    def body(_, operands):
        xb, wb, sb = operands
        y = pvq_matmul(
            xb, wb, sb, None, group=group, bm=bm, bn=bn, bk=bk,
            activation=activation, interpret=interpret,
        )
        return None, y

    _, out = jax.lax.scan(body, None, (x, w_pulses, scales))
    return out


# ---------------------------------------------------------------------------
# Kernel v3: quantized activations — int8 x int8, int32 MXU accumulation
# ---------------------------------------------------------------------------


#: int8 x int8 MXU dots accumulate exactly in int32 at the default
#: precision.  Pinned so that an ambient ``jax_default_matmul_precision``
#: ("highest", e.g. around an f32 reference) never reaches Mosaic, which
#: refuses fp32 contract precision on int8 operands.
_INT8_DOT = jax.lax.Precision.DEFAULT


def _contract_int8_q(x, w, s, group: int, a_tile=None) -> jax.Array:
    """Fully integer tile contraction: per group-slice, one int8 x int8 dot
    with ``preferred_element_type=int32`` (the MXU accumulates in int32),
    then the group's rho row multiplies the int32 partial once — ONE
    multiply per group, now with integer feeds on BOTH operands.

    ``a_tile`` (bm, bk // group), if given, carries per-tile activation
    scales (``ActQuant(granularity="tile")``): group g's partial is scaled
    by ``rho_g * a_tile[:, g]`` — still one rho multiply plus one act-scale
    multiply per group partial, and the epilogue then skips its per-row
    multiply.

    Returns the f32 (bm, bn) partial sum for this (bk, bn) tile.  Beyond
    ``_MAX_UNROLL_GROUPS`` the per-group dots run as one batched
    ``dot_general`` over the group axis instead of an unrolled chain —
    still int8 x int8 / int32, never a dequantized operand.
    """
    bk, bn = w.shape
    bm = x.shape[0]
    n_groups = bk // group
    if n_groups > _MAX_UNROLL_GROUPS:
        xg = jnp.swapaxes(x.reshape(bm, n_groups, group), 0, 1)  # (G, bm, group)
        wg = w.reshape(n_groups, group, bn)
        part = jax.lax.dot_general(
            xg, wg, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
            precision=_INT8_DOT,
        )  # (G, bm, bn) int32
        part = part.astype(jnp.float32) * s[:, None, :]
        if a_tile is not None:
            part = part * jnp.swapaxes(a_tile, 0, 1)[:, :, None]  # (G, bm, 1)
        return jnp.sum(part, axis=0)
    acc = jnp.zeros((bm, bn), jnp.float32)
    for g in range(n_groups):
        xg = x[:, g * group : (g + 1) * group]  # (bm, group) int8
        wg = w[g * group : (g + 1) * group, :]  # (group, bn) int8
        part = jax.lax.dot_general(
            xg, wg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
            precision=_INT8_DOT,
        )
        part = part.astype(jnp.float32) * s[g, :][None, :]
        if a_tile is not None:
            part = part * a_tile[:, g : g + 1]
        acc = acc + part
    return acc


def _q_epilogue(acc, a, bias, activation: str) -> jax.Array:
    """v3 epilogue: the per-row activation scale multiplies the accumulated
    (rho-weighted) integer sums ONCE per output element, then bias + act.
    ``a=None`` when the scale was already applied per tile in the body."""
    y = acc if a is None else acc * a  # (bm, bn) * (bm, 1)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return _apply_activation(y, activation)


def _kernel_q(
    x_ref, w_ref, s_ref, a_ref, o_ref, acc_ref, *, group: int, n_k: int,
    activation: str, per_tile: bool = False,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # x (bm, bk) int8 / w (bk, bn) int8 / s (bk//group, bn) f32
    # a (bm, 1) f32 per-row | (bm, bk//group) f32 per-tile
    acc_ref[...] += _contract_int8_q(
        x_ref[...], w_ref[...], s_ref[...], group,
        a_tile=a_ref[...] if per_tile else None,
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = _q_epilogue(
            acc_ref[...], None if per_tile else a_ref[...], None, activation
        ).astype(o_ref.dtype)


def _kernel_q_bias(
    x_ref, w_ref, s_ref, a_ref, b_ref, o_ref, acc_ref, *, group: int, n_k: int,
    activation: str, per_tile: bool = False,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _contract_int8_q(
        x_ref[...], w_ref[...], s_ref[...], group,
        a_tile=a_ref[...] if per_tile else None,
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = _q_epilogue(
            acc_ref[...], None if per_tile else a_ref[...], b_ref[...], activation
        ).astype(o_ref.dtype)


#: hand-rolled DMA streaming pays off when one (bk, bn) pulse tile is big
#: enough that its HBM->VMEM transfer rivals the MXU time (big-FFN shapes);
#: below this the automatic k-grid pipeline is already optimal.
_DMA_MIN_TILE_ELEMS = 64 * 1024
#: the DMA body holds the full (bm, k_pad) int8 x row-block in VMEM — cap it
#: (plus 2 pulse-tile slots) well under the per-core budget.
_DMA_MAX_X_BYTES = 4 * 1024 * 1024


def _dma_streaming_wanted(
    mp: int, kp: int, np_: int, bm: int, bn: int, bk: int
) -> bool:
    if os.environ.get("REPRO_PVQ_DMA", "") in ("0", "off", "false"):
        return False
    n_chunks = kp // bk
    return (
        n_chunks >= 2  # something to overlap
        and bk * bn >= _DMA_MIN_TILE_ELEMS  # transfer worth hiding
        and bm * kp <= _DMA_MAX_X_BYTES  # whole x row-block fits VMEM
    )


def _kernel_q_dma(
    x_ref, w_hbm_ref, s_ref, a_ref, b_ref, o_ref, wbuf, sems, *, group: int,
    bk: int, n_chunks: int, activation: str, has_bias: bool,
):
    """v3 body with hand-rolled double-buffered pulse streaming.

    Grid is (m/bm, n/bn) — no k grid dimension.  The int8 pulse operand
    stays in HBM (``memory_space=ANY``); the kernel walks the contraction
    dim in ``bk`` chunks, DMA-ing chunk ``i+1`` into one slot of a 2-deep
    VMEM scratch while the MXU contracts chunk ``i`` from the other
    (``pltpu.make_async_copy`` + per-slot DMA semaphores).  x / scales /
    act-scale row blocks are small and ride the automatic pipeline.
    """
    bn = o_ref.shape[1]
    col0 = pl.program_id(1) * bn
    gpc = bk // group  # scale rows per chunk

    def _dma(slot, idx):
        return pltpu.make_async_copy(
            w_hbm_ref.at[pl.ds(idx * bk, bk), pl.ds(col0, bn)],
            wbuf.at[slot],
            sems.at[slot],
        )

    _dma(0, 0).start()

    def body(idx, acc):
        slot = idx % 2

        @pl.when(idx + 1 < n_chunks)
        def _prefetch():
            _dma((idx + 1) % 2, idx + 1).start()

        _dma(slot, idx).wait()
        # x (bm, k_pad) int8 / s (k_pad // group, bn) f32: slice the refs,
        # not loaded values (Mosaic has no dynamic_slice on values)
        xc = x_ref[:, pl.ds(pl.multiple_of(idx * bk, bk), bk)]
        sc = s_ref[pl.ds(pl.multiple_of(idx * gpc, gpc), gpc), :]
        return acc + _contract_int8_q(xc, wbuf[slot], sc, group)

    acc = jax.lax.fori_loop(
        0, n_chunks, body, jnp.zeros(o_ref.shape, jnp.float32)
    )
    bias = b_ref[...] if has_bias else None
    o_ref[...] = _q_epilogue(acc, a_ref[...], bias, activation).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "group", "bm", "bn", "bk", "activation", "out_dtype", "dma_streaming",
        "interpret",
    ),
)
def pvq_matmul_q(
    x_q: jax.Array,  # (m, k) int8 quantized activations
    w_pulses: jax.Array,  # (k, n) int8
    scales: jax.Array,  # (k // group, n) f32
    act_scale: jax.Array,  # (m, 1) / (1, 1) per-row | (m, k//group) per-tile f32
    bias: jax.Array | None = None,  # (n,) optional fused epilogue bias
    *,
    group: int = 128,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    activation: str = "none",
    out_dtype=jnp.float32,
    dma_streaming: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Kernel v3: fused quantized-activation matmul
    ``act(act_scale * (x_q @int32 (pulses * rho)) + bias)``.

    Both MXU operands are int8 and the per-group dot accumulates in int32
    (``preferred_element_type=int32``); rho multiplies each int32 group
    partial once.  A ``(m, 1)`` per-row ``act_scale`` multiplies the final
    accumulator once in the epilogue; a ``(m, k // group)`` per-tile scale
    (``ActQuant(granularity="tile")`` with the tile = the weight group)
    instead multiplies each group's int32 partial alongside rho — one extra
    scalar multiply per group, no per-element work.  ``dma_streaming=None``
    auto-selects the hand-rolled double-buffered HBM->VMEM pulse path for
    big tiles and the automatic k-grid pipeline otherwise (per-tile scales
    always use the k-grid pipeline); True/False force it.
    """
    m, k = x_q.shape
    k2, n = w_pulses.shape
    assert k == k2, (k, k2)
    assert k % group == 0, f"contraction dim {k} must be a group ({group}) multiple"
    assert x_q.dtype == jnp.int8, f"x_q must be pre-quantized int8, got {x_q.dtype}"
    assert w_pulses.dtype == jnp.int8, w_pulses.dtype
    assert scales.shape == (k // group, n), (scales.shape, (k // group, n))
    per_tile = act_scale.shape == (m, k // group) and k > group
    assert per_tile or act_scale.shape in ((m, 1), (1, 1)), (act_scale.shape, m)
    assert activation in ACTIVATIONS, f"activation {activation!r} not in {ACTIVATIONS}"
    if bias is not None:
        assert bias.shape == (n,), (bias.shape, n)

    bm, bn, bk = normalize_tiles(m, k, n, group, bm, bn, bk)

    xp = _pad_to(_pad_to(x_q, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w_pulses, 0, bk), 1, bn)
    sp = _pad_to(_pad_to(scales, 0, bk // group), 1, bn)
    if per_tile:
        # padded k-groups carry zero pulses; their (zero-padded) scales are
        # inert, so the tile grid sees a consistent (mp, kp//group) matrix
        ap = _pad_to(
            _pad_to(act_scale.astype(jnp.float32), 0, bm), 1, bk // group
        )
    else:
        ap = _pad_to(
            jnp.broadcast_to(act_scale.astype(jnp.float32), (m, 1)), 0, bm
        )
    mp, kp = xp.shape
    np_ = wp.shape[1]
    n_k = kp // bk

    if dma_streaming is None:
        dma_streaming = _dma_streaming_wanted(mp, kp, np_, bm, bn, bk)
    if per_tile:
        dma_streaming = False  # the DMA body only threads the per-row scale
    if dma_streaming and kp // bk >= 2:
        kernel = functools.partial(
            _kernel_q_dma, group=group, bk=bk, n_chunks=n_k,
            activation=activation, has_bias=bias is not None,
        )
        in_specs = [
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # pulses stay in HBM
            pl.BlockSpec((kp // group, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ]
        operands = [xp, wp, sp, ap]
        if bias is not None:
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
            operands.append(_pad_to(bias.astype(jnp.float32)[None, :], 1, bn))
        else:
            # keep the kernel arity fixed: a dead (1, bn) zero bias block
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
            operands.append(jnp.zeros((1, np_), jnp.float32))
        out = pl.pallas_call(
            kernel,
            grid=(mp // bm, np_ // bn),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.dtype(out_dtype)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, bn), jnp.int8),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")
            ),
        )(*operands)
        if (mp, np_) != (m, n):
            out = out[:m, :n]
        return out

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((bk // group, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((bm, bk // group), lambda i, j, kk: (i, kk))
        if per_tile
        else pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
    ]
    operands = [xp, wp, sp, ap]
    if bias is None:
        kernel = functools.partial(
            _kernel_q, group=group, n_k=n_k, activation=activation,
            per_tile=per_tile,
        )
    else:
        kernel = functools.partial(
            _kernel_q_bias, group=group, n_k=n_k, activation=activation,
            per_tile=per_tile,
        )
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(_pad_to(bias.astype(jnp.float32)[None, :], 1, bn))

    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.dtype(out_dtype)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(*operands)
    if (mp, np_) != (m, n):
        out = out[:m, :n]
    return out


@functools.partial(
    jax.jit,
    static_argnames=(
        "group", "bm", "bn", "bk", "activation", "out_dtype", "interpret"
    ),
)
def pvq_matmul_q_batched(
    x_q: jax.Array,  # (B, m, k) int8
    w_pulses: jax.Array,  # (B, k, n) int8
    scales: jax.Array,  # (B, k // group, n) f32
    act_scale: jax.Array,  # (B, m, 1) f32
    *,
    group: int = 128,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    activation: str = "none",
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Batched kernel v3 over a shared leading stack axis (MoE experts):
    ``lax.scan`` of :func:`pvq_matmul_q` with ONE shared tile config, one
    compiled body regardless of the expert count — the quantized dispatch
    buffer's per-expert scales ride the scan alongside the pulse planes."""
    assert x_q.ndim == 3 and w_pulses.ndim == 3 and scales.ndim == 3, (
        x_q.shape, w_pulses.shape, scales.shape,
    )
    assert act_scale.ndim == 3 and act_scale.shape[0] == x_q.shape[0], (
        act_scale.shape, x_q.shape,
    )
    assert x_q.shape[0] == w_pulses.shape[0] == scales.shape[0], (
        x_q.shape, w_pulses.shape, scales.shape,
    )

    def body(_, operands):
        xb, wb, sb, ab = operands
        y = pvq_matmul_q(
            xb, wb, sb, ab, None, group=group, bm=bm, bn=bn, bk=bk,
            activation=activation, out_dtype=out_dtype, interpret=interpret,
        )
        return None, y

    _, out = jax.lax.scan(body, None, (x_q, w_pulses, scales, act_scale))
    return out


# ---------------------------------------------------------------------------
# Kernel v4: pvq_attn_q — flash attention decode over the packed KV cache
# ---------------------------------------------------------------------------

#: finite mask value (matches nn.attention.NEG_INF).  Finite on purpose:
#: a fully-masked seq block merges out with weight exp(-1e30 - m) == 0
#: instead of the NaNs that -inf arithmetic would produce.
_ATTN_NEG_INF = -1e30


def _attn_kernel_q(
    len_ref,  # (BH,) int32 SMEM scalar prefetch: valid kv length per row
    q_ref,  # (1, m, hd) int8 quantized queries (m = heads per kv head)
    a_ref,  # (1, m, 1) f32 per-row query scales
    kp_ref,  # (1, bs, hd) int8 packed K pulses
    ks_ref,  # (1, bs, ng) f32 per-group K rho
    vp_ref,  # (1, bs, hd) int8 packed V pulses
    vs_ref,  # (1, bs, ng) f32 per-group V rho
    o_ref,  # (1, m, hd) f32 out: UNNORMALIZED output accumulator
    mo_ref,  # (1, m, 1) f32 out: running row max
    lo_ref,  # (1, m, 1) f32 out: running softmax denominator
    acc_ref, m_ref, l_ref,  # scratch: (m, hd) f32, (m, 1) f32, (m, 1) f32
    *, group: int, n_s: int, sm_scale: float,
):
    """One (batch x kv-head, seq-block) step of the packed flash decode.

    Scores: per sub-head group, int8 query x int8 K-pulse ``dot_general``
    with int32 MXU accumulation; the group's K rho multiplies the int32
    partial once, the per-row query scale and softmax scale apply once per
    score.  Online softmax keeps running (max, denom) per row.  Output: V's
    rho folds into the probabilities per group (one multiply per group),
    the scaled probs requantize to int8 on a per-row dynamic scale, and a
    second int8 x int8 / int32 dot accumulates the output.  The caller
    merges (acc, m, l) with the exact-f32 tail block via logsumexp.
    """
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _ATTN_NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    bs, hd = kp_ref.shape[1], kp_ref.shape[2]
    ng = hd // group
    kv_len = len_ref[pl.program_id(0)]
    m_rows = q_ref.shape[1]
    cols = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = cols < kv_len  # (1, bs)

    q = q_ref[0]  # (m, hd) int8
    scores = jnp.zeros((m_rows, bs), jnp.float32)
    for g in range(ng):
        qg = q[:, g * group : (g + 1) * group]  # (m, group) int8
        kg = kp_ref[0, :, g * group : (g + 1) * group]  # (bs, group) int8
        part = jax.lax.dot_general(
            qg, kg, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
            precision=_INT8_DOT,
        )  # (m, bs) int32
        scores = scores + part.astype(jnp.float32) * ks_ref[0, :, g][None, :]
    scores = scores * a_ref[0] * sm_scale
    scores = jnp.where(valid, scores, _ATTN_NEG_INF)

    m_prev = m_ref[...]  # (m, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    # NEG_INF is finite: exp(scores - m_new) on an all-masked block would be
    # exp(0) = 1 — zero masked probabilities through the mask, never the value
    p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)  # (m, bs)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    outs = []
    for g in range(ng):
        pg = p * vs_ref[0, :, g][None, :]  # V rho folded: ONE multiply per group
        pmax = jnp.max(jnp.abs(pg), axis=-1, keepdims=True)
        s_p = pmax / 127.0
        inv = jnp.where(s_p > 0, 1.0 / jnp.maximum(s_p, 1e-30), 0.0)
        pq = jnp.clip(jnp.round(pg * inv), -127, 127).astype(jnp.int8)
        vg = vp_ref[0, :, g * group : (g + 1) * group]  # (bs, group) int8
        out_g = jax.lax.dot_general(
            pq, vg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
            precision=_INT8_DOT,
        )  # (m, group) int32
        outs.append(out_g.astype(jnp.float32) * s_p)
    acc_ref[...] = acc_ref[...] * alpha + (
        outs[0] if ng == 1 else jnp.concatenate(outs, axis=-1)
    )

    @pl.when(si == n_s - 1)
    def _done():
        o_ref[0] = acc_ref[...]
        mo_ref[0] = m_ref[...]
        lo_ref[0] = l_ref[...]


@functools.partial(
    jax.jit, static_argnames=("group", "sm_scale", "bs", "interpret")
)
def pvq_attn_q(
    q_i8: jax.Array,  # (BH, m, hd) int8 — BH = batch * n_kv, m = q heads / kv head
    act_scale: jax.Array,  # (BH, m, 1) f32 per-row query scales
    k_pulses: jax.Array,  # (BH, S, hd) int8
    k_scales: jax.Array,  # (BH, S, ng) f32
    v_pulses: jax.Array,  # (BH, S, hd) int8
    v_scales: jax.Array,  # (BH, S, ng) f32
    kv_len: jax.Array,  # (BH,) int32 — packed positions valid per (batch, kv-head)
    *,
    group: int,
    sm_scale: float,
    bs: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel v4: packed-KV flash attention decode contraction.

    Grid ``(BH, S/bs)``: each (batch x kv-head) row walks its sequence in
    ``bs``-token blocks with an online softmax.  Returns the UNNORMALIZED
    triple ``(acc (BH, m, hd) f32, m (BH, m, 1), l (BH, m, 1))`` so the
    caller can logsumexp-merge the exact-f32 tail block (the in-flight
    partial cache block lives outside the pulse planes):

        m_tot = max(m_packed, m_tail)
        out = (acc_p * e^(m_p - m_tot) + acc_t * e^(m_t - m_tot))
              / (l_p * e^(m_p - m_tot) + l_t * e^(m_t - m_tot))

    ``kv_len`` rows may be 0 (nothing packed yet): every block masks out,
    l = 0 and m = -1e30, and the merge reduces to the tail alone.
    """
    bh, m, hd = q_i8.shape
    s = k_pulses.shape[1]
    ng = hd // group
    assert hd % group == 0, (hd, group)
    assert q_i8.dtype == jnp.int8 and k_pulses.dtype == jnp.int8
    assert v_pulses.dtype == jnp.int8
    assert k_scales.shape == (bh, s, ng), (k_scales.shape, (bh, s, ng))
    assert v_scales.shape == (bh, s, ng)
    assert act_scale.shape == (bh, m, 1), (act_scale.shape, (bh, m, 1))
    assert kv_len.shape == (bh,), kv_len.shape

    bs = max(min(bs, -(-s // 128) * 128), 128) if s > 128 else max(s, 8)
    mp = -(-m // 8) * 8  # sublane-align the tiny head-group row count

    qp = _pad_to(q_i8, 1, mp)
    ap = _pad_to(act_scale.astype(jnp.float32), 1, mp)
    kpp = _pad_to(k_pulses, 1, bs)
    ksp = _pad_to(k_scales.astype(jnp.float32), 1, bs)
    vpp = _pad_to(v_pulses, 1, bs)
    vsp = _pad_to(v_scales.astype(jnp.float32), 1, bs)
    sp = kpp.shape[1]
    n_s = sp // bs

    kernel = functools.partial(
        _attn_kernel_q, group=group, n_s=n_s, sm_scale=float(sm_scale)
    )
    acc, m_run, l_run = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # kv_len rides in SMEM, read per row
            grid=(bh, n_s),
            in_specs=[
                pl.BlockSpec((1, mp, hd), lambda b, si, L: (b, 0, 0)),
                pl.BlockSpec((1, mp, 1), lambda b, si, L: (b, 0, 0)),
                pl.BlockSpec((1, bs, hd), lambda b, si, L: (b, si, 0)),
                pl.BlockSpec((1, bs, ng), lambda b, si, L: (b, si, 0)),
                pl.BlockSpec((1, bs, hd), lambda b, si, L: (b, si, 0)),
                pl.BlockSpec((1, bs, ng), lambda b, si, L: (b, si, 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, mp, hd), lambda b, si, L: (b, 0, 0)),
                pl.BlockSpec((1, mp, 1), lambda b, si, L: (b, 0, 0)),
                pl.BlockSpec((1, mp, 1), lambda b, si, L: (b, 0, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((mp, hd), jnp.float32),
                pltpu.VMEM((mp, 1), jnp.float32),
                pltpu.VMEM((mp, 1), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, mp, hd), jnp.float32),
            jax.ShapeDtypeStruct((bh, mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, mp, 1), jnp.float32),
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(kv_len.astype(jnp.int32), qp, ap, kpp, ksp, vpp, vsp)
    if mp != m:
        acc, m_run, l_run = acc[:, :m], m_run[:, :m], l_run[:, :m]
    return acc, m_run, l_run
