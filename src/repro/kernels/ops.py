"""Public jit'd dispatch layer for the Pallas kernels.

Every caller (nn layers, serve, QAT, grad-compress, checkpointer) goes through
this module rather than the kernel files, so backend selection, tile
autotuning, and the int8 pulse contract live in exactly one place:

* backend: where JAX's default backend is the TPU, every kernel compiles
  to Mosaic; elsewhere the same calls run ``interpret=True`` (the kernel
  body executes on the CPU, checked against ref.py).  Interpret mode checks
  logic only — block shapes and primitives Mosaic refuses pass there — so
  ``tests/test_tpu_compile.py`` compiles the main-path kernels for a
  described v5e chip.
* tiles: ``pvq_matmul`` consults the persistent autotune cache
  (``repro.kernels.autotune``); explicit tiles still win, and
  ``REPRO_PVQ_AUTOTUNE=1`` enables search-on-miss.
* dtypes: the encoder emits int32 pulses (the pyramid L1 bound can exceed
  int8 for extreme K/N); the matmul consumes int8.  :func:`pulses_to_int8`
  is the one sanctioned cast/clamp boundary, and
  :func:`encode_weight_matrix` produces matmul-ready (int8 pulses, scales)
  directly.
* packed artifacts: :func:`packed_matmul` streams a
  ``repro.core.packed.PackedPVQ`` (matmul layout) straight into the kernel —
  the int8 pulses and f32 scales go to VMEM as-is; no dequantized weight
  matrix ever exists.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import autotune as autotune_lib
from . import ref as ref_lib
from .pvq_encode import pvq_encode_batch as _encode_kernel
from .pvq_matmul import pvq_attn_q as _attn_kernel_q
from .pvq_matmul import pvq_matmul as _matmul_kernel
from .pvq_matmul import pvq_matmul_batched as _matmul_kernel_batched
from .pvq_matmul import pvq_matmul_q as _matmul_kernel_q
from .pvq_matmul import pvq_matmul_q_batched as _matmul_kernel_q_batched


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _quantize_x(x, act_quant, act_scale, group=None):
    """Resolve the ActQuant contract for a matmul entry point.

    Returns ``(x, act_scale)`` where either both are None-quantized (f32
    path) or ``x`` is int8 with f32 scales (v3 path): ``(..., 1)`` per-row,
    or ``(..., k//group)`` per-tile when ``act_quant.mode == "per_tile"`` —
    the tile width is the *weight* PVQ group, so each activation scale lines
    up with exactly one rho group in the kernel.  Per-tile therefore needs
    ``x`` already aligned to a group multiple (callers pad to k_pad first).
    ``act_scale is not None`` means the caller already quantized (the MoE
    dispatch buffer is quantized ONCE and its scales reused across the
    up/gate expert matmuls) — ``x`` must then be int8 already.
    """
    if act_scale is not None:
        if x.dtype != jnp.int8:
            raise ValueError(
                f"pre-quantized dispatch (act_scale given) needs int8 x, got {x.dtype}"
            )
        return x, jnp.asarray(act_scale, jnp.float32)
    if act_quant is None:
        return x, None
    from repro.core.quantize import quantize_activations

    if act_quant.mode == "per_tile":
        if group is None:
            raise ValueError("per_tile activation quantization needs the weight group")
        return quantize_activations(x, act_quant, tile=group)
    return quantize_activations(x, act_quant)


# ---------------------------------------------------------------------------
# dequant matmul
# ---------------------------------------------------------------------------


def pvq_matmul(
    x,
    w_pulses,
    scales,
    *,
    group: int = 128,
    bias=None,
    activation: str = "none",
    act_quant=None,
    act_scale=None,
    interpret: bool | None = None,
    tune: bool | None = None,
    **tiles,
):
    """Fused dequant matmul ``act(x @ (pulses * rho) + bias)``.

    Tile sizes come from (in priority order) explicit ``bm``/``bn``/``bk``
    kwargs, the persistent autotune cache, a timed search when ``tune=True``
    (or ``REPRO_PVQ_AUTOTUNE=1``), else the MXU heuristic.  Ragged shapes are
    padded internally; see kernels.pvq_matmul for the tiling contract.

    ``act_quant`` (a ``repro.core.quantize.ActQuant``) switches to kernel v3:
    x is quantized to symmetric int8 here and contracted int8 x int8 with an
    int32 MXU accumulator — no f32 activation tensor reaches the kernel.
    ``act_scale`` instead marks ``x`` as *already* quantized (int8) with the
    given per-row scales; tiles are then keyed on the int8 activation dtype.
    """
    if interpret is None:
        interpret = not _on_tpu()
    out_dtype = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
    x, act_scale = _quantize_x(x, act_quant, act_scale, group=group)
    if not tiles:
        m, k = x.shape
        n = w_pulses.shape[1]
        bm, bn, bk = autotune_lib.get_tiles(
            m, k, n, group=group, dtype=x.dtype, search=tune, interpret=interpret
        )
        tiles = {"bm": bm, "bn": bn, "bk": bk}
    if act_scale is not None:
        return _matmul_kernel_q(
            x,
            w_pulses,
            scales,
            act_scale,
            bias,
            group=group,
            activation=activation,
            out_dtype=out_dtype,
            interpret=interpret,
            **tiles,
        )
    return _matmul_kernel(
        x,
        w_pulses,
        scales,
        bias,
        group=group,
        activation=activation,
        interpret=interpret,
        **tiles,
    )


def packed_matmul(
    x,
    packed,
    *,
    bias=None,
    activation: str = "none",
    act_quant=None,
    act_scale=None,
    interpret: bool | None = None,
    tune: bool | None = None,
):
    """``act(x @ dequant(packed) + bias)`` on a matmul-layout ``PackedPVQ``
    without ever dequantizing: pulses/scales stream into the int8-native
    kernel and rho lands on the accumulator.

    ``x``: (m, d_in) with ``d_in <= packed.k_pad``; the group-padding columns
    are zero-filled here (zero lanes meet zero pulses — int8 zeros on the
    quantized-activation path).  ``act_quant``/``act_scale`` follow the
    :func:`pvq_matmul` contract (kernel v3, int8 x int8).
    """
    if packed.layout != "matmul":
        raise ValueError(f"packed_matmul needs layout='matmul', got {packed.layout!r}")
    if packed.pulses.ndim != 2:
        raise ValueError(
            f"packed_matmul takes one matrix; got stacked pulses {packed.pulses.shape} "
            "(slice the leading stack axis, e.g. inside lax.scan)"
        )
    k_pad = packed.pulses.shape[0]
    d_in = int(packed.shape[-2])
    if x.shape[-1] not in (d_in, k_pad):
        raise ValueError(
            f"x feature dim {x.shape[-1]} matches neither the packed leaf's "
            f"logical d_in {d_in} nor its padded k_pad {k_pad}"
        )
    if x.shape[-1] != k_pad:
        x = jnp.pad(x, ((0, 0), (0, k_pad - x.shape[-1])))
    return pvq_matmul(
        x,
        packed.pulses,
        packed.scales,
        group=packed.group,
        bias=bias,
        activation=activation,
        act_quant=act_quant,
        act_scale=act_scale,
        interpret=interpret,
        tune=tune,
    )


def packed_matmul_stacked(
    x,
    packed,
    *,
    activation: str = "none",
    act_quant=None,
    act_scale=None,
    interpret: bool | None = None,
    tune: bool | None = None,
):
    """Batched ``act(x[e] @ dequant(packed[e]))`` over an expert-stacked
    matmul-layout ``PackedPVQ`` — the MoE expert-bank contraction.

    ``x``: (E, m, d_in) per-expert dispatch buffers (``moe_forward`` folds
    its (g, E, C, d) buffer to this shape); ``packed.pulses``: (E, k_pad, n).
    Tile sizes are resolved ONCE from the shared per-expert (m, k_pad, n)
    problem through the persistent autotune cache, then every expert step
    of the scan reuses them — the int8 pulse planes stream into the kernel
    as stored, no dense expert tensor is ever materialized.

    ``act_quant`` quantizes the dispatch buffers here (per-row int8, kernel
    v3); ``act_scale`` (E, m, 1) marks ``x`` as already-quantized int8 —
    ``moe_forward`` quantizes its dispatch buffer ONCE and reuses the same
    int8 buffer + scales across the up AND gate expert matmuls.
    """
    if packed.layout != "matmul":
        raise ValueError(
            f"packed_matmul_stacked needs layout='matmul', got {packed.layout!r}"
        )
    if packed.pulses.ndim != 3:
        raise ValueError(
            f"packed_matmul_stacked takes one stacked expert bank; got pulses "
            f"{packed.pulses.shape} (expected (E, k_pad, n) — slice any extra "
            "leading scan axes first, e.g. inside lax.scan)"
        )
    e, k_pad, n = packed.pulses.shape
    if x.ndim != 3 or x.shape[0] != e:
        raise ValueError(
            f"x must be (E={e}, m, d_in) matching the expert axis, got {x.shape}"
        )
    if interpret is None:
        interpret = not _on_tpu()
    d_in = int(packed.shape[-2])
    if x.shape[-1] not in (d_in, k_pad):
        # only the structural group-padding columns may be zero-filled here;
        # any other width is a wrong buffer, not a padding request
        raise ValueError(
            f"x feature dim {x.shape[-1]} matches neither the packed bank's "
            f"logical d_in {d_in} nor its padded k_pad {k_pad}"
        )
    out_dtype = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
    # pad BEFORE quantizing so per-tile scale groups align with the weight
    # rho groups of the padded bank (zero lanes quantize to int8 zeros)
    if x.shape[-1] != k_pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, k_pad - x.shape[-1])))
    x, act_scale = _quantize_x(x, act_quant, act_scale, group=packed.group)
    bm, bn, bk = autotune_lib.get_tiles(
        x.shape[1], k_pad, n, group=packed.group, dtype=x.dtype,
        search=tune, interpret=interpret,
    )
    if act_scale is not None:
        return _matmul_kernel_q_batched(
            x,
            packed.pulses,
            packed.scales,
            act_scale,
            group=packed.group,
            bm=bm,
            bn=bn,
            bk=bk,
            activation=activation,
            out_dtype=out_dtype,
            interpret=interpret,
        )
    return _matmul_kernel_batched(
        x,
        packed.pulses,
        packed.scales,
        group=packed.group,
        bm=bm,
        bn=bn,
        bk=bk,
        activation=activation,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# attention decode over a packed KV cache (kernel v4)
# ---------------------------------------------------------------------------


def pvq_attn_decode(
    q,
    kv,
    kv_len,
    *,
    sm_scale: float,
    interpret: bool | None = None,
    tune: bool | None = None,
    bs: int | None = None,
):
    """Flash decode contraction of queries against a ``PackedKV``'s packed
    planes (kernel v4, ``pvq_attn_q``).

    ``q``: (b, q_len, n_heads, hd) float queries; ``kv``: a
    ``repro.core.packed.PackedKV`` — or a ``PagedKV`` slot-pool, which is
    gathered through its page table into the slot-major ``PackedKV`` view
    right here at the dispatch boundary (a fused paged kernel would consume
    the page table directly; until then the gather lives next to the kernel
    it feeds).  ``kv_len``: (b,) int32 count of *packed* positions valid per
    batch row (the caller clamps to ``min(packed_end(filled), length)`` —
    the f32 tail block is the caller's exact side leg, merged via
    logsumexp).

    Queries are quantized to per-row symmetric int8 here; the kernel
    contracts int8 q x int8 K pulses and int8 probs x int8 V pulses on the
    MXU with int32 accumulation, applying each rho once per group.  The
    grouped-query layout is preserved end to end: the packed planes stay at
    ``n_kv`` heads and the ``n_heads // n_kv`` query group rides the kernel's
    row axis — the cache is never expanded to ``n_heads``.

    Returns UNNORMALIZED ``(acc, m, l)`` shaped ``(b, q_len, n_kv, gpr, hd)``
    / ``(..., 1)`` / ``(..., 1)`` for the caller's online-softmax merge:
    ``out = (acc + exp(m_t - M) * acc_tail) / (l * exp(m - M) + ...)`` — see
    ``nn.attention``.  Rows with ``kv_len == 0`` come back with ``l == 0``
    (tail-only merge stays exact).
    """
    from repro.core.packed import is_paged_kv
    from repro.core.quantize import ActQuant, quantize_activations

    if is_paged_kv(kv):
        kv = kv.gather()
    if interpret is None:
        interpret = not _on_tpu()
    b, q_len, n_heads, hd = q.shape
    n_kv = kv.k_pulses.shape[2]
    if n_heads % n_kv:
        raise ValueError(f"n_heads {n_heads} not a multiple of n_kv {n_kv}")
    gpr = n_heads // n_kv
    m = q_len * gpr
    s = kv.k_pulses.shape[1]

    # (b, q_len, n_kv, gpr, hd) -> (b*n_kv, q_len*gpr, hd): each kernel row
    # block holds all query rows sharing one kv head
    qg = q.reshape(b, q_len, n_kv, gpr, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b * n_kv, m, hd)
    q_i8, a_scale = quantize_activations(qg, ActQuant(mode="per_row"))

    def to_bh(plane):  # (b, S, n_kv, X) -> (b*n_kv, S, X)
        return plane.transpose(0, 2, 1, 3).reshape(b * n_kv, s, plane.shape[-1])

    kv_len_bh = jnp.repeat(jnp.asarray(kv_len, jnp.int32), n_kv)
    if bs is None:
        bs = autotune_lib.get_attn_tiles(
            m, hd, s, group=kv.group, dtype=jnp.int8,
            search=tune, interpret=interpret,
        )
    acc, m_run, l_run = _attn_kernel_q(
        q_i8,
        a_scale,
        to_bh(kv.k_pulses),
        to_bh(kv.k_scales),
        to_bh(kv.v_pulses),
        to_bh(kv.v_scales),
        kv_len_bh,
        group=kv.group,
        sm_scale=sm_scale,
        bs=bs,
        interpret=interpret,
    )

    def from_bh(x):  # (b*n_kv, m, X) -> (b, q_len, n_kv, gpr, X)
        x = x.reshape(b, n_kv, q_len, gpr, x.shape[-1])
        return x.transpose(0, 2, 1, 3, 4)

    return from_bh(acc), from_bh(m_run), from_bh(l_run)


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------


def pvq_encode(
    w,
    *,
    k_pulses: int,
    bg: int | None = None,
    delta_max: int | None = None,
    interpret: bool | None = None,
):
    """Batched PVQ projection onto P(N, K) (largest-remainder, bounded
    greedy correction).

    Returns (pulses i32 (g, n), rho_ls f32 (g,)).  ``delta_max >= k_pulses``
    reproduces the exact greedy search.  ``bg``/``delta_max`` default to the
    persistent autotune cache (tuned entries win; ``REPRO_PVQ_AUTOTUNE=1``
    enables search-on-miss; else the heuristic defaults) — explicit values
    always win, exactly like the matmul tile dispatch.
    """
    if interpret is None:
        interpret = not _on_tpu()
    if bg is None or delta_max is None:
        tuned_bg, tuned_delta = autotune_lib.get_encode_params(
            w.shape[0], w.shape[1], k_pulses, dtype=w.dtype, interpret=interpret
        )
        bg = bg if bg is not None else tuned_bg
        delta_max = delta_max if delta_max is not None else tuned_delta
    return _encode_kernel(
        w,
        k_pulses=k_pulses,
        bg=bg,
        delta_max=delta_max,
        interpret=interpret,
    )


def pulses_to_int8(pulses: jax.Array) -> jax.Array:
    """The sanctioned int32 -> int8 pulse boundary for the matmul kernel.

    A P(N, K) coordinate is bounded by K, so K <= 127 is always lossless.
    For K > 127 a coordinate may legally exceed the int8 range and the clamp
    is lossy — callers that persist the clamped code MUST refit the scale
    against the clamped pulses (``core.packed`` does) so the stored artifact
    stays self-consistent; the clamp here just makes the boundary explicit
    rather than a silent overflow wrap.
    """
    return jnp.clip(pulses, -127, 127).astype(jnp.int8)


def encode_weight_matrix(
    w: jax.Array,  # (k, n) float weight matrix, k the contraction dim
    *,
    group: int = 128,
    k_pulses: int,
    bg: int | None = None,
    delta_max: int | None = None,
    interpret: bool | None = None,
):
    """Encode a dense weight matrix into matmul-kernel format.

    Each (group-slice, output-column) gets its own pyramid code: returns
    ``(pulses int8 (k_pad, n), scales f32 (k_pad//group, n), k_pad)`` where
    ``k_pad`` rounds k up to a group multiple (padded rows are zero weights
    and receive zero pulses).  Feed the result straight to :func:`pvq_matmul`
    with x zero-padded to ``k_pad`` columns (``pvq_dense`` in nn.layers does
    this for you).
    """
    k, n = w.shape
    pad = (-k) % group
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad, n), w.dtype)], axis=0)
    k_pad = k + pad
    # (k_pad, n) -> columns-major groups: (n * k_pad/group, group)
    wg = w.T.reshape(n * (k_pad // group), group)
    pulses, rho = pvq_encode(
        wg, k_pulses=k_pulses, bg=bg, delta_max=delta_max, interpret=interpret
    )
    pulses = pulses_to_int8(pulses)
    pulses = jnp.transpose(
        pulses.reshape(n, k_pad // group, group), (1, 2, 0)
    ).reshape(k_pad, n)
    scales = rho.reshape(n, k_pad // group).T.astype(jnp.float32)
    return pulses, scales, k_pad


def pvq_encode_grouped_fast(
    flat: jax.Array,
    group: int,
    k: int,
    delta_max: int | None = None,
    scale_mode: str = "ls",
):
    """Grouped encode of a flat vector on the fast largest-remainder path.

    Dispatches to the Pallas kernel on TPU and its jnp twin
    (``core.pvq.pvq_quantize_direction_fast``, bit-identical output)
    elsewhere (interpret-mode Pallas is a correctness proxy, not a fast path
    on CPU).  Returns (pulses i32 (G, group), rho f32 (G,)); trailing
    zero-padding never receives pulses.  The kernel natively emits the ``ls``
    scale; other scale modes are recomputed from the pulses.
    ``delta_max=None`` resolves through the encoder autotune cache (both
    backends use the resolved value, so results agree across them).
    """
    from repro.core.pvq import _scales, pvq_quantize_direction_fast

    n = flat.shape[0]
    pad = (-n) % group
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    wg = flat.reshape(-1, group)
    if delta_max is None:
        _, delta_max = autotune_lib.get_encode_params(
            wg.shape[0], group, k, dtype=wg.dtype, interpret=not _on_tpu()
        )
    if _on_tpu():
        pulses, rho = pvq_encode(wg, k_pulses=k, delta_max=delta_max)
        if scale_mode != "ls":
            rho = _scales(wg, pulses, scale_mode)
        return pulses, rho
    pulses = pvq_quantize_direction_fast(wg, k, delta_max=delta_max)
    return pulses, _scales(wg, pulses, scale_mode)


# re-export oracles for test convenience
pvq_matmul_ref = ref_lib.pvq_matmul_ref
pvq_encode_ref = ref_lib.pvq_encode_ref
