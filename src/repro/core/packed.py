"""The unified packed PVQ parameter representation.

``PackedPVQ`` is the *single* quantized-weight artifact of this repo: the
int8 pulse tensor plus per-group f32 scales, carried together with the
static metadata (group size, pulse budget K, original shape/dtype, layout)
needed to consume it anywhere — the Pallas int8-native matmul, the serving
layers, the checkpointer, the sharding rules, and the gradient pipeline all
speak this one type.  The paper's value proposition is exactly this: the
PVQ code is both the storage format (≈1 byte/weight before entropy coding)
and the compute format (adds/subs + ONE multiply per group), so a weight is
encoded once and never expanded back to a full f32 matrix on the hot path.

Two physical layouts:

* ``'matmul'`` — pulses ``(k_pad, n)`` int8 / scales ``(k_pad//group, n)``
  f32, the exact HBM layout ``repro.kernels.ops.pvq_matmul`` streams.  Used
  for 2-D dense kernels (and their scan-stacked ``(repeats, k_pad, n)``
  variants: the leading axes ride along as batch dims, so ``lax.scan``
  slices a packed layer per step with zero repacking).
* ``'flat'`` — pulses ``(G, group)`` int8 / scales ``(G,)`` f32, row-major
  groups of the flattened original tensor.  Used for embeddings (group is
  chosen to divide ``d`` so a token row maps to whole groups — lookups
  gather + dequantize only the touched rows) and any other non-matmul leaf.

``PackedPVQ`` is registered as a pytree node with named children
(``pulses``/``scales``); the metadata is static aux data.  That makes packed
params transparently compatible with ``jit``, ``lax.scan`` over stacked
layers, ``jax.device_put`` with shardings, and the checkpointer's
path-keyed flattening.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .quantize import KVQuant, QuantPolicy, _path_str, k_for

Array = jax.Array

#: the MoE expert banks `_pack_leaf` packs into the expert-stacked matmul
#: layout — THE predicate every expert-bank report (serve, export,
#: moe_bench) filters with, so they can never drift from what is packed.
EXPERT_LEAF_REGEX = r"(wi_up|wi_gate|wo)_experts$"

#: leaves the packed policy must never touch even when a rule matches:
#: conv kernels and learned positions are consumed raw (einsum / dynamic
#: slice), and the MLA absorbed-decode b-projections are reshaped per head
#: at decode time — packing them would force a per-step dequant.
PACK_SKIP_REGEX = r"(conv_kernel|pos_embedding|wk_b|wv_b|time_|router)"


def _fit_group(group: int, dim: int) -> int:
    """Largest power-of-two divisor chain of ``group`` that divides ``dim``."""
    g = max(int(group), 1)
    while g > 1 and dim % g:
        g //= 2
    return max(g, 1)


def matmul_plan(group: int, d_in: int) -> Tuple[int, int]:
    """(effective group, group-padded contraction dim) for a matmul-layout
    pack of a ``(d_in, n)`` kernel.  This is THE shape derivation the packed
    artifact dispatches with — anything pre-tuning kernel tiles (e.g.
    ``launch/serve.py --tune``) must key on exactly these values."""
    g = _fit_group(group, d_in) if d_in < group else int(group)
    k_pad = -(-d_in // g) * g
    return g, k_pad


def _resolve_k(g: int, n_over_k: Optional[float], k: Optional[int]) -> int:
    if (n_over_k is None) == (k is None):
        raise ValueError("pass exactly one of n_over_k / k")
    return int(k) if k is not None else k_for(g, n_over_k)


@dataclasses.dataclass(frozen=True, eq=False)
class PackedPVQ:
    """One PVQ-coded tensor: int8 pulses + per-group f32 scales + metadata.

    ``shape``/``dtype`` describe the logical dense tensor (unstacked — extra
    leading axes on ``pulses``/``scales`` are treated as batch/stack dims).
    """

    pulses: Array  # int8; 'matmul': (..., k_pad, n)  'flat': (..., G, group)
    scales: Array  # f32;  'matmul': (..., k_pad//group, n)  'flat': (..., G)
    group: int  # group size (static)
    k: int  # pulse budget per group (static)
    shape: Tuple[int, ...]  # logical dense shape (unstacked)
    dtype: str  # logical dense dtype name
    layout: str = "matmul"  # 'matmul' | 'flat'
    scale_mode: str = "ls"

    # ------------------------------------------------------------- properties

    @property
    def k_pad(self) -> int:
        """Group-padded contraction extent (matmul layout)."""
        return int(self.pulses.shape[-2]) if self.layout == "matmul" else 0

    @property
    def nbytes_packed(self) -> int:
        """HBM bytes of the packed artifact (int8 pulses + f32 scales)."""
        return int(np.prod(self.pulses.shape)) + 4 * int(np.prod(self.scales.shape))

    @property
    def nbytes_dense(self) -> int:
        """Bytes of the dense tensor this replaces (at its logical dtype)."""
        lead = self.pulses.shape[: self.pulses.ndim - 2]
        itemsize = jnp.dtype(self.dtype).itemsize
        return int(np.prod(lead, initial=1)) * int(np.prod(self.shape)) * itemsize

    # ------------------------------------------------------------ dequantize

    def dequantize(self, dtype=None) -> Array:
        """Expand back to the logical dense tensor (leading stack dims kept).

        This is the *cold* path — tests, tooling, and the few consumers with
        no packed compute path.  Hot paths stream ``pulses``/``scales``.
        """
        out_dtype = jnp.dtype(dtype if dtype is not None else self.dtype)
        p = self.pulses.astype(jnp.float32)
        if self.layout == "matmul":
            w = p * jnp.repeat(self.scales, self.group, axis=-2)
            lead = w.shape[:-2]
            w = w[..., : self.shape[-2], :]
            return w.reshape(*lead, *self.shape).astype(out_dtype)
        deq = p * self.scales[..., None]
        lead = deq.shape[:-2]
        flat = deq.reshape(*lead, -1)[..., : int(np.prod(self.shape))]
        return flat.reshape(*lead, *self.shape).astype(out_dtype)

    def __repr__(self) -> str:  # keep pytree dumps readable
        return (
            f"PackedPVQ(shape={self.shape}, dtype={self.dtype}, layout={self.layout!r}, "
            f"group={self.group}, k={self.k}, pulses={tuple(self.pulses.shape)})"
        )


def _packed_flatten_with_keys(p: PackedPVQ):
    children = (
        (jax.tree_util.DictKey("pulses"), p.pulses),
        (jax.tree_util.DictKey("scales"), p.scales),
    )
    aux = (p.group, p.k, p.shape, p.dtype, p.layout, p.scale_mode)
    return children, aux


def _packed_unflatten(aux, children):
    group, k, shape, dtype, layout, scale_mode = aux
    return PackedPVQ(
        pulses=children[0], scales=children[1], group=group, k=k,
        shape=shape, dtype=dtype, layout=layout, scale_mode=scale_mode,
    )


jax.tree_util.register_pytree_with_keys(
    PackedPVQ,
    _packed_flatten_with_keys,
    lambda aux, xs: _packed_unflatten(aux, xs),
)


def is_packed(leaf: Any) -> bool:
    return isinstance(leaf, PackedPVQ)


def materialize(leaf: Any, dtype=None) -> Array:
    """Dense view of a (possibly packed) leaf — the sanctioned escape hatch
    for consumers without a packed compute path."""
    if is_packed(leaf):
        return leaf.dequantize(dtype)
    return leaf if dtype is None else leaf.astype(dtype)


# ---------------------------------------------------------------------------
# PackedKV: the PVQ-compressed attention KV cache (kernel v4 consumer)
# ---------------------------------------------------------------------------


#: ``jax.named_scope`` around every PVQ encode of KV pages and its scatter
#: into the page pool.  It reaches the compiled ops' ``op_name`` metadata
#: only, so a device trace's ops are put under it by instruction name
#: (``repro.runtime.telemetry.hlo_op_scopes``), whatever the encode's ops are.
KV_ENCODE_SCOPE = "kv_page_encode"


def _kv_encode_scope():
    return jax.named_scope(KV_ENCODE_SCOPE)


def _kv_encode_planes(x: Array, group: int, k: int) -> Tuple[Array, Array]:
    """PVQ-encode the head dim of ``x (..., hd)`` in ``hd // group`` groups.

    Returns ``(pulses int8 (..., hd), scales f32 (..., hd // group))`` with
    the least-squares rho fitted against the int8 pulses actually stored.
    Jit-safe (static ``group``/``k``) — this runs *inside* the traced decode
    step every time a cache block fills.
    """
    from .pvq import _scales, pvq_quantize_direction_fast

    shp = x.shape
    ng = shp[-1] // group
    xg = x.astype(jnp.float32).reshape(shp[:-1] + (ng, group))
    pulses = pvq_quantize_direction_fast(xg, k)
    p8 = jnp.clip(pulses, -127, 127).astype(jnp.int8)
    scales = _scales(xg, p8, "ls").astype(jnp.float32)
    if not isinstance(x, jax.core.Tracer):
        # eager calls only — inside the jitted decode step x is a tracer
        # and the probe never runs (host-side hooks only)
        _probe_kv_encode(xg, p8, scales)
    return p8.reshape(shp), scales


def _probe_kv_encode(xg, p8, scales) -> None:
    """KV-block reconstruction SNR + scale-saturation probe (eager only)."""
    from repro.runtime import obs, telemetry

    if not obs.enabled():
        return
    ref = np.asarray(xg)
    pn = np.asarray(p8)
    sn = np.asarray(scales)
    approx = pn.astype(np.float32) * sn[..., None]
    obs.counter("quant.kv_blocks_probed").inc()
    obs.histogram("quant.kv_snr_db").record(telemetry.snr_db(ref, approx))
    if pn.size:
        obs.histogram("quant.kv_clamp_frac").record(
            float(np.count_nonzero(np.abs(pn) == 127)) / pn.size
        )
    if sn.size:
        obs.histogram("quant.kv_zero_scale_frac").record(
            float(np.count_nonzero(sn == 0)) / sn.size
        )


@dataclasses.dataclass(frozen=True, eq=False)
class PackedKV:
    """Block-aligned PVQ-compressed KV cache for one attention layer.

    Layout (``S`` = block-padded cache length, ``ng = head_dim // group``):

    * ``k_pulses``/``v_pulses`` — ``(b, S, n_kv, head_dim)`` int8 pulse
      planes, one PVQ code of P(group, k) per (token, kv-head, sub-group);
    * ``k_scales``/``v_scales`` — ``(b, S, n_kv, ng)`` f32 per-group rho;
    * ``tail_k``/``tail_v`` — ``(b, block, n_kv, head_dim)`` ring in the
      logical cache dtype holding the in-flight partial block.  Slot
      ``pos % block`` holds position ``pos``; the moment a block completes
      (``(pos+1) % block == 0``) it is encoded and stored at
      ``pos + 1 - block`` in the pulse planes, and the ring is reused.

    The split between packed and tail is *physical*: positions below
    ``packed_end(filled) = (filled // block) * block`` are served from the
    pulse planes, positions in ``[packed_end, filled)`` from the exact
    tail.  Per-batch ragged ``length`` masks only — it never moves the
    split, because every batch row shares the same global write position.

    Registered as a pytree with named children, so the cache shards with
    path-keyed rules (``kv/k_pulses`` ...), rides ``lax.scan`` over stacked
    layers, and pads along the sequence axis like the dense cache.
    """

    k_pulses: Array  # int8 (b, S, n_kv, hd)
    k_scales: Array  # f32  (b, S, n_kv, ng)
    v_pulses: Array  # int8 (b, S, n_kv, hd)
    v_scales: Array  # f32  (b, S, n_kv, ng)
    tail_k: Array  # cache dtype (b, block, n_kv, hd)
    tail_v: Array  # cache dtype (b, block, n_kv, hd)
    block: int  # tokens per encoded block (static)
    group: int  # effective sub-head PVQ group (static, divides hd)
    k: int  # pulse budget per group (static, <= 127)
    dtype: str  # logical cache dtype name (tail dtype, dequantize target)

    # ------------------------------------------------------------- properties

    @property
    def head_dim(self) -> int:
        return int(self.k_pulses.shape[-1])

    @property
    def n_groups(self) -> int:
        return int(self.k_scales.shape[-1])

    @property
    def max_len(self) -> int:
        """Block-padded cache length (>= the requested max_len)."""
        return int(self.k_pulses.shape[-3])

    @property
    def packed_bytes_per_token(self) -> int:
        """HBM bytes per token per kv-head pair (K+V pulses + scales)."""
        return 2 * (self.head_dim + 4 * self.n_groups)

    @property
    def dense_bytes_per_token(self) -> int:
        """Bytes per token per kv-head pair of the dense cache it replaces."""
        return 2 * self.head_dim * jnp.dtype(self.dtype).itemsize

    def packed_end(self, filled) -> Array:
        """First position served from the tail (= completed-block extent)."""
        return (filled // self.block) * self.block

    # -------------------------------------------------------------- creation

    @classmethod
    def init(
        cls, batch: int, max_len: int, n_kv: int, head_dim: int,
        *, kvq: KVQuant, dtype=jnp.bfloat16,
    ) -> "PackedKV":
        g = _fit_group(kvq.group, head_dim)
        blk = int(kvq.block)
        s_pad = -(-int(max_len) // blk) * blk
        ng = head_dim // g
        dt = jnp.dtype(dtype)
        return cls(
            k_pulses=jnp.zeros((batch, s_pad, n_kv, head_dim), jnp.int8),
            k_scales=jnp.zeros((batch, s_pad, n_kv, ng), jnp.float32),
            v_pulses=jnp.zeros((batch, s_pad, n_kv, head_dim), jnp.int8),
            v_scales=jnp.zeros((batch, s_pad, n_kv, ng), jnp.float32),
            tail_k=jnp.zeros((batch, blk, n_kv, head_dim), dt),
            tail_v=jnp.zeros((batch, blk, n_kv, head_dim), dt),
            block=blk, group=g, k=int(kvq.k), dtype=dt.name,
        )

    @classmethod
    def from_dense(cls, k: Array, v: Array, *, kvq: KVQuant, dtype=None) -> "PackedKV":
        """Encode a dense prefill cache ``(b, s, n_kv, hd)`` pair.

        The ``s // block`` complete blocks are encoded into the pulse
        planes; the remainder lands in the tail at slots ``0 .. s%block-1``
        (= ``pos % block`` for those positions, matching ``append``).
        """
        b, s, n_kv, hd = k.shape
        dt = jnp.dtype(dtype if dtype is not None else k.dtype)
        pkv = cls.init(b, s, n_kv, hd, kvq=kvq, dtype=dt)
        blk = pkv.block
        n_full = s // blk
        rem = s - n_full * blk
        new = {}
        if n_full:
            full_k = k[:, : n_full * blk].astype(jnp.float32)
            full_v = v[:, : n_full * blk].astype(jnp.float32)
            kp, ks = _kv_encode_planes(full_k, pkv.group, pkv.k)
            vp, vs = _kv_encode_planes(full_v, pkv.group, pkv.k)
            new.update(
                k_pulses=pkv.k_pulses.at[:, : n_full * blk].set(kp),
                k_scales=pkv.k_scales.at[:, : n_full * blk].set(ks),
                v_pulses=pkv.v_pulses.at[:, : n_full * blk].set(vp),
                v_scales=pkv.v_scales.at[:, : n_full * blk].set(vs),
            )
        if rem:
            new.update(
                tail_k=pkv.tail_k.at[:, :rem].set(k[:, n_full * blk :].astype(dt)),
                tail_v=pkv.tail_v.at[:, :rem].set(v[:, n_full * blk :].astype(dt)),
            )
        return dataclasses.replace(pkv, **new) if new else pkv

    # --------------------------------------------------------------- updates

    def append(self, k_new: Array, v_new: Array, pos) -> "PackedKV":
        """Write one decode step ``(b, 1, n_kv, hd)`` at position ``pos``.

        The write always lands in the tail ring (cast to the *cache* dtype,
        never the projection dtype); when it completes a block, the whole
        block is PVQ-encoded and stored into the pulse planes.
        """
        blk = self.block
        tdt = self.tail_k.dtype
        slot = jnp.mod(pos, blk)
        tail_k = jax.lax.dynamic_update_slice_in_dim(
            self.tail_k, k_new.astype(tdt), slot, axis=1
        )
        tail_v = jax.lax.dynamic_update_slice_in_dim(
            self.tail_v, v_new.astype(tdt), slot, axis=1
        )

        def encode(planes):
            kp, ks, vp, vs = planes
            start = pos + 1 - blk
            pk, sk = _kv_encode_planes(tail_k, self.group, self.k)
            pv, sv = _kv_encode_planes(tail_v, self.group, self.k)
            upd = jax.lax.dynamic_update_slice_in_dim
            return (
                upd(kp, pk, start, axis=1),
                upd(ks, sk, start, axis=1),
                upd(vp, pv, start, axis=1),
                upd(vs, sv, start, axis=1),
            )

        planes = (self.k_pulses, self.k_scales, self.v_pulses, self.v_scales)
        kp, ks, vp, vs = jax.lax.cond(
            jnp.mod(pos + 1, blk) == 0, encode, lambda p: p, planes
        )
        return dataclasses.replace(
            self, k_pulses=kp, k_scales=ks, v_pulses=vp, v_scales=vs,
            tail_k=tail_k, tail_v=tail_v,
        )

    # ------------------------------------------------------------ dequantize

    def dense_kv(self, filled, dtype=jnp.float32) -> Tuple[Array, Array]:
        """Exact dense view ``(k, v)`` of shape ``(b, S, n_kv, hd)``.

        Positions below ``packed_end(filled)`` are dequantized from the
        pulse planes; positions at/above it come from the tail ring via a
        gather + where overlay (no dynamic_update_slice — its index
        clamping would corrupt rows when the tail window runs past ``S``).
        Rows beyond ``filled`` carry garbage and must stay length-masked.
        """
        blk = self.block
        s = self.max_len
        # filled may be scalar or per-batch (b,); broadcast against positions
        pe = jnp.atleast_1d(self.packed_end(filled))[:, None]  # (b|1, 1)
        posn = jnp.arange(s)[None, :]  # (1, S)

        def expand(pulses, scales):
            return pulses.astype(jnp.float32) * jnp.repeat(
                scales, self.group, axis=-1
            )

        tidx = jnp.mod(posn - pe, blk)  # (b|1, S)
        mask = (posn >= pe)[:, :, None, None]

        def overlay(deq, tail):
            t_full = jnp.take_along_axis(
                tail.astype(jnp.float32), tidx[:, :, None, None], axis=1
            )
            return jnp.where(mask, t_full, deq)

        k = overlay(expand(self.k_pulses, self.k_scales), self.tail_k)
        v = overlay(expand(self.v_pulses, self.v_scales), self.tail_v)
        return k.astype(dtype), v.astype(dtype)

    def __repr__(self) -> str:  # keep pytree dumps readable
        return (
            f"PackedKV(shape={tuple(self.k_pulses.shape)}, dtype={self.dtype}, "
            f"block={self.block}, group={self.group}, k={self.k})"
        )


def _packed_kv_flatten_with_keys(p: PackedKV):
    names = ("k_pulses", "k_scales", "v_pulses", "v_scales", "tail_k", "tail_v")
    children = tuple(
        (jax.tree_util.DictKey(n), getattr(p, n)) for n in names
    )
    aux = (p.block, p.group, p.k, p.dtype)
    return children, aux


def _packed_kv_unflatten(aux, children):
    block, group, k, dtype = aux
    return PackedKV(
        k_pulses=children[0], k_scales=children[1],
        v_pulses=children[2], v_scales=children[3],
        tail_k=children[4], tail_v=children[5],
        block=block, group=group, k=k, dtype=dtype,
    )


jax.tree_util.register_pytree_with_keys(
    PackedKV,
    _packed_kv_flatten_with_keys,
    lambda aux, xs: _packed_kv_unflatten(aux, xs),
)


def is_packed_kv(leaf: Any) -> bool:
    return isinstance(leaf, PackedKV)


# ---------------------------------------------------------------------------
# Paged PVQ KV pool (continuous-batching serve engine)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class PagedKV:
    """Physical-page pool view of :class:`PackedKV` for a slot-pool engine.

    The continuous-batching engine (``launch.engine``) serves a fixed pool
    of ``n_slots`` decode slots whose sequences join and leave mid-flight.
    Instead of one contiguous plane per slot, the PVQ-encoded KV blocks
    live in a shared pool of physical *pages* — **page size = kv block
    size**, so a page is exactly one PVQ encode unit and pages stay packed
    at rest (int8 pulse planes + per-group rho, never re-encoded on
    allocator moves; moving a page is moving int8 bytes).

    Children (unstacked; a leading layer-stack axis rides along like every
    other cache leaf):

    * ``k_pages``/``v_pages`` — ``(P + 1, page, n_kv, hd)`` int8 pulse
      pool.  Physical page ``P`` (the last one) is the *trash page*:
      masked scatter destinations land there, and page-table entries of
      unallocated logical blocks point at it.  Its content is garbage and
      is never visible through a length mask.
    * ``k_page_scales``/``v_page_scales`` — ``(P + 1, page, n_kv, ng)``
      f32 per-group rho pool.
    * ``tail_k``/``tail_v`` — ``(n_slots, page, n_kv, hd)`` exact ring in
      the cache dtype: the per-slot in-flight partial block (ring slot of
      position ``p`` is ``p % page``, same as :class:`PackedKV`).
    * ``page_table`` — ``(n_slots, max_pages)`` int32: physical page of
      each slot's logical block, trash-page id where unallocated.  The
      engine's host-side allocator owns these values and refreshes them
      every step.
    * ``write_page`` — ``(n_slots,)`` int32: physical destination of the
      block a slot completes THIS step (trash-page id when the step does
      not complete a block).  Pre-assigned by the allocator, so ``append``
      never needs host round-trips.

    ``gather()`` materializes a :class:`PackedKV` view through the page
    table — the kernel-v4 decode contract is unchanged, only indirected.
    """

    k_pages: Array  # int8 (P+1, page, n_kv, hd)
    k_page_scales: Array  # f32 (P+1, page, n_kv, ng)
    v_pages: Array  # int8 (P+1, page, n_kv, hd)
    v_page_scales: Array  # f32 (P+1, page, n_kv, ng)
    tail_k: Array  # cache dtype (n_slots, page, n_kv, hd)
    tail_v: Array  # cache dtype (n_slots, page, n_kv, hd)
    page_table: Array  # int32 (n_slots, max_pages)
    write_page: Array  # int32 (n_slots,)
    page: int  # tokens per page == PVQ block (static)
    group: int  # sub-head PVQ group (static, divides hd)
    k: int  # pulse budget per group (static, <= 127)
    dtype: str  # logical cache dtype name (tail dtype)

    # ------------------------------------------------------------- properties

    @property
    def _stacked(self) -> bool:
        return self.k_pages.ndim == 5

    @property
    def n_pages(self) -> int:
        """Usable physical pages (the +1 trash page excluded)."""
        return int(self.k_pages.shape[-4]) - 1

    @property
    def trash_page(self) -> int:
        return self.n_pages

    @property
    def n_slots(self) -> int:
        return int(self.tail_k.shape[-4])

    @property
    def max_pages(self) -> int:
        """Logical pages per slot (page-table width)."""
        return int(self.page_table.shape[-1])

    @property
    def head_dim(self) -> int:
        return int(self.k_pages.shape[-1])

    @property
    def n_groups(self) -> int:
        return int(self.k_page_scales.shape[-1])

    @property
    def block(self) -> int:
        """PackedKV-compatible alias: the PVQ encode granularity."""
        return self.page

    @property
    def encode_chunk(self) -> int:
        """Rings PVQ-encoded per trip of :meth:`append`'s encode loop: the
        mean number of slots that complete a page per decode step when
        positions are spread, ``ceil(n_slots / page)``.  Larger trips
        encode rings no slot needs; each further trip adds the
        projection's loops, several hundred device ops per layer."""
        return -(-self.n_slots // self.page)

    def packed_end(self, filled) -> Array:
        return (filled // self.page) * self.page

    # -------------------------------------------------------------- creation

    @classmethod
    def init(
        cls, n_slots: int, n_pages: int, max_pages: int, n_kv: int,
        head_dim: int, *, kvq: KVQuant, dtype=jnp.bfloat16,
    ) -> "PagedKV":
        g = _fit_group(kvq.group, head_dim)
        page = int(kvq.block)
        ng = head_dim // g
        dt = jnp.dtype(dtype)
        trash = int(n_pages)
        return cls(
            k_pages=jnp.zeros((n_pages + 1, page, n_kv, head_dim), jnp.int8),
            k_page_scales=jnp.zeros((n_pages + 1, page, n_kv, ng), jnp.float32),
            v_pages=jnp.zeros((n_pages + 1, page, n_kv, head_dim), jnp.int8),
            v_page_scales=jnp.zeros((n_pages + 1, page, n_kv, ng), jnp.float32),
            tail_k=jnp.zeros((n_slots, page, n_kv, head_dim), dt),
            tail_v=jnp.zeros((n_slots, page, n_kv, head_dim), dt),
            page_table=jnp.full((n_slots, max_pages), trash, jnp.int32),
            write_page=jnp.full((n_slots,), trash, jnp.int32),
            page=page, group=g, k=int(kvq.k), dtype=dt.name,
        )

    def with_tables(self, page_table: Array, write_page: Array) -> "PagedKV":
        """Refresh the allocator-owned children (broadcasts over a leading
        layer-stack axis when the container is stacked)."""
        if self._stacked:
            reps = self.k_pages.shape[0]
            page_table = jnp.broadcast_to(page_table[None], (reps,) + page_table.shape)
            write_page = jnp.broadcast_to(write_page[None], (reps,) + write_page.shape)
        return dataclasses.replace(
            self, page_table=page_table.astype(jnp.int32),
            write_page=write_page.astype(jnp.int32),
        )

    # ---------------------------------------------------------------- views

    def gather(self) -> PackedKV:
        """Slot-major :class:`PackedKV` view through the page table.

        ``k_pulses[slot, b * page + t] = k_pages[page_table[slot, b], t]``
        — unallocated logical blocks read the trash page, whose garbage
        stays behind the per-slot length mask.  This is the gather a fused
        paged kernel would do through its page-table operand; expressing it
        as a jnp gather keeps kernel v4 bit-compatible.
        """
        pt = self.page_table  # (n_slots, mp)
        ns, mp = pt.shape
        s = mp * self.page

        def pick(pool):  # (P+1, page, n_kv, X) -> (n_slots, S, n_kv, X)
            g = pool[pt]  # (n_slots, mp, page, n_kv, X)
            return g.reshape(ns, s, g.shape[-2], g.shape[-1])

        return PackedKV(
            k_pulses=pick(self.k_pages), k_scales=pick(self.k_page_scales),
            v_pulses=pick(self.v_pages), v_scales=pick(self.v_page_scales),
            tail_k=self.tail_k, tail_v=self.tail_v,
            block=self.page, group=self.group, k=self.k, dtype=self.dtype,
        )

    def gather_slot(self, slot) -> PackedKV:
        """Single-slot :class:`PackedKV` view through one page-table row
        (batch 1).  The chunked-prefill read leg attends only to the slot
        it extends, so gathering the full slot pool per chunk would be
        ``n_slots`` times the bytes for no extra information."""
        pt = jax.lax.dynamic_slice_in_dim(
            self.page_table, jnp.asarray(slot, jnp.int32), 1, axis=0
        )  # (1, mp)
        s = int(pt.shape[-1]) * self.page

        def pick(pool):  # (P+1, page, n_kv, X) -> (1, S, n_kv, X)
            g = pool[pt]
            return g.reshape(1, s, g.shape[-2], g.shape[-1])

        def row(tail):
            return jax.lax.dynamic_slice_in_dim(
                tail, jnp.asarray(slot, jnp.int32), 1, axis=0
            )

        return PackedKV(
            k_pulses=pick(self.k_pages), k_scales=pick(self.k_page_scales),
            v_pulses=pick(self.v_pages), v_scales=pick(self.v_page_scales),
            tail_k=row(self.tail_k), tail_v=row(self.tail_v),
            block=self.page, group=self.group, k=self.k, dtype=self.dtype,
        )

    def dense_kv(self, filled, dtype=jnp.float32) -> Tuple[Array, Array]:
        """Exact dense oracle view (via the gathered :class:`PackedKV`)."""
        return self.gather().dense_kv(filled, dtype=dtype)

    # --------------------------------------------------------------- updates

    def append(self, k_new: Array, v_new: Array, pos) -> "PagedKV":
        """Write one decode step ``(n_slots, 1, n_kv, hd)`` at per-slot
        positions ``pos (n_slots,)``.

        Every slot's row lands in its tail ring at ``pos % page``; slots
        whose write completes a block (``(pos + 1) % page == 0``) get the
        whole ring PVQ-encoded and scattered to their pre-assigned
        ``write_page``.  The encode runs only when some slot completes,
        and then over the completing rings alone: they are put first
        (stably) and encoded, K and V together, ``encode_chunk`` rings per
        trip of a ``while_loop`` into a buffer whose rows past the
        completing ones scatter to the trash page.  With
        ``encode_chunk == n_slots`` every ring is encoded in one pass and
        scattered to ``write_page`` or the trash page.
        """
        page = self.page
        tdt = self.tail_k.dtype
        pos = jnp.asarray(pos, jnp.int32)
        slot_in_ring = jnp.mod(pos, page)

        # a select, not a per-slot scatter: TPU runs that as a loop over
        # the slots, dozens of device ops per layer and step
        row = (jnp.arange(page) == slot_in_ring[:, None])[:, :, None, None]
        tail_k = jnp.where(row, k_new.astype(tdt), self.tail_k)
        tail_v = jnp.where(row, v_new.astype(tdt), self.tail_v)

        completes = jnp.mod(pos + 1, page) == 0  # (n_slots,)
        dest = jnp.where(completes, self.write_page, self.trash_page)

        def encode_all(pools):
            kpg, ksg, vpg, vsg = pools
            pk, sk = _kv_encode_planes(tail_k.astype(jnp.float32), self.group, self.k)
            pv, sv = _kv_encode_planes(tail_v.astype(jnp.float32), self.group, self.k)
            # duplicate trash indices are fine: the trash page is never read
            return (
                kpg.at[dest].set(pk), ksg.at[dest].set(sk),
                vpg.at[dest].set(pv), vsg.at[dest].set(sv),
            )

        def encode_rings(rk, rv):
            # K and V rings as one flat batch of head vectors: the
            # projection's loops run once per trip, not once for each, and
            # a 2-D batch compiles to fewer device ops per loop step
            x = jnp.concatenate([rk, rv]).astype(jnp.float32)
            p, s = _kv_encode_planes(x.reshape(-1, x.shape[-1]), self.group, self.k)
            p, s = p.reshape(x.shape), s.reshape(x.shape[:-1] + s.shape[-1:])
            r = rk.shape[0]
            return p[:r], s[:r], p[r:], s[r:]

        ns, c = self.n_slots, self.encode_chunk

        def encode_completing(pools):
            rows = -(-ns // c) * c  # whole trips, so no slice is clamped
            # rank of each slot with the completing ones first, each group in
            # slot order; order[r] is the slot of rank r (slot 0 past ns)
            done = completes.astype(jnp.int32)
            n = jnp.sum(done)
            slot = jnp.arange(ns)
            before = jnp.cumsum(done) - done
            rank = jnp.where(completes, before, n + slot - before)
            order = jnp.sum(
                jnp.where(rank[None, :] == jnp.arange(rows)[:, None], slot[None, :], 0), axis=1
            )

            def trip(carry):
                j, bufs = carry
                idx = jax.lax.dynamic_slice_in_dim(order, j * c, c)
                out = encode_rings(tail_k[idx], tail_v[idx])
                bufs = tuple(
                    jax.lax.dynamic_update_slice_in_dim(b, x, j * c, axis=0)
                    for b, x in zip(bufs, out)
                )
                return j + 1, bufs

            # rows the trips leave unwritten go to the trash page, so the
            # fill is never read; filled from n, not a literal, so XLA keeps
            # the fill under the encode's scope
            bufs = tuple(jnp.full((rows,) + p.shape[1:], n, p.dtype) for p in pools)
            _, bufs = jax.lax.while_loop(
                lambda carry: carry[0] * c < n, trip, (jnp.int32(0), bufs)
            )
            dest_sorted = jnp.where(jnp.arange(rows) < n, dest[order], self.trash_page)
            return tuple(pool.at[dest_sorted].set(b) for pool, b in zip(pools, bufs))

        encode = encode_all if c == ns else encode_completing

        pools = (self.k_pages, self.k_page_scales, self.v_pages, self.v_page_scales)
        any_completes = jnp.any(completes)
        with _kv_encode_scope():
            kpg, ksg, vpg, vsg = jax.lax.cond(any_completes, encode, lambda p: p, pools)
        return dataclasses.replace(
            self, k_pages=kpg, k_page_scales=ksg, v_pages=vpg, v_page_scales=vsg,
            tail_k=tail_k, tail_v=tail_v,
        )

    def graft(
        self, k_dense: Array, v_dense: Array, slot, page_ids: Array, real_len
    ) -> "PagedKV":
        """Graft one prefilled request into decode slot ``slot``.

        ``k_dense``/``v_dense``: the request's EXACT dense prefill cache
        ``(1, L_b, n_kv, hd)`` at a page-aligned bucket length ``L_b``
        (prompt padded up; padded rows are garbage and stay behind the
        length mask).  ``page_ids (L_b // page,)`` are the allocator's
        physical destinations — trash-page id for block indices at/after
        ``real_len // page``, so the partially-filled last block never
        pollutes the pool.  The exact rows of that partial block land in
        the slot's tail ring (f32-exact, same as a fresh ``append``
        stream would have left them).

        PVQ encoding happens HERE, not in the prefill step: the prefill
        runs with a dense cache and the graft encodes only complete
        blocks, which keeps the encode bit-identical to the fixed-batch
        ``PackedKV.from_dense`` path.  Implemented as the ``start=0``
        case of :meth:`graft_chunk`, so the monolithic and chunked
        prefill paths share one encode and cannot drift apart.
        """
        return self.graft_chunk(k_dense, v_dense, slot, page_ids, 0, real_len)

    def graft_chunk(
        self, k_dense: Array, v_dense: Array, slot, page_ids: Array,
        start, real_len,
    ) -> "PagedKV":
        """Graft one page-aligned prefill *chunk* into decode slot ``slot``.

        ``k_dense``/``v_dense`` hold the chunk's EXACT dense KV
        ``(1, C, n_kv, hd)`` for absolute positions
        ``[start, start + C)`` of the slot's context, with ``C`` a page
        multiple and ``start`` page-aligned (the chunked-prefill
        scheduler only ever cuts at page boundaries, so a chunk never
        straddles a partially-filled page).  ``page_ids (C // page,)``
        are the physical destinations of the chunk's logical blocks
        ``start // page ..`` — trash-page id for block indices at/after
        ``real_len // page``.  Blocks are PVQ-encoded with the same
        ``_kv_encode_planes`` every other write path uses, so running a
        context through any sequence of chunks leaves the pool (and the
        tail ring) bit-identical to one whole-prompt ``graft`` /
        ``PackedKV.from_dense``.

        The tail window write targets ``packed_end(real_len) - start``:
        only the FINAL chunk (the one containing ``packed_end``) writes
        meaningful tail rows; earlier chunks write a clamped garbage
        window that the final chunk overwrites (harmless — tail rings
        are slot-private and masked by length until then).
        """
        if self._stacked:
            return jax.vmap(
                lambda s, kd, vd: s.graft_chunk(
                    kd, vd, slot, page_ids, start, real_len
                )
            )(self, k_dense, v_dense)
        page = self.page
        kf = k_dense[0].astype(jnp.float32)  # (C, n_kv, hd)
        vf = v_dense[0].astype(jnp.float32)
        nb = kf.shape[0] // page
        kb = kf.reshape(nb, page, kf.shape[-2], kf.shape[-1])
        vb = vf.reshape(nb, page, vf.shape[-2], vf.shape[-1])
        with _kv_encode_scope():
            pk, sk = _kv_encode_planes(kb, self.group, self.k)
            pv, sv = _kv_encode_planes(vb, self.group, self.k)
        ids = jnp.asarray(page_ids, jnp.int32)

        # exact tail: the block window starting at packed_end(real_len),
        # chunk-relative.  dynamic_slice clamps both ends: a mid chunk
        # (packed_end beyond the chunk) or a fully-packed final chunk
        # copies garbage that the tail-valid count masks until the real
        # writer (final chunk / appends) lands.
        pe = self.packed_end(jnp.asarray(real_len, jnp.int32))
        off = pe - jnp.asarray(start, jnp.int32)
        tdt = self.tail_k.dtype
        tk = jax.lax.dynamic_slice_in_dim(kf, off, page, axis=0).astype(tdt)
        tv = jax.lax.dynamic_slice_in_dim(vf, off, page, axis=0).astype(tdt)
        upd = jax.lax.dynamic_update_slice_in_dim
        with _kv_encode_scope():
            pools = dict(
                k_pages=self.k_pages.at[ids].set(pk),
                k_page_scales=self.k_page_scales.at[ids].set(sk),
                v_pages=self.v_pages.at[ids].set(pv),
                v_page_scales=self.v_page_scales.at[ids].set(sv),
            )
        return dataclasses.replace(
            self, **pools,
            tail_k=upd(self.tail_k, tk[None], slot, axis=0),
            tail_v=upd(self.tail_v, tv[None], slot, axis=0),
        )

    def __repr__(self) -> str:
        return (
            f"PagedKV(pages={self.n_pages}, page={self.page}, "
            f"slots={tuple(self.tail_k.shape)}, dtype={self.dtype}, "
            f"group={self.group}, k={self.k})"
        )


_PAGED_KV_CHILDREN = (
    "k_pages", "k_page_scales", "v_pages", "v_page_scales",
    "tail_k", "tail_v", "page_table", "write_page",
)


def _paged_kv_flatten_with_keys(p: PagedKV):
    children = tuple(
        (jax.tree_util.DictKey(n), getattr(p, n)) for n in _PAGED_KV_CHILDREN
    )
    aux = (p.page, p.group, p.k, p.dtype)
    return children, aux


def _paged_kv_unflatten(aux, children):
    page, group, k, dtype = aux
    kwargs = dict(zip(_PAGED_KV_CHILDREN, children))
    return PagedKV(page=page, group=group, k=k, dtype=dtype, **kwargs)


jax.tree_util.register_pytree_with_keys(
    PagedKV,
    _paged_kv_flatten_with_keys,
    lambda aux, xs: _paged_kv_unflatten(aux, xs),
)


def is_paged_kv(leaf: Any) -> bool:
    return isinstance(leaf, PagedKV)


# ---------------------------------------------------------------------------
# Pulse geometry: layout -> canonical symbol orders (entropy coding + stats)
# ---------------------------------------------------------------------------


def pulse_stream(pk: PackedPVQ) -> np.ndarray:
    """1-D int64 stream of the *logical* pulse symbols (no structural padding).

    The canonical symbol order the ``.pvqz`` entropy streams encode:
    matmul layout walks column-major over the contraction dim (groups stay
    contiguous) and drops the group-padding rows; flat layout walks row-major
    and drops the tail padding.  Padding therefore never costs wire bits.
    """
    pulses = np.asarray(pk.pulses, np.int64)
    if pk.layout == "matmul":
        d_in = int(pk.shape[-2])
        return np.swapaxes(pulses, -1, -2)[..., :d_in].ravel()
    numel = int(np.prod(pk.shape))
    lead = pulses.shape[:-2]
    return pulses.reshape(*lead, -1)[..., :numel].ravel()


def pulse_groups(pk: PackedPVQ) -> np.ndarray:
    """(G_total, group) group-major int64 view, padded groups included —
    the geometry the fixed-length enumeration codec and per-group size
    models price."""
    pulses = np.asarray(pk.pulses, np.int64)
    if pk.layout == "matmul":
        return np.swapaxes(pulses, -1, -2).reshape(-1, pk.group)
    return pulses.reshape(-1, pk.group)


# ---------------------------------------------------------------------------
# Encoding single arrays
# ---------------------------------------------------------------------------


def pack_matmul(
    w: Array, *, group: int, n_over_k: Optional[float] = None,
    k: Optional[int] = None, scale_mode: str = "ls",
    interpret: Optional[bool] = None,
) -> PackedPVQ:
    """Encode a dense weight matrix (contraction dim first) into the
    kernel-native matmul layout.  An N-D input (N >= 3) is treated as a
    stack over its leading axes — ``(repeats, d_in, d_out)`` scan stacks,
    ``(E, d_in, d_out)`` expert banks, and ``(repeats, E, d_in, d_out)``
    scan-stacked expert banks are all encoded per trailing matrix with the
    stack axes riding along on ``pulses``/``scales``.  Pass either the
    paper's ``n_over_k`` ratio (K derived from the *effective* group) or an
    explicit per-group ``k`` (used verbatim, even if the group is fitted
    down to divide ``d_in``)."""
    from repro.kernels import ops  # deferred: core must stay importable alone

    if w.ndim > 2:
        lead = w.shape[:-2]
        flat = w.reshape((-1,) + w.shape[-2:])
        packed = [
            pack_matmul(flat[i], group=group, n_over_k=n_over_k, k=k,
                        scale_mode=scale_mode, interpret=interpret)
            for i in range(flat.shape[0])
        ]
        pulses = jnp.stack([p.pulses for p in packed])
        scales = jnp.stack([p.scales for p in packed])
        return PackedPVQ(
            pulses=pulses.reshape(lead + pulses.shape[1:]),
            scales=scales.reshape(lead + scales.shape[1:]),
            group=packed[0].group, k=packed[0].k, shape=packed[0].shape,
            dtype=str(w.dtype), layout="matmul", scale_mode=scale_mode,
        )
    if w.ndim != 2:
        raise ValueError(f"matmul layout needs a tensor of rank >= 2, got {w.shape}")
    d_in, _ = w.shape
    g, _ = matmul_plan(group, d_in)
    k = _resolve_k(g, n_over_k, k)
    pulses, scales, _ = ops.encode_weight_matrix(
        w.astype(jnp.float32), group=g, k_pulses=k, interpret=interpret
    )
    # encode_weight_matrix emits the 'ls' scale natively — but it fits rho
    # against the *unclamped* int32 pulses.  When K > 127 a coordinate may
    # legally exceed the int8 range and get clamped, so refit the scale from
    # the pulses actually stored (the artifact must be self-consistent);
    # non-'ls' scale modes always recompute.
    if scale_mode != "ls" or k > 127:
        from .pvq import _scales

        k_pad = pulses.shape[0]
        pad = k_pad - d_in
        wp = jnp.pad(w.astype(jnp.float32), ((0, pad), (0, 0))) if pad else w.astype(jnp.float32)
        wg = wp.T.reshape(wp.shape[1], k_pad // g, g)
        pg = pulses.T.reshape(pulses.shape[1], k_pad // g, g)
        scales = _scales(wg, pg, scale_mode).T.astype(jnp.float32)
    return PackedPVQ(
        pulses=pulses, scales=scales, group=g, k=k, shape=tuple(w.shape),
        dtype=str(w.dtype), layout="matmul", scale_mode=scale_mode,
    )


def pack_flat(
    w: Array, *, group: int, n_over_k: Optional[float] = None,
    k: Optional[int] = None, scale_mode: str = "ls",
    row_align: Optional[int] = None,
) -> PackedPVQ:
    """Encode any tensor as row-major groups of its flattening.

    ``row_align`` (e.g. the embedding dim) shrinks the group so it divides
    the row length — then every original row covers whole groups and row
    gathers touch only that row's codes.  K comes from ``n_over_k`` (scaled
    with the effective group) or is passed explicitly via ``k``.
    """
    from repro.kernels import ops

    g = _fit_group(group, row_align) if row_align else int(group)
    k = _resolve_k(g, n_over_k, k)
    flat = w.reshape(-1).astype(jnp.float32)
    pulses_i32, scales = ops.pvq_encode_grouped_fast(flat, g, k, scale_mode=scale_mode)
    pulses = ops.pulses_to_int8(pulses_i32)
    if k > 127:
        # K > 127 permits clamped coordinates: refit rho from stored pulses
        from .pvq import _scales

        pad = (-flat.shape[0]) % g
        wg = (jnp.pad(flat, (0, pad)) if pad else flat).reshape(-1, g)
        scales = _scales(wg, pulses, scale_mode)
    scales = scales.astype(jnp.float32)
    return PackedPVQ(
        pulses=pulses, scales=scales, group=g, k=k, shape=tuple(w.shape),
        dtype=str(w.dtype), layout="flat", scale_mode=scale_mode,
    )


# ---------------------------------------------------------------------------
# Tree transforms
# ---------------------------------------------------------------------------


def _pack_leaf(
    pstr: str, leaf: Array, n_over_k: float, group: Optional[int],
    scale_mode: str, interpret: Optional[bool],
) -> Optional[PackedPVQ]:
    """Pack one leaf if a packed consumer exists for it; else None."""
    g = group or 256
    if re.search(PACK_SKIP_REGEX, pstr):
        return None
    if re.search(r"(^|/)embedding$", pstr) and leaf.ndim == 2:
        return pack_flat(
            leaf, group=g, n_over_k=n_over_k, scale_mode=scale_mode,
            row_align=leaf.shape[-1],
        )
    if re.search(r"kernel$", pstr) and leaf.ndim in (2, 3):
        return pack_matmul(
            leaf, group=g, n_over_k=n_over_k, scale_mode=scale_mode,
            interpret=interpret,
        )
    # stacked MoE expert banks: (E, d_in, d_out) or scan-stacked
    # (repeats, E, d_in, d_out).  Encoded per expert matrix into the
    # expert-stacked matmul layout; moe_forward contracts the dispatch
    # buffers against them through ops.packed_matmul_stacked.
    if re.search(EXPERT_LEAF_REGEX, pstr) and leaf.ndim in (3, 4):
        return pack_matmul(
            leaf, group=g, n_over_k=n_over_k, scale_mode=scale_mode,
            interpret=interpret,
        )
    return None


def quantize_params(
    params: Any,
    policy: QuantPolicy,
    *,
    min_size: int = 64,
    interpret: Optional[bool] = None,
) -> Any:
    """Encode a model pytree once into a mixed pytree of ``PackedPVQ`` leaves
    (dense kernels, embeddings) and untouched leaves (norms, biases, and
    anything without a packed consumer).

    The result is the deployment artifact: serve it, checkpoint it, shard
    it — the pulses are never re-encoded and never expanded to a full f32
    matrix on the decode path.
    """

    def visit(path, leaf):
        if is_packed(leaf):
            return leaf  # idempotent: already the artifact
        if not isinstance(leaf, (jax.Array, np.ndarray)) or leaf.ndim < 2:
            return leaf
        if leaf.size < min_size or not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        pstr = _path_str(path)
        m = policy.match(pstr)
        if m is None:
            return leaf
        n_over_k, group = m
        packed = _pack_leaf(
            pstr, jnp.asarray(leaf), n_over_k, group, policy.scale_mode, interpret
        )
        if packed is None:
            return leaf
        _probe_weight_pack(pstr, leaf, packed)
        return packed

    return jax.tree_util.tree_map_with_path(visit, params, is_leaf=is_packed)


def _probe_weight_pack(pstr: str, leaf, packed: PackedPVQ) -> None:
    """Per-leaf pack-time reconstruction SNR (pack is a host-side, eager
    transform, so dequantizing once per leaf here never touches a hot
    loop; no-op unless the registry is enabled)."""
    from repro.runtime import obs, telemetry

    if not obs.enabled() or isinstance(leaf, jax.core.Tracer):
        return
    ref = np.asarray(jnp.asarray(leaf), np.float32)
    approx = np.asarray(packed.dequantize(jnp.float32))
    obs.counter("quant.weight_leaves_packed").inc()
    obs.counter("quant.weight_bytes_packed").add(packed.nbytes_packed)
    obs.counter("quant.weight_bytes_dense").add(packed.nbytes_dense)
    obs.histogram("quant.weight_snr_db").record(telemetry.snr_db(ref, approx))


def dequantize_params(params: Any) -> Any:
    """Inverse transform: expand every ``PackedPVQ`` leaf back to dense."""
    return jax.tree.map(materialize, params, is_leaf=is_packed)


def packed_leaves(params: Any) -> Dict[str, PackedPVQ]:
    """{path: PackedPVQ} for every packed leaf (reporting/tests)."""
    out: Dict[str, PackedPVQ] = {}

    def visit(path, leaf):
        if is_packed(leaf):
            out[_path_str(path)] = leaf
        return leaf

    jax.tree_util.tree_map_with_path(visit, params, is_leaf=is_packed)
    return out


def expert_leaves(params: Any) -> Dict[str, PackedPVQ]:
    """{path: PackedPVQ} for the packed MoE expert banks only."""
    return {
        k: v for k, v in packed_leaves(params).items()
        if re.search(EXPERT_LEAF_REGEX, k)
    }


def packed_stats(params: Any, *, entropy: bool = True) -> Dict[str, float]:
    """Aggregate artifact-size report for a mixed pytree.

    Beyond the raw int8+f32 HBM byte counts, ``entropy=True`` (default)
    prices the pulse streams under the paper's §VI codecs with the *exact*
    ``core.codes`` size models.  ``entropy_bits_per_weight`` applies the
    ``.pvqz`` per-leaf selection rule itself (``bitstream.choose_codec``),
    so it reports what ``write_pvqz`` would actually produce; the per-codec
    ``*_bits_per_weight`` keys are whole-tree totals under that single
    codec (``enum`` is the exact sub-ladder stream size wherever its count
    tables fit memory).
    """
    packed_bytes = 0
    replaced_dense_bytes = 0
    untouched_bytes = 0
    n_packed = 0
    numel = 0
    scale_bits = 0
    best_bits = 0.0
    codec_bits = {"golomb": 0.0, "rle": 0.0, "enum": 0.0}
    enum_priceable = True
    for leaf in jax.tree.leaves(params, is_leaf=is_packed):
        if is_packed(leaf):
            packed_bytes += leaf.nbytes_packed
            replaced_dense_bytes += leaf.nbytes_dense
            n_packed += 1
            if entropy:
                from . import bitstream

                stream = pulse_stream(leaf)
                numel += stream.size
                scale_bits += 32 * int(np.prod(leaf.scales.shape))
                chosen, sizes = bitstream.choose_codec(
                    stream, pulse_groups(leaf), leaf.k
                )
                best_bits += sizes[chosen]
                codec_bits["golomb"] += sizes["golomb"]
                codec_bits["rle"] += sizes["rle"]
                if "enum" in sizes:
                    codec_bits["enum"] += sizes["enum"]
                else:
                    enum_priceable = False
        elif isinstance(leaf, (jax.Array, np.ndarray)):
            untouched_bytes += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
    out = {
        "packed_tensors": n_packed,
        "packed_bytes": packed_bytes,
        "replaced_dense_bytes": replaced_dense_bytes,
        "untouched_bytes": untouched_bytes,
        "weight_compression_ratio": replaced_dense_bytes / max(packed_bytes, 1),
        "total_bytes": packed_bytes + untouched_bytes,
    }
    if entropy and n_packed:
        if not enum_priceable:
            del codec_bits["enum"]
        for codec, bits in codec_bits.items():
            out[f"{codec}_bits_per_weight"] = bits / max(numel, 1)
        out["entropy_bits_per_weight"] = (best_bits + scale_bits) / max(numel, 1)
        out["entropy_coded_bytes_est"] = int((best_bits + scale_bits) // 8)
        out["entropy_compression_ratio"] = 8.0 * replaced_dense_bytes / max(
            best_bits + scale_bits, 1.0
        )
    return out


# ---------------------------------------------------------------------------
# Update semantics
# ---------------------------------------------------------------------------


def packed_update(packed: PackedPVQ, delta: Array) -> PackedPVQ:
    """Apply a dense additive update to a packed leaf: dequantize, add,
    re-encode onto the same pyramid (same layout/group/K).

    This is the *explicit* re-encode point for fine-tuning or EMA on a
    packed artifact; the gradient pipeline (``optim.grad_compress``) treats
    packed leaves as frozen unless the caller opts in via this helper.
    """
    dense = packed.dequantize(jnp.float32)
    lead = packed.pulses.shape[: packed.pulses.ndim - 2]
    updated = dense + delta.astype(jnp.float32).reshape(*lead, *packed.shape)
    if packed.layout == "matmul":
        return pack_matmul(
            updated.astype(packed.dtype), group=packed.group, k=packed.k,
            scale_mode=packed.scale_mode,
        )
    return pack_flat(
        updated.astype(packed.dtype), group=packed.group, k=packed.k,
        scale_mode=packed.scale_mode,
        row_align=packed.shape[-1] if len(packed.shape) >= 2 else None,
    )
