"""The work the algorithm needs, counted from the configuration's logical
shapes and each slot's actual context, never from padded shapes or the
pool's capacity.

* v3 (``pvq_matmul_q``), one weight GEMM of ``m`` rows, ``k`` in, ``n``
  out, PVQ groups of ``g``: ``2 m k n`` operations; bytes are the int8
  pulses ``k n``, the f32 rho ``4 (k / g) n``, the int8 activations
  ``m k`` and the f32 output ``4 m n``.
* v4 (``pvq_attn_q``), one slot with ``kv_len`` packed positions: for
  each layer and kv head the K and V pulses and rho of those positions,
  ``2 kv_len (hd + 4 hd / g_kv)`` bytes, and ``4 kv_len n_heads hd``
  operations per layer.
* A decode token at context ``ctx``: ``2`` operations per GEMM weight
  (the tied unembedding included) plus ``4 ctx n_heads hd`` per layer.

The least time of a piece of work is the larger of its operations over
the chip's int8 peak and its bytes over the HBM bandwidth.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from harness.weights import MATRICES, dims


def gemms(arch: Dict) -> List[Tuple[str, int, int]]:
    """(name, k, n) of the weight GEMMs of one layer."""
    d = dims(arch)
    return [(name, d[din], d[dout]) for name, din, dout in MATRICES]


def gemm_weights(arch: Dict) -> int:
    """Weights that take part in GEMMs per token: every layer's matrices
    and the tied unembedding."""
    per_layer = sum(k * n for _, k, n in gemms(arch))
    return arch["n_layers"] * per_layer + arch["vocab_size"] * arch["d_model"]


def kv_bytes_per_token(arch: Dict, kv_group: int) -> int:
    """PVQ KV bytes per token over all layers: K and V pulses and rho."""
    hd = arch["head_dim"]
    return arch["n_layers"] * arch["n_kv_heads"] * 2 * (hd + 4 * (hd // kv_group))


def least_time(ops: float, nbytes: float, peaks: Dict) -> float:
    return max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def v3_step(arch: Dict, m: int, group: int, peaks: Dict) -> float:
    """Least seconds of one decode step's layer GEMMs at ``m`` active rows."""
    t = 0.0
    for _, k, n in gemms(arch):
        g = group
        while k < group and g > 1 and k % g:  # a dim shorter than a group takes a divisor of it
            g //= 2
        ops = 2.0 * m * k * n
        nbytes = k * n + 4.0 * (k / g) * n + m * k + 4.0 * m * n
        t += least_time(ops, nbytes, peaks)
    return arch["n_layers"] * t


def v4_step(arch: Dict, kv_lens: Iterable[int], kv_group: int, peaks: Dict) -> float:
    """Least seconds of one decode step's packed attention, one call per
    layer over all slots' packed positions."""
    hd, kvh, h = arch["head_dim"], arch["n_kv_heads"], arch["n_heads"]
    total = sum(kv_lens)
    nbytes = total * kvh * 2 * (hd + 4 * (hd // kv_group))
    ops = 4.0 * total * h * hd
    return arch["n_layers"] * least_time(ops, nbytes, peaks)


def decode_token_ops(arch: Dict, ctx: int) -> float:
    """Model operations of one decode token at context length ``ctx``."""
    attn = 4.0 * ctx * arch["n_heads"] * arch["head_dim"] * arch["n_layers"]
    return 2.0 * gemm_weights(arch) + attn


def packed_len(length: int, page: int) -> int:
    """Positions a decode step reads from packed pages: the completed
    blocks of a slot holding ``length`` positions."""
    return (length // page) * page
