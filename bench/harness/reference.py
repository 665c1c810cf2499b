"""The plain reference: a dense decoder in ``jax.numpy`` at float32.

It imports nothing of the program and reads only the benchmark's own
weight arrays (``harness.weights``): PVQ pulses times rho, expanded here
one layer at a time.  Every matmul runs at ``precision="highest"``.  The
configuration serves int8 activations, so each weight matmul takes its
activation rows quantized to ``act_bits`` (symmetric, one scale per row,
round to nearest), the stated contract; everything else is exact float32:
attention over exact keys and values, softmax, norms and the residual
stream.

The block is the Llama one the program builds for these configurations:
RMSNorm (eps 1e-6, as the program has it), grouped-query attention with
rotary positions (rotate-half pairing, theta from the configuration, scale
``1 / sqrt(head_dim)``), a SwiGLU MLP, a final RMSNorm and logits against
the tied embedding.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from harness.weights import MATRICES, dims

EPS = 1e-6


def act_round(x: jax.Array, bits: int) -> jax.Array:
    """``x`` with each row rounded to a symmetric ``bits``-bit grid."""
    qmax = float(2 ** (bits - 1) - 1)
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax
    q = jnp.where(s > 0, jnp.round(x / jnp.where(s > 0, s, 1.0)), 0.0)
    return q * s


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale.astype(jnp.float32)


def _expand(pulses, scales, d_in):
    """Dense ``(d_in, n)`` matrix of one matmul-layout code."""
    k_pad, n = pulses.shape
    g = k_pad // scales.shape[0]
    w = pulses.astype(jnp.float32).reshape(k_pad // g, g, n) * scales[:, None, :]
    return w.reshape(k_pad, n)[:d_in]


def _rope(x, theta):
    """Rotary positions on ``(T, heads, hd)``, rotate-half pairing."""
    t, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def make_forward(arch: Dict, act_bits: int):
    """``forward(raw, tokens (T,)) -> logits (T, vocab)``, jitted."""
    d = dims(arch)
    h, kvh, hd = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    theta = float(arch["rope_theta"])
    d_in = {name: d[din] for name, din, _ in MATRICES}

    def mm(x, w):
        return act_round(x, act_bits) @ w

    def forward(raw, tokens):
        emb = raw["embed"]
        vocab, dm = arch["vocab_size"], arch["d_model"]
        table = (emb["pulses"].astype(jnp.float32) * emb["scales"][:, None]).reshape(vocab, dm)
        x = table[tokens]
        t = tokens.shape[0]
        causal = jnp.tril(jnp.ones((t, t), bool))

        def layer(x, lw):
            w = {n: _expand(lw[n]["pulses"], lw[n]["scales"], d_in[n]) for n in d_in}
            a = _rms(x, lw["ln_mix"])
            q = _rope(mm(a, w["wq"]).reshape(t, h, hd), theta)
            k = _rope(mm(a, w["wk"]).reshape(t, kvh, hd), theta)
            v = mm(a, w["wv"]).reshape(t, kvh, hd)
            qg = q.reshape(t, kvh, h // kvh, hd)
            s = jnp.einsum("qhgd,khd->hgqk", qg, k) / math.sqrt(hd)
            s = jnp.where(causal, s, -jnp.inf)
            o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v)
            x = x + mm(o.reshape(t, h * hd), w["wo"])
            f = _rms(x, lw["ln_ffn"])
            gate, up = mm(f, w["wi_gate"]), mm(f, w["wi_up"])
            return x + mm(jax.nn.silu(gate) * up, w["wo_ffn"]), None

        xs = dict(raw["layers"])
        xs["ln_mix"], xs["ln_ffn"] = raw["norms"]["ln_mix"], raw["norms"]["ln_ffn"]
        x, _ = jax.lax.scan(layer, x, xs)
        x = _rms(x, raw["norms"]["final"])
        return mm(x, table.T)

    def run(raw, tokens):
        with jax.default_matmul_precision("highest"):
            return forward(raw, tokens)

    return jax.jit(run)
