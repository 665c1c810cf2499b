"""The reference of the dense Llama block, and its work counts.

A configuration names its reference module in its file (``"reference":
"llama"``) and ``harness.spec`` loads ``bench/references/<name>.py``.  A
reference module gives:

* ``arch(model_config) -> dict``: the sizes the reference and the counts
  read, from the program's model configuration; it holds ``vocab_size``;
* ``view(arch, params) -> raw``: the reference's arrays, read from the
  program's packed parameter tree (it may walk several blocks);
* ``make_forward(arch, act_bits)``: the jitted ``forward(raw, tokens (T,))
  -> logits (T, vocab)`` in float32 at ``precision="highest"``, each weight
  matmul taking its activation rows rounded to ``act_bits``;
* optionally the work counts the metric readers ask for: ``v3_step``,
  ``v4_step`` and ``decode_token_ops``.  A module without one leaves that
  reader silent.

This one is the block the program builds for the dense configurations:
RMSNorm (eps 1e-6, as the program has it), grouped-query attention with
rotary positions (rotate-half pairing, theta from the configuration, scale
``1 / sqrt(head_dim)``), a SwiGLU MLP, a final RMSNorm and logits against
the tied embedding.  The forward imports nothing of the program: PVQ
pulses times rho, expanded one layer at a time.  The configuration serves
int8 activations, so each weight matmul takes its activation rows
quantized to ``act_bits`` (symmetric, one scale per row, round to
nearest), the stated contract; everything else is exact float32:
attention over exact keys and values, softmax, norms and the residual
stream.

Work counts, from the logical shapes and each slot's actual context:

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
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp

from harness.work import least_time

#: reference role -> the program's parameter path, as a suffix (matmul
#: roles and norms under the one scanned block: leading axis = layers)
LAYER_ROLES = {
    "wq": "mixer/wq/kernel", "wk": "mixer/wk/kernel", "wv": "mixer/wv/kernel",
    "wo": "mixer/wo/kernel", "wi_gate": "ffn/wi_gate/kernel", "wi_up": "ffn/wi_up/kernel",
    "wo_ffn": "ffn/wo/kernel", "ln_mix": "ln_mix/rms_scale", "ln_ffn": "ln_ffn/rms_scale",
}
EMBED_ROLE = "embed/embedding"
FINAL_NORM_ROLE = "final_norm/rms_scale"
#: (role, contraction dim key, output dim key) of the layer matmuls
MATRICES = (("wq", "d_model", "q_dim"), ("wk", "d_model", "kv_dim"), ("wv", "d_model", "kv_dim"),
            ("wo", "q_dim", "d_model"), ("wi_gate", "d_model", "d_ff"), ("wi_up", "d_model", "d_ff"),
            ("wo_ffn", "d_ff", "d_model"))
EPS = 1e-6


def arch(cfg) -> Dict:
    return {
        "n_layers": cfg.n_layers, "d_model": cfg.d_model, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.resolved_head_dim,
        "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta, "param_dtype": cfg.param_dtype,
    }


def dims(arch: Dict) -> Dict[str, int]:
    hd = arch["head_dim"]
    return {
        "d_model": arch["d_model"], "d_ff": arch["d_ff"],
        "q_dim": arch["n_heads"] * hd, "kv_dim": arch["n_kv_heads"] * hd,
    }


def view(arch: Dict, params) -> Dict:
    """The arrays of ``params`` by role, as plain dicts:

    * ``embed``: ``pulses (vocab * d / g, g) int8``, ``scales (vocab * d / g,)``;
    * ``layers[role]`` for ``wq wk wv wo wi_gate wi_up wo_ffn``:
      ``pulses (L, k_pad, n) int8``, ``scales (L, k_pad / g, n) f32``;
    * ``norms``: ``ln_mix`` and ``ln_ffn`` ``(L, d)``, ``final`` ``(d,)``.
    """
    from repro.core.packed import is_packed

    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params, is_leaf=is_packed)[0]:
        pstr = "/".join(str(getattr(p, "key", p)) for p in path)
        for role, suffix in list(LAYER_ROLES.items()) + [("embed", EMBED_ROLE), ("final", FINAL_NORM_ROLE)]:
            if pstr == suffix or pstr.endswith("/" + suffix):
                if role in found:
                    raise ValueError(f"two leaves for {role!r}: the reference reads one scanned block")
                found[role] = leaf
    missing = (set(LAYER_ROLES) | {"embed", "final"}) - set(found)
    if missing:
        raise ValueError(f"no leaf for {sorted(missing)} in the program's tree")
    code = lambda p: {"pulses": p.pulses, "scales": p.scales}  # noqa: E731
    layers = {role: code(found[role]) for role, _, _ in MATRICES}
    for role, leaf in list(layers.items()):
        if leaf["pulses"].shape[0] != arch["n_layers"]:
            raise ValueError(f"{role}: leading axis {leaf['pulses'].shape[0]} is not the layers")
    return {
        "embed": code(found["embed"]),
        "layers": layers,
        "norms": {"ln_mix": found["ln_mix"], "ln_ffn": found["ln_ffn"], "final": found["final"]},
    }


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
