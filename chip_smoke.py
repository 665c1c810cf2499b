"""Smoke run of the PVQ serving path on one TPU chip.  Not a benchmark: the
times it prints are one cold run's, compilation included.

    python3 chip_smoke.py

In one process it

1. refuses to run (exit 2, no result line) unless JAX's first device is a
   TPU;
2. points JAX's persistent compile cache at its directory
   (``repro.runtime.caches``);
3. serves smollm-360m at its published widths (32 layers, d_model 960,
   vocab 49152; random weights from ``--seed``) twice through
   ``repro.launch.serve``'s engine path, with PVQ-packed weights, int8
   activations and paged PVQ KV, 32 new tokens per request over 8 slots:

   * 16 prompts of 288-512 tokens behind a shared 64-token prefix, in
     128-token chunks: chunked prefill and the prefix page cache.  The
     engine's agreement with the fixed-batch oracle, which prefills each
     prompt whole, is printed but not gated: a chunk reads the earlier
     chunks and the shared prefix through PVQ-packed pages, where the
     oracle attends to exact K/V, and at random init that moves about 3%
     of the argmaxes;
   * 8 prompts of 64-128 tokens, each no longer than a chunk and with no
     shared prefix, so the engine admits them whole (bucketed prefill and
     graft), the oracle's own function: agreement must be >= 99%.

   Every request must finish with all its tokens, and the decode step must
   carry Mosaic kernels (``tpu_custom_call``), not interpreted ones;
4. checks chunked prefill against whole-prompt prefill at the same widths
   (f32 weights): the RMS of the last-position logit difference must stay
   within ``CHUNK_TOL`` of the whole-prompt logits' std;
5. checks kernels v3 and v4 at smollm-360m shapes against the
   ``repro.kernels.ref`` oracles, computed at full f32 precision;
6. prints one line per phase, then as its last line
   ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

Any failed phase raises, so the exit code is non-zero and no ``ok`` line
is printed.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

NEW_TOKENS = 32
SERVE_COMMON = [
    "--arch", "smollm-360m", "--seed", "0",
    "--pvq", "--act-int8", "--kv-pvq", "--engine",
    "--engine-slots", "8", "--rate", "0",
    "--prefill-chunk", "4", "--gen", str(NEW_TOKENS),
]
#: (name, argv, requests, agreement gate or None)
SERVE_RUNS = [
    # a shared 64-token prefix + 224..448 own tokens (288..512); 4-page
    # (128-token) chunks; agreement computed (gate 0) and printed
    ("chunked", SERVE_COMMON + [
        "--requests", "16", "--shared-prefix", "64", "--prompt-len", "448",
        "--agreement-min", "0",
    ], 16, None),
    # 64..128 tokens, each within one chunk: whole-prompt admission
    ("whole", SERVE_COMMON + [
        "--requests", "8", "--prompt-len", "128", "--agreement-min", "0.99",
    ], 8, 0.99),
]

#: chunked vs whole-prompt prefill: RMS of the logit difference over the
#: std of the whole-prompt logits (the tolerance of
#: ``tests/test_engine.py::test_prefill_chunk_matches_whole_prompt_prefill``).
#: On the CPU at full widths with 2-8 layers the PVQ-packed context gives
#: 0.07-0.09; a dropped packed leg, chunk positions from 0, or one page of
#: context missing give 0.22-1.35.
CHUNK_TOL = 0.15
CHUNK_PROMPT = 448  # 4 chunks of 128 tokens: 3 read packed context

#: v3: int8 x int8 dots are exact in int32, so only the f32 rho and scale
#: multiplies differ from the oracle: max |y - ref| <= V3_TOL * max |ref|
V3_TOL = 1e-4
#: v4 running max and denominator: f32 rounding only
V4_STAT_TOL = 1e-4
#: v4 output: the kernel requantizes probabilities x V-rho to int8 per
#: (row, block, group), up to 1/254 of the block max per element; on random
#: data that is ~1% of max |V| (interpret mode), so allow 3%
V4_OUT_TOL = 3e-2


def _smollm_kernel_cases():
    """(name, fn) pairs: each fn runs one kernel at a smollm-360m shape on
    the default device and returns its error against the oracle over the
    tolerance (pass when <= 1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import autotune, ops
    from repro.kernels.pvq_matmul import pvq_attn_q
    from repro.kernels.ref import pvq_attn_q_ref, pvq_matmul_q_ref

    def v3(m, k, n, group=256):
        def run():
            kx, kw, ks = jax.random.split(jax.random.PRNGKey(m * k + n), 3)
            x_q = jax.random.randint(kx, (m, k), -127, 128, jnp.int8)
            a = jnp.full((m, 1), 0.01, jnp.float32)
            w = jax.random.randint(kw, (k, n), -4, 5, jnp.int8)
            s = jnp.abs(jax.random.normal(ks, (k // group, n))) * 0.05
            y = ops.pvq_matmul(x_q, w, s, group=group, act_scale=a)
            with jax.default_matmul_precision("highest"):
                ref = pvq_matmul_q_ref(x_q, w, s, a, group=group)
            return float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref))) / V3_TOL
        return f"v3 m{m} k{k} n{n} g{group}", run

    def v4(bh, m, s, hd=64, group=32):
        def run():
            ks = jax.random.split(jax.random.PRNGKey(bh * m + s), 7)
            q = jax.random.randint(ks[0], (bh, m, hd), -127, 128, jnp.int8)
            a = jnp.abs(jax.random.normal(ks[1], (bh, m, 1))) * 0.02
            kp = jax.random.randint(ks[2], (bh, s, hd), -6, 7, jnp.int8)
            vp = jax.random.randint(ks[3], (bh, s, hd), -6, 7, jnp.int8)
            k_s = jnp.abs(jax.random.normal(ks[4], (bh, s, hd // group))) * 0.1
            v_s = jnp.abs(jax.random.normal(ks[5], (bh, s, hd // group))) * 0.1
            kv_len = jax.random.randint(ks[6], (bh,), 1, s + 1)
            bs = autotune.get_attn_tiles(m, hd, s, group=group)
            acc, mx, l = pvq_attn_q(
                q, a, kp, k_s, vp, v_s, kv_len, group=group, sm_scale=0.125, bs=bs
            )
            with jax.default_matmul_precision("highest"):
                r_acc, r_mx, r_l = pvq_attn_q_ref(
                    q, a, kp, k_s, vp, v_s, kv_len, group=group, sm_scale=0.125
                )
            v_max = float(jnp.max(jnp.abs(vp.astype(jnp.float32) * jnp.repeat(v_s, group, -1))))
            errs = (
                float(jnp.max(jnp.abs(mx - r_mx) / (1 + jnp.abs(r_mx)))) / V4_STAT_TOL,
                float(jnp.max(jnp.abs(l / r_l - 1))) / V4_STAT_TOL,
                float(np.max(np.abs(acc / l - r_acc / r_l))) / v_max / V4_OUT_TOL,
            )
            return max(errs)
        return f"v4 bh{bh} m{m} s{s}", run

    return [
        v3(8, 1024, 960),  # decode: wq / wo
        v3(8, 1024, 2560),  # decode: gate / up
        v3(8, 2560, 960),  # decode: down
        v3(128, 1024, 2560),  # chunked prefill: gate / up
        v4(40, 3, 544),  # engine decode: 8 slots x 5 kv heads, 3 q heads each
        v4(5, 384, 544),  # chunk step: 128 tokens x 3 q heads per kv head
    ]


def _chunked_prefill_error(seed: int = 0) -> float:
    """RMS of (chunked - whole-prompt) last-position logits over the std of
    the whole-prompt ones, for one random ``CHUNK_PROMPT``-token prompt on
    smollm-360m at published widths (f32 weights from ``seed``, the serve
    runs' KV quantization), over ``CHUNK_TOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.quantize import KVQuant, kv_quant_scope
    from repro.launch.engine import prefill_in_chunks
    from repro.nn.models import build_model

    cfg = get_config("smollm-360m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed), max_seq=CHUNK_PROMPT)
    prompt = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (1, CHUNK_PROMPT), 0, cfg.vocab_size
    )
    with kv_quant_scope(KVQuant()), jax.default_matmul_precision("highest"):
        whole, _ = jax.jit(model.prefill)(params, {"tokens": prompt})
        chunked = prefill_in_chunks(model, params, np.asarray(prompt[0]), 128)
    w, c = whole[0, -1], chunked[0, -1]
    return float(jnp.sqrt(jnp.mean((c - w) ** 2)) / jnp.std(w)) / CHUNK_TOL


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    print(f"smoke device: {dev.platform} {dev.device_kind} x{len(devices)}", flush=True)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch import serve
    from repro.runtime.caches import enable_compile_cache

    print(f"smoke compile cache: {enable_compile_cache()}", flush=True)

    for name, argv, n_req, gate in SERVE_RUNS:
        t0 = time.time()
        rc, report = serve.run(argv)
        print(f"smoke serve {name} wall (not a benchmark): {time.time() - t0:.1f}s", flush=True)
        print(f"smoke serve {name} report: {json.dumps(report)}", flush=True)
        if rc != 0:
            raise SystemExit(f"serve {name} exited {rc}: {report}")
        got = (report["engine_requests"], report["engine_generated_tokens"])
        if got != (n_req, n_req * NEW_TOKENS):
            raise SystemExit(f"{name}: requests/tokens {got} != {(n_req, n_req * NEW_TOKENS)}")
        if report["engine_decode_mosaic_kernels"] < 1:
            raise SystemExit(f"{name}: decode step lowered no tpu_custom_call")
        if gate is None and (report["engine_prefix_hits"] < 1 or report["engine_chunks"] < 1):
            raise SystemExit(f"{name}: chunked prefill or the prefix cache never ran")
        if gate is not None and (
            report["engine_prefill_batches"] < 1 or report["engine_chunks"] != 0
            or report["engine_token_agreement"] < gate
        ):
            raise SystemExit(f"{name}: whole-prompt admission did not run or disagreed")
        print(
            f"smoke serve {name}: encode {report['pvq_encode_s']}s, warmup/compile "
            f"{report['engine_warmup_s']}s, {got[0]} requests / {got[1]} tokens, "
            f"agreement with the whole-prompt oracle {report['engine_token_agreement']}"
            f" (gate {gate}), {report['engine_decode_mosaic_kernels']} Mosaic calls "
            f"in the decode step",
            flush=True,
        )

    err = _chunked_prefill_error()
    print(f"smoke chunked vs whole-prompt prefill: error / tolerance = {err:.4f}", flush=True)
    if not err <= 1.0:
        raise SystemExit(f"chunked prefill outside its tolerance ({err:.4f})")

    for name, run in _smollm_kernel_cases():
        err = run()
        print(f"smoke kernel {name}: error / tolerance = {err:.4f}", flush=True)
        if not err <= 1.0:
            raise SystemExit(f"kernel {name} outside its tolerance ({err:.4f})")

    stats = dev.memory_stats() or {}
    print(f"smoke peak_bytes_in_use: {stats.get('peak_bytes_in_use')}", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
