"""Batched serving driver: prefill a batch of prompts, then decode tokens
autoregressively with the KV/SSM cache — optionally with PVQ-quantized
weights (the paper's inference-cost story).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --batch 4 --prompt-len 16 --gen 16 [--pvq]

``--pvq`` serves the *packed* artifact: the model pytree is encoded ONCE
into ``PackedPVQ`` leaves (int8 pulses + f32 group scales) and the decode
loop streams those codes straight into the int8-native Pallas matmul —
no per-layer re-encode, no full-matrix f32 dequantization anywhere on the
hot path.  ``--pvq-sim`` keeps the old dequantize-back-to-f32 simulation
(same numerics as the paper tables, none of the memory win) for A/B runs.

``--artifact model.pvqz`` skips the encode entirely: the entropy-coded
container (written by ``repro.launch.export``) is decoded leaf-by-leaf
straight into ``PackedPVQ`` — bit-exact pulses/scales, no re-encode, peak
decode memory bounded by one leaf — and served through the same int8-native
path, so logits are identical to the in-memory ``--pvq`` artifact it was
exported from.

``--act-int8`` (with ``--pvq`` or ``--artifact``) sets the process-wide
``ActQuant`` contract: every packed matmul on the hot path quantizes its
activations to per-row symmetric int8 and runs the int8 x int8 kernel v3
(int32 MXU accumulation) — the all-integer contraction of the paper plus
Liguori's follow-up, with an activation-bandwidth win on top of the weight
one.  ``--agreement-min T`` additionally serves the same prompts on the
f32 reference path (f32 activations, dense f32 KV cache) and fails
(exit 1) if greedy top-1 token agreement drops below T — the CI gate.

``--kv-pvq`` sets the process-wide ``KVQuant`` contract: every attention
layer's decode cache becomes a ``core.packed.PackedKV`` — completed blocks
of K/V rows are PVQ-encoded (int8 pulse planes + per-group rho), decode
contracts them with the int8 attention kernel v4, and only the in-flight
partial block stays exact f32.  This is the decode *bandwidth* half: after
``--pvq --act-int8`` shrank weights and activations, re-reading the KV
cache every token dominates; packed KV cuts those bytes ~3.6x vs f32.
"""

from __future__ import annotations

import argparse
import json
import time
import weakref
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.packed import expert_leaves, packed_stats, quantize_params
from repro.core.quantize import QuantPolicy, quantize_tree, total_bits
from repro.launch.engine import bucket_len
from repro.nn.models import build_model
from repro.runtime import obs
from repro.runtime.caches import enable_compile_cache

# Actual XLA trace counts of the shared decode step (incremented by a
# Python side effect that only runs while tracing).  The regression tests
# read this to prove cache-length bucketing + the shared jit keep
# generate() from recompiling per (batch, cache_len).
TRACE_COUNTS: dict = {"decode_step": 0}
_STEP_JITS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _jit_step(model):
    """One shared jitted ``decode_step`` per Model.

    The old pattern — a fresh ``jax.jit(model.decode_step)`` inside every
    ``generate()`` call — gave each call its own empty compile cache, so
    EVERY call retraced (and every distinct ``(batch, cache_len)`` pair
    recompiled even across a shared wrapper).  One wrapper per model plus
    kv-block cache-length bucketing bounds compiles by shape buckets."""
    fn = _STEP_JITS.get(model)
    if fn is None:
        def counted_step(params, cache, tok, pos):
            # both side effects run at TRACE time only (host-side python;
            # nothing lands inside the compiled body): the test dict, and
            # the same watcher promoted to a first-class metric
            TRACE_COUNTS["decode_step"] += 1
            obs.counter("serve.decode_step_traces").inc()
            return model.decode_step(params, cache, tok, pos)

        fn = jax.jit(counted_step)
        _STEP_JITS[model] = fn
    return fn


def _decode_bucket() -> int:
    """Cache-length bucket: the active KVQuant block (packed planes must
    cover whole blocks anyway) or 32 for dense caches."""
    from repro.core.quantize import default_kv_quant

    kvq = default_kv_quant()
    return int(kvq.block) if kvq else 32


def _expert_report(params) -> dict:
    """Weight-bytes report for the packed MoE expert bank (if any)."""
    ex = expert_leaves(params)
    if not ex:
        return {}
    packed_bytes = sum(leaf.nbytes_packed for leaf in ex.values())
    dense_bytes = sum(leaf.nbytes_dense for leaf in ex.values())
    return {
        "packed_expert_tensors": len(ex),
        "packed_expert_bytes": packed_bytes,
        "dense_expert_bytes": dense_bytes,
        "expert_compression_ratio": round(dense_bytes / max(packed_bytes, 1), 3),
    }


def generate(model, params, tokens, *, gen: int, cache_len: int, extra_batch=None):
    """Greedy decode. tokens: (b, s) prompt. Returns (b, s+gen).

    ``cache_len`` is rounded up to the kv-block bucket so nearby lengths
    share one compiled decode step (positions past the true length stay
    behind the attention length mask)."""
    cache_len = bucket_len(cache_len, _decode_bucket())
    with obs.span("serve/generate", args={
        "batch": int(tokens.shape[0]), "gen": int(gen), "cache_len": cache_len,
    }):
        batch = {"tokens": tokens}
        if extra_batch:
            batch.update(extra_batch)
        with obs.span("serve/prefill"):
            logits, cache = model.prefill(params, batch, cache_len=cache_len)
        out = [tokens]
        tok = jnp.argmax(logits[:, -1, :], -1)[:, None].astype(jnp.int32)

        step = _jit_step(model)
        pos0 = tokens.shape[1]
        for i in range(gen):
            out.append(tok)
            logits, cache = step(params, cache, tok, jnp.int32(pos0 + i))
            tok = jnp.argmax(logits[:, -1, :], -1)[:, None].astype(jnp.int32)
        return jnp.concatenate(out, axis=1)


def teacher_forced_logits(
    model, params, seq, *, prompt_len: int, extra_batch=None
):
    """Per-position next-token logits along a FIXED sequence, through the
    decode path (prefill on the prompt, then ``decode_step`` fed the given
    tokens).  Returns (b, seq_len - prompt_len, vocab) logits predicting
    positions ``prompt_len..seq_len-1``."""
    with obs.span("serve/teacher_forced", args={
        "batch": int(seq.shape[0]), "seq_len": int(seq.shape[1]),
    }):
        batch = {"tokens": seq[:, :prompt_len]}
        if extra_batch:
            batch.update(extra_batch)
        cache_len = bucket_len(seq.shape[1], _decode_bucket())
        logits, cache = model.prefill(params, batch, cache_len=cache_len)
        steps = [logits[:, -1, :]]
        step = _jit_step(model)
        for i in range(seq.shape[1] - prompt_len - 1):
            tok = seq[:, prompt_len + i : prompt_len + i + 1]
            logits, cache = step(params, cache, tok, jnp.int32(prompt_len + i))
            steps.append(logits[:, -1, :])
        return jnp.stack(steps, axis=1)


def top1_agreement(logits_a, logits_b) -> dict:
    """Top-1 agreement between two logit tensors over the same contexts.

    Returns ``{"top1_agreement", "top1_agreement_strict", "ties_excused"}``.
    Strict agreement is plain argmax equality.  The headline number
    additionally excuses *sub-noise ties*: a disagreeing position counts as
    agreeing only when BOTH

    * the reference margin ``logits_a[argmax_a] - logits_a[argmax_b]`` is at
      most the MEASURED logit perturbation ``max_v |a - b|`` at that very
      position — the paths differ by less than the gap they disagree over;
    * that margin is also below 5% of the reference logits' own spread at
      the position — the reference itself calls the two candidates a
      near-tie, so no int8 kernel (indeed no reordered f32 kernel) could
      reproduce the pick deterministically.

    The second condition keeps the excuse from laundering a broken kernel:
    gross perturbations produce disagreements with LARGE reference margins,
    which are never excused.  On a trained model margins dwarf the noise
    and the two metrics coincide; the excuse exists for random-init smoke
    models whose near-tie margins are coin flips.
    """
    a = jnp.asarray(logits_a, jnp.float32)
    b = jnp.asarray(logits_b, jnp.float32)
    pa = jnp.argmax(a, -1)
    pb = jnp.argmax(b, -1)
    strict = pa == pb
    noise = jnp.max(jnp.abs(a - b), axis=-1)  # (b, t)
    margin = jnp.take_along_axis(a, pa[..., None], -1)[..., 0] - jnp.take_along_axis(
        a, pb[..., None], -1
    )[..., 0]
    tie_cap = 0.05 * jnp.std(a, axis=-1)
    agree = strict | ((margin <= noise) & (margin <= tie_cap))
    out = {
        "top1_agreement": float(jnp.mean(agree.astype(jnp.float32))),
        "top1_agreement_strict": float(jnp.mean(strict.astype(jnp.float32))),
        "ties_excused": int(jnp.sum((agree & ~strict).astype(jnp.int32))),
    }
    if obs.enabled():
        # agreement as a streaming metric, not just one gate number
        total = int(np.prod(np.asarray(strict.shape)))
        obs.counter("quality.tokens_total").add(total)
        obs.counter("quality.tokens_agree").add(int(jnp.sum(agree)))
        obs.counter("quality.ties_excused").add(out["ties_excused"])
        obs.histogram("quality.ref_margin").record_many(
            np.asarray(margin, np.float64).ravel()
        )
    return out


def engine_token_agreement(model, params, requests, outputs) -> dict:
    """Token-level agreement of the continuous-batching engine against the
    fixed-batch decode oracle.

    For every request, the engine's full output sequence is teacher-forced
    through the fixed-batch path (prefill + lockstep ``decode_step``, same
    quantized contracts) and each engine token is compared against the
    oracle's argmax *given the identical context* — no free-running
    cascade, so one near-tie flip can't rewrite a suffix.  A disagreeing
    token is excused only when the oracle itself calls it a near-tie (its
    margin over the engine's pick is under 5% of the logits' spread —
    the ``top1_agreement`` tie rule with the oracle as its own reference).
    """
    agree = total = excused = 0
    for req in requests:
        gen = outputs.get(req.rid)
        if not gen:
            continue
        seq = jnp.asarray([list(req.prompt) + list(gen)], jnp.int32)
        lg = teacher_forced_logits(model, params, seq, prompt_len=len(req.prompt))
        lg = jnp.asarray(lg[0], jnp.float32)  # (len(gen), vocab)
        oracle = np.asarray(jnp.argmax(lg, -1))
        toks = np.asarray(gen)
        match = oracle == toks
        margin = np.asarray(
            jnp.take_along_axis(lg, jnp.asarray(oracle)[:, None], -1)[:, 0]
            - jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], -1)[:, 0]
        )
        tie = margin <= 0.05 * np.asarray(jnp.std(lg, axis=-1))
        agree += int(np.sum(match | tie))
        excused += int(np.sum(~match & tie))
        total += len(gen)
        if obs.enabled():
            # per-request streaming counters + the running agreement level
            obs.counter("quality.tokens_total").add(len(gen))
            obs.counter("quality.tokens_agree").add(int(np.sum(match | tie)))
            obs.counter("quality.ties_excused").add(int(np.sum(~match & tie)))
            obs.gauge("quality.agreement_running").set(agree / max(total, 1))
    return {
        "engine_token_agreement": agree / max(total, 1),
        "engine_tokens_compared": total,
        "engine_ties_excused": excused,
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI entry point: serve, print the one-line JSON report, return the
    exit code (1 when a gate fails)."""
    rc, report = run(argv)
    print(json.dumps(report))
    return rc


def run(argv: Optional[Sequence[str]] = None) -> Tuple[int, dict]:
    """Parse ``argv`` (``sys.argv[1:]`` when None), serve, and return
    ``(exit code, report)`` — the in-process form of :func:`main`."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument(
        "--pvq",
        action="store_true",
        help="serve the packed PVQ artifact (int8 pulses streamed into the "
        "int8-native kernel; encode once, zero dequant on the hot path)",
    )
    ap.add_argument(
        "--pvq-sim",
        action="store_true",
        help="legacy dequantized simulation: encode then expand back to f32 "
        "(paper-table numerics, no memory win)",
    )
    ap.add_argument(
        "--artifact",
        default=None,
        metavar="MODEL.PVQZ",
        help="serve a .pvqz compressed artifact (repro.launch.export): "
        "entropy-coded pulses stream-decode leaf-by-leaf into PackedPVQ "
        "with no re-encode, then serve int8-native",
    )
    ap.add_argument(
        "--act-int8",
        action="store_true",
        help="quantize activations to per-row symmetric int8 and run every "
        "packed matmul through the int8 x int8 kernel v3 (int32 MXU "
        "accumulation); requires --pvq or --artifact",
    )
    ap.add_argument(
        "--kv-pvq",
        action="store_true",
        help="PVQ-compress the decode KV cache: completed blocks are stored "
        "as int8 pulse planes + per-group rho and contracted by the int8 "
        "attention kernel v4; the in-flight partial block stays exact f32",
    )
    ap.add_argument(
        "--kv-block",
        type=int,
        default=32,
        help="with --kv-pvq: tokens per encoded cache block (the f32 tail "
        "ring is this long)",
    )
    ap.add_argument(
        "--kv-group",
        type=int,
        default=32,
        help="with --kv-pvq: sub-head PVQ group width (fitted down when it "
        "does not divide head_dim)",
    )
    ap.add_argument(
        "--max-kv-bytes-ratio",
        type=float,
        default=0.35,
        metavar="R",
        help="with --kv-pvq: exit 1 if the packed cache's bytes/token "
        "exceeds R x the f32 cache (the compression the kernel-v4 path "
        "exists to deliver)",
    )
    ap.add_argument(
        "--agreement-min",
        type=float,
        default=None,
        metavar="T",
        help="with --act-int8 and/or --kv-pvq: also serve the same prompts "
        "on the f32 reference path (f32 activations, dense f32 KV cache) "
        "and exit 1 if greedy top-1 token agreement < T",
    )
    ap.add_argument(
        "--engine",
        action="store_true",
        help="serve a Poisson request trace through the continuous-batching "
        "engine (launch.engine): paged PVQ KV cache, async admission, "
        "prefill/decode disaggregation; requires --kv-pvq (pages are PVQ "
        "blocks).  Also times the fixed-batch generate() loop run "
        "sequentially over the same trace for the speedup report",
    )
    ap.add_argument("--engine-slots", type=int, default=4,
                    help="with --engine: decode slot-pool size")
    ap.add_argument("--engine-pages", type=int, default=None,
                    help="with --engine: physical KV pages (default: fully "
                    "provisioned slots*max_pages; smaller oversubscribes "
                    "and exercises eviction)")
    ap.add_argument("--requests", type=int, default=16,
                    help="with --engine: trace length")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="with --engine: Poisson arrival rate (req/s); "
                    "0/inf = all arrive at t=0 (saturate-then-drain)")
    ap.add_argument("--min-speedup", type=float, default=None, metavar="S",
                    help="with --engine: exit 1 if engine tokens/s is not "
                    "at least S x the sequential fixed-batch baseline")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="P",
                    help="with --engine: chunked prefill — prompts longer "
                    "than P pages stream in P-page chunks interleaved with "
                    "decode steps (bounds p99 inter-token latency during "
                    "long-prompt admission); also enables the shared-prefix "
                    "page cache")
    ap.add_argument("--prefill-batch", type=int, default=1, metavar="B",
                    help="with --engine: admit up to B same-bucket waiting "
                    "requests per step through ONE multi-row prefill compile")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="with --engine --prefill-chunk: disable the "
                    "shared-prefix page cache (refcounted page reuse)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="with --engine: prepend one common N-token prefix "
                    "to every trace prompt (shared-system-prompt traffic; "
                    "exercises the prefix page cache)")
    ap.add_argument("--min-prefix-hits", type=int, default=None, metavar="H",
                    help="with --engine: exit 1 if the prefix page cache "
                    "recorded fewer than H page hits over the run")
    ap.add_argument("--n-over-k", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--tune",
        action="store_true",
        help="pre-tune pvq_matmul tiles for this config's decode/prefill GEMM "
        "shapes and persist them (REPRO_PVQ_TUNE_CACHE); later PVQ-kernel "
        "dispatch through kernels.ops picks the tuned tiles up transparently",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        metavar="DIR",
        help="enable the process telemetry registry (repro.runtime.obs) and "
        "write metrics.jsonl + a perfetto-loadable trace.json into DIR on "
        "exit (every exit path, gate failures included)",
    )
    args = ap.parse_args(argv)
    if args.act_int8 and not (args.pvq or args.artifact):
        ap.error("--act-int8 quantizes the packed matmul activations; "
                 "it requires --pvq or --artifact")
    if args.agreement_min is not None and not (args.act_int8 or args.kv_pvq):
        ap.error("--agreement-min compares a quantized path against the f32 "
                 "reference; it requires --act-int8 and/or --kv-pvq")
    if args.engine and not args.kv_pvq:
        ap.error("--engine pages the PVQ-compressed KV cache (page = kv "
                 "block); it requires --kv-pvq")

    enable_compile_cache()
    if args.metrics_out:
        obs.set_enabled(True)
    try:
        return _serve(args)
    finally:
        if args.metrics_out:
            obs.write(args.metrics_out)


def _probe_act_rows(params) -> None:
    """Host-side ActQuant quality probe on real weight rows.

    The serving matmuls quantize activations under jit, where the
    eager-only probe in ``quantize_activations`` can't fire; here we run
    the identical transform eagerly on rows of the packed embedding (or
    the first packed leaf) so the clamp/saturation metrics get real data.
    """
    import re

    from repro.core.packed import packed_leaves
    from repro.core.quantize import default_act_quant, quantize_activations

    aq = default_act_quant()
    leaves = packed_leaves(params)
    if aq is None or not leaves:
        return
    pick = next(
        (l for p, l in leaves.items() if re.search(r"(^|/)embedding$", p)),
        next(iter(leaves.values())),
    )
    rows = pick.dequantize(jnp.float32)
    rows = rows.reshape(-1, rows.shape[-1])[:32]
    quantize_activations(rows, aq)


def _serve(args) -> Tuple[int, dict]:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed), max_seq=args.prompt_len + args.gen)

    report = {}
    if args.metrics_out:
        report["metrics_out"] = args.metrics_out
    if args.tune:
        from repro.core.packed import matmul_plan
        from repro.kernels import autotune

        t_tune = time.time()
        autotune.reset_tune_stats()
        d_model = cfg.d_model
        d_ff = getattr(cfg, "d_ff", 0) or 4 * d_model
        group = cfg.pvq.group or 128
        tuned = {}
        # decode (m=batch) and prefill (m=batch*prompt) GEMMs of the block,
        # keyed exactly as the packed artifact will dispatch them (same
        # effective group + group-padded contraction dim via matmul_plan) —
        # otherwise the pre-tuned entries can never be cache hits
        shapes = {
            (args.batch, d_model, d_model),
            (args.batch, d_model, d_ff),
            (args.batch, d_ff, d_model),
            (args.batch * args.prompt_len, d_model, d_ff),
        }
        if args.engine:
            # slot-pool decode GEMMs: m is the engine's fixed slot count
            shapes |= {
                (args.engine_slots, d_model, d_model),
                (args.engine_slots, d_model, d_ff),
                (args.engine_slots, d_ff, d_model),
            }
            if args.prefill_chunk:
                # chunked-prefill GEMMs: one static row count C per step
                c_tok = args.prefill_chunk * max(args.kv_block, 1)
                shapes |= {
                    (c_tok, d_model, d_model),
                    (c_tok, d_model, d_ff),
                    (c_tok, d_ff, d_model),
                }
            if args.prefill_batch > 1:
                # batched-admission prefill GEMM: B rows x the prompt bucket
                b_tok = args.prefill_batch * bucket_len(
                    max(args.shared_prefix + args.prompt_len, 1),
                    max(args.kv_block, 1),
                )
                shapes.add((b_tok, d_model, d_ff))
        if cfg.moe is not None:
            # per-expert dispatch-buffer GEMMs (m = groups * capacity): the
            # batched expert matmul keys its shared tiles on exactly these
            from repro.nn.moe import dispatch_gemm_rows

            mo = cfg.moe
            for t in (args.batch, args.batch * args.prompt_len):
                m_exp = dispatch_gemm_rows(mo, t)
                shapes.add((m_exp, d_model, mo.d_expert))
                shapes.add((m_exp, mo.d_expert, d_model))
        for m, k, n in sorted(shapes):
            g, k_pad = matmul_plan(group, k)
            e = autotune.autotune(m, k_pad, n, group=g)
            tuned[f"{m}x{k_pad}x{n}"] = {kk: e[kk] for kk in ("bm", "bn", "bk", "us")}
            if args.act_int8:
                # the act dtype is part of the cache key: int8 entries time
                # the quantized-activation kernel v3 body and can never be
                # confused with the f32-activation tiles above
                e8 = autotune.autotune(m, k_pad, n, group=g, dtype=jnp.int8)
                tuned[f"{m}x{k_pad}x{n}:int8"] = {
                    kk: e8[kk] for kk in ("bm", "bn", "bk", "us")
                }
        if args.kv_pvq:
            # kernel-v4 attention decode shape: m = grouped query rows per kv
            # head, s = the packed plane length the serve caches will carry
            # (prefill pads roundup(prompt, block) planes out to cache_len)
            from repro.core.packed import _fit_group

            hd = cfg.resolved_head_dim
            g = _fit_group(args.kv_group, hd)
            blk = max(args.kv_block, 1)
            m_q = max(cfg.n_heads // cfg.n_kv_heads, 1)
            s_planes = -(-args.prompt_len // blk) * blk + args.gen
            ea = autotune.autotune_attn(m_q, hd, s_planes, group=g, dtype=jnp.int8)
            tuned[f"attn{m_q}x{hd}x{s_planes}:int8"] = {
                kk: ea[kk] for kk in ("bs", "us")
            }
            if args.engine:
                # engine decode shapes are keyed on the slot-pool geometry:
                # the gathered plane extent is always max_pages * page,
                # independent of which sequences are resident
                s_pool = bucket_len(
                    args.shared_prefix + args.prompt_len + args.gen, blk
                )
                attn_shapes = [(m_q, hd, s_pool)]
                if args.prefill_chunk:
                    # the chunk step's packed leg: C query rows, each
                    # expanded to grouped rows per kv head, against the
                    # same slot-pool plane extent
                    c_tok = args.prefill_chunk * blk
                    attn_shapes.append((c_tok * m_q, hd, s_pool))
                autotune.tune_attn_shapes(attn_shapes, group=g, dtype=jnp.int8)
                for mm, _, ss in attn_shapes:
                    ent = autotune.autotune_attn(mm, hd, ss, group=g, dtype=jnp.int8)
                    tuned[f"attn{mm}x{hd}x{ss}:int8:engine"] = {
                        kk: ent[kk] for kk in ("bs", "us")
                    }
        report["tuned_tiles"] = tuned
        report["tune_cache"] = str(autotune.cache_path())
        # tuning cost was silent before: total wall time + per-key
        # hit/miss/search counts straight from the autotuner
        report["tune_wall_s"] = round(time.time() - t_tune, 2)
        report["tune_stats"] = autotune.tune_stats()
    if args.artifact:
        import os

        from repro.checkpoint.artifact import load_pvqz, read_toc

        t0 = time.time()
        # blob -> PackedPVQ wall time lands in the trace as one span right
        # next to the engine's time-to-first-token spans; the per-codec
        # decode MB/s histograms underneath come from the artifact layer
        with obs.span("artifact/cold_start", args={"path": args.artifact}):
            params = load_pvqz(args.artifact, target=params)
        cold_s = time.time() - t0
        # entropy=False: the at-rest bits/weight is already in the export
        # report / TOC; don't re-price every pulse stream on serve startup
        st = packed_stats(params, entropy=False)
        toc = read_toc(args.artifact)
        report["pvq_mode"] = "artifact"
        report["artifact"] = args.artifact
        report["artifact_bytes"] = os.path.getsize(args.artifact)
        report["artifact_meta"] = toc.get("meta", {})
        report["pvq_tensors"] = st["packed_tensors"]
        report["artifact_decode_s"] = round(cold_s, 2)
        if obs.enabled():
            obs.gauge("artifact.cold_start_s").set(cold_s)
            # fold the per-codec throughput counters into the startup report
            snap = {
                (m["name"], m["labels"].get("codec")): m["value"]
                for m in obs.registry().snapshot()
                if m["name"].startswith("artifact.decode_") and m["kind"] == "counter"
            }
            mbps = {}
            for (name, codec), sym in snap.items():
                if name != "artifact.decode_symbols":
                    continue
                secs = snap.get(("artifact.decode_s", codec), 0.0)
                if secs:
                    mbps[codec] = round(sym / secs / 1e6, 1)
            if mbps:
                report["artifact_decode_mb_s"] = mbps
        report.update(_expert_report(params))
    elif args.pvq or args.pvq_sim:
        policy = QuantPolicy(
            rules=(("embedding", cfg.pvq.n_over_k_embed, cfg.pvq.group),
                   ("kernel|experts", args.n_over_k, cfg.pvq.group)),
            scale_mode="ls",
        )
        t0 = time.time()
        if args.pvq_sim:
            params, codes, _ = quantize_tree(params, policy)
            report["pvq_mode"] = "dequant-sim"
            report["pvq_tensors"] = len(codes)
            report.update({k: round(v, 3) for k, v in total_bits(codes).items()
                           if "ratio" in k or "bits_per" in k})
        else:
            params = quantize_params(params, policy)
            st = packed_stats(params, entropy=False)
            report["pvq_mode"] = "packed"
            report["pvq_tensors"] = st["packed_tensors"]
            report["packed_bytes"] = st["packed_bytes"]
            report["weight_compression_ratio"] = round(st["weight_compression_ratio"], 3)
            report.update(_expert_report(params))
        report["pvq_encode_s"] = round(time.time() - t0, 1)

    from repro.core.quantize import (
        ActQuant,
        KVQuant,
        act_quant_scope,
        kv_quant_scope,
        set_default_act_quant,
        set_default_kv_quant,
    )

    if args.act_int8:
        # one switch sets the process-wide contract: every packed matmul
        # below (dense, unembed, MoE dispatch buffers) quantizes its
        # activations and dispatches kernel v3 — no per-layer threading
        set_default_act_quant(ActQuant(mode="per_row"))
        report["act_quant"] = "int8:per_row"
    if args.kv_pvq:
        # same pattern for the KV cache: init_kv_cache /
        # attention_prefill_cache pick the default up and every attention
        # layer's cache comes out as a PackedKV (kernel-v4 decode)
        kvq = KVQuant(block=args.kv_block, group=args.kv_group)
        set_default_kv_quant(kvq)
        from repro.core.packed import _fit_group

        hd = cfg.resolved_head_dim
        g = _fit_group(kvq.group, hd)
        ng = hd // g
        packed_bpt = 2 * (hd + 4 * ng)  # per kv head: K+V pulses + scales
        dense_bpt = 2 * hd * 4  # f32 reference
        report["kv_quant"] = f"pvq:block{kvq.block}:g{g}:k{kvq.k}"
        report["kv_bytes_per_token_per_head"] = packed_bpt
        report["kv_bytes_ratio_vs_f32"] = round(packed_bpt / dense_bpt, 3)
        if packed_bpt / dense_bpt > args.max_kv_bytes_ratio:
            report["kv_bytes_fail"] = (
                f"packed KV bytes ratio {packed_bpt / dense_bpt:.3f} > "
                f"allowed {args.max_kv_bytes_ratio}"
            )
            return 1, report

    if obs.enabled() and args.act_int8:
        _probe_act_rows(params)

    if args.engine:
        from repro.launch.engine import PVQEngine, poisson_trace

        max_len = bucket_len(
            args.shared_prefix + args.prompt_len + args.gen, args.kv_block
        )
        trace = poisson_trace(
            args.requests, rate=args.rate, vocab=cfg.vocab_size,
            prompt_lens=(max(args.prompt_len // 2, 1), args.prompt_len),
            max_new=args.gen, seed=args.seed + 2,
            shared_prefix=args.shared_prefix,
        )
        eng = PVQEngine(
            model, params, n_slots=args.engine_slots, max_len=max_len,
            n_pages=args.engine_pages,
            prefill_chunk=args.prefill_chunk,
            prefill_batch=args.prefill_batch,
            prefix_cache=not args.no_prefix_cache,
        )
        t0 = time.time()
        eng.warmup(prompt_lens=[len(r.prompt) for r in trace])
        report["engine_warmup_s"] = round(time.time() - t0, 2)
        # Pallas kernels that lowered to Mosaic (0 when they interpret)
        report["engine_decode_mosaic_kernels"] = eng.decode_hlo().count(
            "tpu_custom_call"
        )
        res = eng.run(trace)
        outputs = res.pop("outputs")
        report["arch"] = cfg.name
        report.update({f"engine_{k}": v for k, v in res.items()})

        # baseline: the fixed-batch generate() loop run SEQUENTIALLY over
        # the same trace (one request at a time — what serving without
        # continuous batching degenerates to under ragged arrivals).
        # Warm its compile buckets first so both legs time steady state.
        prompts = {
            r.rid: jnp.asarray([r.prompt], jnp.int32) for r in trace
        }
        for r in trace[:1]:
            generate(model, params, prompts[r.rid], gen=args.gen,
                     cache_len=len(r.prompt) + args.gen)
        t0 = time.time()
        base_tokens = 0
        for r in trace:
            out = generate(model, params, prompts[r.rid], gen=args.gen,
                           cache_len=len(r.prompt) + args.gen)
            base_tokens += out.shape[1] - len(r.prompt)
        base_dt = time.time() - t0
        report["baseline_tokens_per_s"] = round(base_tokens / max(base_dt, 1e-9), 2)
        report["baseline_wall_s"] = round(base_dt, 2)
        speedup = res["tokens_per_s"] / max(report["baseline_tokens_per_s"], 1e-9)
        report["engine_speedup_vs_fixed_batch"] = round(speedup, 3)

        if args.agreement_min is not None:
            ag = engine_token_agreement(model, params, trace, outputs)
            report["engine_token_agreement"] = round(ag["engine_token_agreement"], 4)
            report["engine_tokens_compared"] = ag["engine_tokens_compared"]
            report["engine_ties_excused"] = ag["engine_ties_excused"]
            if ag["engine_token_agreement"] < args.agreement_min:
                report["agreement_fail"] = (
                    f"engine token agreement {ag['engine_token_agreement']:.4f}"
                    f" < required {args.agreement_min}"
                )
                return 1, report
        if args.min_speedup is not None and speedup < args.min_speedup:
            report["speedup_fail"] = (
                f"engine speedup {speedup:.3f}x < required {args.min_speedup}x"
            )
            return 1, report
        if (
            args.min_prefix_hits is not None
            and res["prefix_hits"] < args.min_prefix_hits
        ):
            report["prefix_cache_fail"] = (
                f"prefix cache hits {res['prefix_hits']} < required "
                f"{args.min_prefix_hits}"
            )
            return 1, report
        return 0, report

    key = jax.random.PRNGKey(args.seed + 1)
    tokens = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab_size)
    extra = {}
    if cfg.family == "encdec":
        extra["frames"] = jax.random.normal(key, (args.batch, args.prompt_len, cfg.d_model))
    if cfg.family == "vlm":
        extra["patches"] = jax.random.normal(key, (args.batch, cfg.prefix_len, cfg.d_model))

    t0 = time.time()
    out = generate(model, params, tokens, gen=args.gen,
                   cache_len=args.prompt_len + args.gen, extra_batch=extra)
    dt = time.time() - t0
    report.update({
        "arch": cfg.name, "batch": args.batch,
        "generated_shape": list(out.shape),
        "tokens_per_s": round(args.batch * args.gen / dt, 1),
        "wall_s": round(dt, 2),
    })

    if args.agreement_min is not None:
        # A/B legs: identical packed weights; the quantized leg keeps the
        # active ActQuant/KVQuant defaults, the reference leg clears BOTH
        # (f32 activations, dense f32 KV cache).  Contexts AND compute path
        # matched — both walk the same decode loop teacher-forced with the
        # quantized-leg tokens.  (A free-running comparison conflates
        # kernel fidelity with the autoregressive cascade — one near-tie
        # flip rewrites the whole suffix; a prefill re-score changes the
        # tile shapes, which int8 rounding amplifies into whole quanta.)
        lg_q = teacher_forced_logits(
            model, params, out, prompt_len=args.prompt_len, extra_batch=extra
        )
        with act_quant_scope(None), kv_quant_scope(None):
            lg_f = teacher_forced_logits(
                model, params, out, prompt_len=args.prompt_len,
                extra_batch=extra,
            )
        ag = top1_agreement(lg_f, lg_q)
        report["act_int8_top1_agreement"] = round(ag["top1_agreement"], 4)
        report["act_int8_top1_agreement_strict"] = round(
            ag["top1_agreement_strict"], 4
        )
        report["act_int8_ties_excused"] = ag["ties_excused"]
        if ag["top1_agreement"] < args.agreement_min:
            report["agreement_fail"] = (
                f"top-1 agreement {ag['top1_agreement']:.4f} < required "
                f"{args.agreement_min}"
            )
            return 1, report

    return 0, report


if __name__ == "__main__":
    raise SystemExit(main())
