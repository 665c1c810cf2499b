"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
        --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/run0 [--pvq-qat]

Wires together: config -> model -> AdamW -> jitted step on one device ->
deterministic data pipeline -> async checkpointing -> fault-tolerant
runner.  ``--pvq-qat`` trains with the
paper's mixed-optimization recipe (STE PVQ projection on matmul weights).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data import TokenLoader, TokenTask
from repro.nn.models import build_model
from repro.optim import AdamW, cosine_schedule
from repro.runtime.caches import enable_compile_cache
from repro.runtime.fault_tolerance import StragglerPolicy, TrainingRunner


def make_state_and_step(model, optimizer, *, pvq_qat=False, pvq_k=None, pvq_group=256, seed=0):
    """Returns (state=(params, opt_state), jitted step_fn(state, batch))."""

    params = model.init(jax.random.PRNGKey(seed), max_seq=4096)
    opt_state = optimizer.init(params)

    def maybe_project(p):
        if not pvq_qat:
            return p
        from repro.core.qat import pvq_ste
        from repro.core.quantize import QuantPolicy

        policy = QuantPolicy()

        def visit(path, leaf):
            pstr = "/".join(str(getattr(q, "key", getattr(q, "idx", q))) for q in path)
            if leaf.ndim >= 2 and policy.match(pstr) and leaf.size >= 1024:
                return pvq_ste(leaf, pvq_k or max(leaf.size // 1, 1) and pvq_k, pvq_group)
            return leaf

        return jax.tree_util.tree_map_with_path(visit, p)

    @jax.jit
    def step_fn(state, batch):
        params, opt_state = state
        # per-step rng for stochastic train features (MoE router jitter),
        # seeded by the run and advanced by the optimizer step counter
        rng = jax.random.fold_in(jax.random.PRNGKey(seed), opt_state.step)
        def loss_fn(p):
            return model.loss(maybe_project(p), batch, rng)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state, gnorm = optimizer.update(grads, opt_state, params)
        return (params, opt_state), dict(metrics, loss=loss, grad_norm=gnorm)

    return (params, opt_state), step_fn


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--pvq-qat", action="store_true")
    ap.add_argument("--pvq-k", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    optimizer = AdamW(lr=cosine_schedule(args.lr, warmup=20, total=args.steps))
    state, step_fn = make_state_and_step(
        model, optimizer, pvq_qat=args.pvq_qat, pvq_k=args.pvq_k, seed=args.seed
    )

    task = TokenTask(cfg.vocab_size, seed=args.seed)
    loader = TokenLoader(task, args.batch, args.seq, seed=args.seed)
    ckpt = Checkpointer(args.ckpt_dir, keep=3)
    runner = TrainingRunner(
        step_fn, state, loader, ckpt, ckpt_every=args.ckpt_every,
        straggler=StragglerPolicy(),
    )

    t0 = time.time()
    runner.run(args.steps)
    dt = time.time() - t0
    hist = runner.history
    first = sum(h["loss"] for h in hist[:10]) / max(len(hist[:10]), 1)
    last = sum(h["loss"] for h in hist[-10:]) / max(len(hist[-10:]), 1)
    print(json.dumps({
        "arch": cfg.name, "steps": len(hist), "wall_s": round(dt, 1),
        "loss_first10": round(first, 4), "loss_last10": round(last, 4),
        "stragglers_flagged": len(runner.straggler.flagged),
        "restores": runner.restores,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
