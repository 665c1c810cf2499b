"""The comparison that decides ``correct``.

Each sampled request's prompt and served tokens run once through the
reference (the configuration's module in ``bench/references``),
teacher-forced.  At every position that produced a served token the
reading is the gap by which the served token's logit lies below the
reference's best, in units of the reference logits' standard deviation at
that position; a request reads its widest gap.  A
greedy server that computes what the configuration states serves the
reference's best token or a near-tie of it, so its widest gap stays small;
a fault moves tokens off the reference's best by whole logit spreads.

The control puts the reference itself in the program's place at the next
precision down (int4 activations for int8): at each of the same positions
it reads the gap of the token that the lower precision ranks first.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def widest_gap(ref_rows: jax.Array, tokens: jax.Array) -> float:
    """The widest gap of ``tokens`` below the best of ``ref_rows``, in
    logit standard deviations (infinite where a token is out of range or a
    reading is not a number)."""
    vocab = ref_rows.shape[-1]
    best = jnp.max(ref_rows, axis=-1)
    own = jnp.take_along_axis(ref_rows, jnp.clip(tokens, 0, vocab - 1)[:, None], axis=-1)[:, 0]
    gap = (best - own) / jnp.std(ref_rows, axis=-1)
    bad = (tokens < 0) | (tokens >= vocab) | ~jnp.isfinite(gap)
    return float(jnp.max(jnp.where(bad, jnp.inf, gap)))


def readings(
    ref, arch: Dict, raw: Dict, samples: Sequence[Dict], t_pad: int, *, control: bool = False,
) -> List[Dict]:
    """Per sample ``{"served", "gap"[, "control_gap"]}``, through the
    reference module ``ref`` (``arch``, ``raw``: its ``arch`` and ``view``).
    Each sample is ``{"prompt": [...], "served": [...]}`` with
    ``len(prompt) + len(served) <= t_pad``."""
    forward = ref.make_forward(arch, 8)
    low = ref.make_forward(arch, 4) if control else None
    out = []
    for s in samples:
        plen, n = len(s["prompt"]), len(s["served"])
        seq = np.zeros((t_pad,), np.int32)
        seq[: plen + n] = np.concatenate([s["prompt"], s["served"]]).astype(np.int32)
        rows = slice(plen - 1, plen - 1 + n)
        ref_rows = forward(raw, jnp.asarray(seq))[rows]
        rec = {"served": n, "gap": widest_gap(ref_rows, jnp.asarray(s["served"], jnp.int32))}
        if low is not None:
            rec["control_gap"] = widest_gap(ref_rows, jnp.argmax(low(raw, jnp.asarray(seq))[rows], axis=-1))
        out.append(rec)
    return out


def pick_samples(records: Sequence, tokens: int, seed: int) -> List:
    """The request that was served most tokens, then others that were
    served any, in an order drawn from the seed, until the sample holds
    ``tokens`` served tokens (or every such request).  A request still
    streaming when serving stopped counts with the tokens it had been
    served: a backlog of long outputs finishes few requests in a window,
    and how many tokens each holds follows the program's speed, so the
    sample is sized by tokens and not by requests."""
    served = [r for r in records if r.req.generated]
    if not served:
        return []
    served.sort(key=lambda r: (-len(r.req.generated), r.req.rid))
    rest = served[1:]
    rng = np.random.default_rng([int(seed) % (1 << 64), 7])
    chosen = [served[0]]
    total = len(served[0].req.generated)
    for i in rng.permutation(len(rest)):
        if total >= tokens:
            break
        chosen.append(rest[i])
        total += len(rest[i].req.generated)
    return chosen


def checks(reads: Sequence[Dict], limits: Dict, key: str = "gap") -> Dict[str, Dict]:
    """The numbers compared, each with its limit: the widest gap (read
    under ``key``: ``"control_gap"`` judges the control in the program's
    place) and the tokens compared."""
    gap = max((r[key] for r in reads), default=float("inf"))
    tokens = sum(r["served"] for r in reads)
    lim = limits["widest_gap"]["limit"]
    return {
        "widest_gap": {"value": gap, "limit": lim, "ok": gap <= lim},
        "tokens_compared": {"value": tokens, "min": limits["min_tokens"], "ok": tokens >= limits["min_tokens"]},
    }
