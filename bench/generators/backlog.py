"""Backlog traffic: a queue kept full, as offline batch jobs keep it.

Parameters (``bench/traffic/<mix>.json``):

* ``queue_factor`` — requests kept waiting, as a multiple of the slots;
* ``prompt`` / ``output`` — lognormal lengths: ``median``, ``sigma``,
  ``min``, ``max``.

Requests come in rounds of ``n_slots``.  Every round holds the same
(prompt, output) pairs, stratified quantiles of the two lengths joined by
a fixed pairing.  The seed orders the first round and draws the token
ids.  Later rounds come in one order for every seed: a request admitted
while others decode costs the encode of its prompt's pages, so the size
of the one that takes a freed slot is work, and every seed then admits
the same sizes at the same steps.  Set-up
queues the first round and ``queue_factor`` more and admits until every
slot decodes, so the first round fills the slots whatever the seed; then
the window opens.  A request is due the moment it joins the queue.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from harness import draw


class Generator:
    def __init__(self, params: Dict, ctx: Dict):
        self.ctx = ctx
        self.round = int(ctx["n_slots"])
        self.depth = int(params["queue_factor"]) * self.round
        prompts = draw.stratified_lognormal(self.round, params["prompt"])
        outputs = draw.stratified_lognormal(self.round, params["output"])
        pairing = np.random.default_rng(0).permutation(self.round)
        self.pairs = [(prompts[i], outputs[j]) for i, j in enumerate(pairing)]
        self.prompt_range = (int(params["prompt"]["min"]), int(params["prompt"]["max"]))
        self._rng = draw.rng_for(ctx["seed"], 1)
        self._queue: List[Dict] = []
        self._rounds = 0

    def warm_prompt_lens(self) -> List[int]:
        lo, hi = self.prompt_range
        return list(range(lo, hi + 1, int(self.ctx["page"]))) + [hi]

    def _next(self) -> Dict:
        if not self._queue:
            # the seed orders the first round only (see the docstring)
            seed = self.ctx["seed"] if self._rounds == 0 else 0
            order = draw.rng_for(seed, 2, self._rounds).permutation(self.round)
            self._rounds += 1
            for i in order:
                plen, out = self.pairs[i]
                self._queue.append({
                    "prompt": draw.tokens(self._rng, plen, self.ctx["vocab"]),
                    "max_new": out,
                })
        return self._queue.pop(0)

    def setup_items(self) -> List[Dict]:
        return [self._next() for _ in range(self.round + self.depth)]

    def ready(self, engine) -> bool:
        return all(st is not None and st.phase == "decode" for st in engine.slots)

    def due(self, t_rel: float, n_pending: int) -> List[Dict]:
        out = []
        for _ in range(max(self.depth - n_pending, 0)):
            item = self._next()
            item["due"] = t_rel
            out.append(item)
        return out
