"""The generators' lengths from a seed."""

import numpy as np
import pytest

from harness import draw, spec

CTX = {"vocab": 1000, "n_slots": 8, "page": 32}
BIG_SEED = 2**31 + 12345


def _gen(kind, params, seed, **ctx):
    return spec.generator(kind).Generator(params, {**CTX, "seed": seed, **ctx})


BACKLOG = {"queue_factor": 2,
           "prompt": {"median": 256, "sigma": 0.5, "min": 64, "max": 512},
           "output": {"median": 768, "sigma": 0.5, "min": 256, "max": 1536}}


def test_stratified_lognormal_median_and_clip():
    xs = draw.stratified_lognormal(101, {"median": 256, "sigma": 0.5, "min": 64, "max": 512})
    assert xs == sorted(xs) and xs[50] == 256 and min(xs) >= 64 and max(xs) <= 512
    assert xs[-1] == 512  # the top quantile reaches past the clip


def test_backlog_rounds_hold_the_same_sizes_in_a_seeded_order():
    a, b = _gen("backlog", BACKLOG, 1), _gen("backlog", BACKLOG, BIG_SEED)
    ia, ib = a.setup_items(), b.setup_items()
    assert len(ia) == len(ib) == 8 + 16  # first round + queue_factor x slots
    sizes = lambda items: sorted((len(i["prompt"]), i["max_new"]) for i in items)  # noqa: E731
    assert sizes(ia[:8]) == sizes(ib[:8]) == sizes(ia[8:16])
    assert [len(i["prompt"]) for i in ia[:8]] != [len(i["prompt"]) for i in ib[:8]]
    # the requests that take the places of finished ones: one order for every seed
    later = lambda items: [(len(i["prompt"]), i["max_new"]) for i in items[8:]]  # noqa: E731
    assert later(ia) == later(ib) and later(ia)[:8] != later(ia)[8:]
    again = _gen("backlog", BACKLOG, 1).setup_items()
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(ia, again))


def test_backlog_keeps_the_queue_at_depth():
    g = _gen("backlog", BACKLOG, 3)
    g.setup_items()
    assert len(g.due(1.0, 16)) == 0
    topped = g.due(2.5, 10)
    assert len(topped) == 6 and all(i["due"] == 2.5 for i in topped)
