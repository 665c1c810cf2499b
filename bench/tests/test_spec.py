"""BENCHMARK.json against the files that serve it, and the entry point's
refusal to run without a TPU."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec, weights
from harness.weights import model_config

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_piece_is_found_by_name():
    b = spec.benchmark()
    assert b["command"] == ["python3", "bench/run.py"] and b["paths"] == ["bench"]
    for w in b["workloads"]:
        s = spec.cell(w["name"])
        assert s["end_to_end"] and s["per_layer"]
        assert any(m["name"] == "setup_s" for m, _ in s["end_to_end"])
        assert all(hasattr(r, "read") for _, r in s["end_to_end"] + s["per_layer"])
        assert all(hasattr(s["reference"], f) for f in ("arch", "view", "make_forward"))
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]] + [w["name"] for w in b["workloads"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for m in b["per_layer"]:
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}


@pytest.mark.parametrize("conf", [c["name"] for c in spec.benchmark()["configs"]])
def test_config_file_is_what_runs(conf):
    c = {c["name"]: c for c in spec.benchmark()["configs"]}[conf]
    f = spec._json(spec.ROOT / c["file"])
    assert f["reduced"] == c["reduced"] and f["source"] == c["source"]
    cfg = model_config(f)
    for k, v in f["model"].items():
        assert getattr(cfg, k) == v, k
    assert f["engine"]["max_len"] <= f["context_length"]


SMALL = {"arch": "smollm-360m", "reference": "llama", "model": dict(
    n_layers=2, d_model=96, n_heads=4, n_kv_heads=2, head_dim=24, d_ff=160, vocab_size=64),
    "weights": {"group": 64, "n_over_k": 1.0, "n_over_k_embed": 0.5}, "engine": {"max_len": 64}}


def test_weights_fill_the_programs_own_tree():
    """The weights fill the tree that the program's ``quantize_params``
    gives ``Model.init``'s tree (structure, shapes, dtypes and packing
    metadata) with PVQ codes: every group's pulses have L1 norm ``k`` and
    the rows past the logical contraction dim are zero."""
    from repro.core.packed import is_packed

    tree = weights.program_tree(SMALL)
    ref = spec.reference(SMALL)
    params, raw = weights.make(ref.arch(model_config(SMALL)), SMALL, 2**31 + 5, ref)
    assert jax.tree.structure(params) == jax.tree.structure(tree)  # packing metadata included
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(tree)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    packed = [p for p in jax.tree.leaves(params, is_leaf=is_packed) if is_packed(p)]
    assert {p.layout for p in packed} == {"matmul", "flat"}
    for p in packed:
        y = np.abs(np.asarray(p.pulses, np.int64))
        if p.layout == "matmul":
            lead, (k_pad, n) = y.shape[:-2], y.shape[-2:]
            assert (y.reshape(lead + (k_pad // p.group, p.group, n)).sum(-2) == p.k).all()
            assert not y[..., p.shape[-2]:, :].any()
        else:
            assert (y.sum(-1) == p.k).all()
    assert raw["layers"]["wq"]["pulses"].shape[0] == 2 and raw["embed"]["pulses"].shape[-1] == 32


def test_weights_are_what_the_programs_encoder_gives():
    """``quantize_params`` applied to the dense weights that the codes
    stand for gives the same codes back: they are codes the program would
    make, not a format of the benchmark's own."""
    from repro.core.packed import is_packed, quantize_params
    from repro.core.quantize import QuantPolicy

    ref = spec.reference(SMALL)
    params, _ = weights.make(ref.arch(model_config(SMALL)), SMALL, 11, ref)
    dense = jax.tree.map(lambda p: p.dequantize(jnp.float32) if is_packed(p) else p, params,
                         is_leaf=is_packed)
    policy = QuantPolicy(rules=(("embedding", 0.5, 64), ("kernel|experts", 1.0, 64)), scale_mode="ls")
    again = quantize_params(dense, policy)
    for a, b in zip(jax.tree.leaves(params, is_leaf=is_packed), jax.tree.leaves(again, is_leaf=is_packed)):
        if is_packed(a):
            assert (np.asarray(a.pulses) == np.asarray(b.pulses)).all()
            np.testing.assert_allclose(np.asarray(a.scales), np.asarray(b.scales), rtol=1e-5)


def test_no_tpu_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload", "smollm-360m.batch-longgen",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 2
    assert "needs a TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
