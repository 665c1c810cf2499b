"""A configuration names its reference module, and the harness reaches the
architecture only through it: a module written for one test is found by
the same lookup and drives the weights' view, the comparison and the count
readers; the Llama module reads what the harness read before it was one;
a configuration that names none, or an unknown one, stops the cell."""

import dataclasses
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from harness import judge, spec, weights
from harness.cell import Run, Step
from test_harness_cpu import CONFIG, SEED

#: a module of the reference interface that no harness file names: logits
#: from the embedding alone, and work counts that are easy to check
PLUGIN = "plugin_embedding_only"
PLUGIN_SOURCE = '''
import jax
import jax.numpy as jnp


def arch(cfg):
    return {"vocab_size": cfg.vocab_size, "d_model": cfg.d_model}


def view(arch, params):
    from repro.core.packed import is_packed

    for path, leaf in jax.tree_util.tree_flatten_with_path(params, is_leaf=is_packed)[0]:
        if "/".join(str(getattr(p, "key", p)) for p in path).endswith("embed/embedding"):
            return {"pulses": leaf.pulses, "scales": leaf.scales}
    raise ValueError("no embedding")


def make_forward(arch, act_bits):
    def forward(raw, tokens):
        table = (raw["pulses"].astype(jnp.float32) * raw["scales"][:, None]).reshape(
            arch["vocab_size"], arch["d_model"])
        return table[tokens] @ table.T

    return jax.jit(forward)
'''
COUNTS_SOURCE = '''

def v3_step(arch, m, group, peaks):
    return 1e-3 * m


def v4_step(arch, kv_lens, kv_group, peaks):
    return 1e-6 * sum(kv_lens)


def decode_token_ops(arch, ctx):
    return 1e9 + ctx
'''
PEAKS = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TRACE = {"devices": 1, "window_ns": 2e9, "busy_ns": 1e9, "modules": {}, "op_stats": {},
         "ops": {"jit__decode_fn": {"pvq_matmul_q.58": 4e6, "pvq_attn_q.8": 1e6}}}


def _read(name, run):
    return spec._module(spec.BENCH / "metrics" / f"{name}.py").read(run)


@pytest.fixture
def plugins(tmp_path, monkeypatch):
    (tmp_path / f"{PLUGIN}.py").write_text(PLUGIN_SOURCE + COUNTS_SOURCE)
    (tmp_path / f"{PLUGIN}_uncounted.py").write_text(PLUGIN_SOURCE)
    monkeypatch.setattr(spec, "REFERENCES", tmp_path)
    return tmp_path


def test_a_plugged_in_reference_drives_the_harness(plugins):
    config = dict(CONFIG, reference=PLUGIN)
    ref = spec.reference(config)
    arch = ref.arch(weights.model_config(config))
    params, raw = weights.make(arch, config, SEED, ref)
    assert set(raw) == {"pulses", "scales"}
    # the tokens the plugged-in forward ranks first read a gap of 0, others more
    forward = ref.make_forward(arch, 8)
    prompt, t_pad = np.arange(1, 9), CONFIG["engine"]["max_len"]
    seq = np.zeros((t_pad,), np.int32)
    seq[:8] = prompt
    best = [int(np.argmax(np.asarray(forward(raw, seq))[7]))]
    reads = judge.readings(ref, arch, raw, [{"prompt": prompt, "served": best},
                                            {"prompt": prompt, "served": [(best[0] + 1) % 128]}],
                           t_pad, control=True)
    assert reads[0]["gap"] == 0.0 and reads[1]["gap"] > 0.0
    assert reads[0]["control_gap"] == 0.0  # the forward here ignores act_bits

    run = Run(arch=arch, config={"engine": {"page": 4, "kv_group": 4}, "weights": {"group": 32}},
              records=[], t0=0.0, t1=2.0, setup_s=1.0,
              steps=[Step(0.5, 0.6, [5, 9]), Step(0.6, 0.7, [6])], trace=TRACE, peaks=PEAKS, ref=ref)
    assert _read("v3_roofline", run) == pytest.approx(100.0 * (2e-3 + 1e-3) / 4e-3)
    assert _read("v4_roofline", run) == pytest.approx(100.0 * 1e-6 * (4 + 8 + 4) / 1e-3)
    assert _read("decode_mfu", run) == pytest.approx(100.0 * (3e9 + 20) / (2.0 * 1e12))

    run.ref = spec.reference(dict(CONFIG, reference=f"{PLUGIN}_uncounted"))
    for name in ("v3_roofline", "v4_roofline", "decode_mfu"):
        assert _read(name, run) is None, name

    for d in ("harness", "metrics"):
        for f in (spec.BENCH / d).glob("*.py"):
            assert PLUGIN not in f.read_text(), f


#: ``weights.make``'s reference arrays and ``judge.readings``' gaps at the
#: tiny CPU configuration, computed before the reference became a module:
#: per role the pulses' shape, a digest of their bytes and the sum of rho;
#: per norm its shape and digest
PARENT_RAW = {
    "embed": [[256, 32], "692aa2aa93726b5e", 2.0192501223646104],
    "wq": [[2, 64, 64], "45c8f49e5c878255", 23.89070187881589],
    "wk": [[2, 64, 32], "99ce03888a15455c", 11.907518994063139],
    "wv": [[2, 64, 32], "9d33bd081f29958f", 11.829056490212679],
    "wo": [[2, 64, 64], "959d2535357b7d6a", 23.491406455636024],
    "wi_gate": [[2, 64, 96], "dbbbf9839dca140a", 35.94872768595815],
    "wi_up": [[2, 64, 96], "560f3bd4e2c26d9e", 35.292592126876116],
    "wo_ffn": [[2, 96, 64], "b68dd69b89f79978", 29.081251207739115],
    "norm_ln_mix": [[2, 64], "25a9061297c7d255"],
    "norm_ln_ffn": [[2, 64], "5b930d55489510f2"],
    "norm_final": [[64], "154430860d972ad9"],
}
PARENT_GAPS = [3.792009115219116, 5.045720100402832, 4.297944068908691]
PARENT_CONTROL_GAPS = [0.26602256298065186, 0.9980238676071167, 1.040844440460205]


def _digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


def test_the_llama_module_reads_what_the_harness_read():
    ref = spec.reference(CONFIG)
    arch = ref.arch(weights.model_config(CONFIG))
    _, raw = weights.make(arch, CONFIG, SEED, ref)
    got = {"embed": raw["embed"], **raw["layers"]}
    assert set(got) | {f"norm_{n}" for n in raw["norms"]} == set(PARENT_RAW)
    for role, c in got.items():
        shape, digest, rho = PARENT_RAW[role]
        assert [list(c["pulses"].shape), _digest(c["pulses"])] == [shape, digest], role
        assert float(np.asarray(c["scales"], np.float64).sum()) == pytest.approx(rho, rel=1e-6), role
    for n, a in raw["norms"].items():
        assert [list(a.shape), _digest(a)] == PARENT_RAW[f"norm_{n}"], n

    rng = np.random.default_rng(5)
    samples = [{"prompt": rng.integers(0, 128, n).tolist(), "served": rng.integers(0, 128, m).tolist()}
               for n, m in ((9, 6), (16, 20), (12, 40))]
    reads = judge.readings(ref, arch, raw, samples,
                           CONFIG["engine"]["max_len"], control=True)
    assert [r["served"] for r in reads] == [6, 20, 40]
    assert [r["gap"] for r in reads] == pytest.approx(PARENT_GAPS, rel=1e-6)
    assert [r["control_gap"] for r in reads] == pytest.approx(PARENT_CONTROL_GAPS, rel=1e-6)


@pytest.mark.parametrize("reference", [None, "no_such_architecture"])
def test_a_config_without_a_known_reference_stops_the_cell(tmp_path, monkeypatch, reference):
    name = "smollm-360m.batch-longgen"
    b = spec.benchmark()
    conf = {c["name"]: c for c in b["configs"]}[{w["name"]: w for w in b["workloads"]}[name]["config"]]
    config = spec._json(spec.ROOT / conf["file"])
    config.pop("reference")
    if reference is not None:
        config["reference"] = reference
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    conf["file"] = str(path)
    monkeypatch.setattr(spec, "benchmark", lambda: b)
    with pytest.raises((KeyError, FileNotFoundError)) as err:
        spec.cell(name)
    assert str(path) in str(err.value)
    if reference is not None:
        assert str(spec.REFERENCES / f"{reference}.py") in str(err.value)



def test_a_reference_without_a_listed_metrics_count_stops_the_cell(plugins, tmp_path, monkeypatch):
    name = "smollm-360m.batch-longgen"
    b = spec.benchmark()
    conf = {c["name"]: c for c in b["configs"]}[{w["name"]: w for w in b["workloads"]}[name]["config"]]
    config = dict(spec._json(spec.ROOT / conf["file"]), reference=f"{PLUGIN}_uncounted")
    path = tmp_path / "config.json"
    path.write_text(json.dumps(config))
    conf["file"] = str(path)
    monkeypatch.setattr(spec, "benchmark", lambda: b)
    with pytest.raises(AttributeError) as err:
        spec.cell(name)
    assert str(plugins / f"{PLUGIN}_uncounted.py") in str(err.value)
    assert any(f"'{count}'" in str(err.value) for count in ("v3_step", "v4_step", "decode_token_ops"))
    config["reference"] = PLUGIN
    path.write_text(json.dumps(config))
    assert spec.cell(name)["reference"].__file__ == str(plugins / f"{PLUGIN}.py")

def test_a_nested_group_replaces_fields_of_the_published_one():
    from repro.configs import get_config
    from repro.nn.moe import MoEConfig

    published = get_config("deepseek-v2-lite-16b")
    cfg = weights.model_config({"arch": "deepseek-v2-lite-16b", "model": {"moe": {"n_experts": 8}}})
    assert isinstance(cfg.moe, MoEConfig) and cfg.moe.n_experts == 8
    assert cfg.moe == published.moe._replace(n_experts=8)
    assert cfg == dataclasses.replace(published, moe=cfg.moe)
    with pytest.raises(TypeError):
        weights.model_config({"arch": "smollm-360m", "model": {"moe": {"n_experts": 8}}})


def test_every_configuration_names_a_module_of_the_interface():
    for c in spec.benchmark()["configs"]:
        config = spec._json(spec.ROOT / c["file"])
        ref = spec.reference(config)
        assert Path(ref.__file__).parent == spec.REFERENCES
        arch = ref.arch(weights.model_config(config))
        assert arch["vocab_size"] == config["model"]["vocab_size"]
        assert callable(ref.view) and callable(ref.make_forward)
