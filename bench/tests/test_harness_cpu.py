"""The whole run at a tiny size on the CPU (kernels interpreted), with the
chip check skipped: sound runs come out correct, and the control (the
reference at int4 activations) and runs with the timed path broken
underneath come out not correct."""

import time

import jax
import jax.numpy as jnp
import pytest

from harness import cell, judge, spec

MODEL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
             vocab_size=128, rope_theta=10000.0, param_dtype="bfloat16", compute_dtype="bfloat16")
CONFIG = {"arch": "smollm-360m", "reference": "llama", "model": MODEL,
          "weights": {"group": 32, "n_over_k": 1.0, "n_over_k_embed": 0.5},
          "engine": {"n_slots": 4, "max_len": 128, "n_pages": 64, "page": 8, "kv_group": 8,
                     "kv_pulses": 127, "prefill_chunk": 2, "prefix_cache": True}}
TRAFFIC = {"generator": "backlog", "params": {
    "queue_factor": 2, "prompt": {"median": 12, "sigma": 0.5, "min": 8, "max": 16},
    "output": {"median": 24, "sigma": 0.5, "min": 8, "max": 48}}}
#: the tiny model's own limit: sound runs read 0-0.2 here, the control and
#: the faults 1 or more
LIMITS = {"sample_tokens": 200, "min_tokens": 30, "widest_gap": {"limit": 0.5}}
SEED = 2**31 + 77


def _run(seconds=2.0, on_engine=None):
    s = {"config": CONFIG, "reference": spec.reference(CONFIG), "traffic": TRAFFIC, "limits": LIMITS,
         "chips": 1, "generator": spec.generator(TRAFFIC["generator"])}
    return cell.run(s, SEED, seconds, False, t_start=time.perf_counter(),
                    require_tpu=False, on_engine=on_engine)


def test_sound_run_is_correct():
    out = _run()
    assert all(c["ok"] for c in out["checks"].values()), out["checks"]
    assert out["run"].window_s >= 2.0 and out["attempted"] > 4
    assert not out["compiled_in_window"]


def test_control_is_not_correct():
    """The reference at int4 activations in the program's place, on the
    requests a sound run served, judged as a run judges the program."""
    out = _run()
    recs = judge.pick_samples(out["run"].records, LIMITS["sample_tokens"], SEED)
    samples = [{"prompt": r.req.prompt, "served": r.req.generated} for r in recs]
    from harness import weights

    ref = spec.reference(CONFIG)
    arch = ref.arch(weights.model_config(CONFIG))
    _, raw = weights.make(arch, CONFIG, SEED, ref)
    reads = judge.readings(ref, arch, raw, samples,
                           CONFIG["engine"]["max_len"], control=True)
    assert all(c["ok"] for c in judge.checks(reads, LIMITS).values())
    assert not judge.checks(reads, LIMITS, key="control_gap")["widest_gap"]["ok"], reads


def _break_decode(fn):
    """Swap the engine's compiled decode step for ``fn`` wrapped around the
    engine's own step function, compiled the same way."""
    def on_engine(engine):
        step = engine._decode_fn
        engine._decode = jax.jit(lambda *args: fn(step, *args))
    return on_engine


def _state_unchanged(decode, params, cache, *args):
    """The decode step returns the cache it was given: nothing appended."""
    tok, _ = decode(params, cache, *args)
    return tok, cache


def _token_altered(decode, *args):
    """Every token the decode step produces is changed where it is made."""
    tok, cache = decode(*args)
    return (tok + 1) % MODEL["vocab_size"], cache


def _half_batch(decode, params, cache, tokens, *args):
    """The decode step computes the first half of the slots; the others
    are handed slot 0's input and answer."""
    tokens = jnp.asarray(tokens)
    half = tokens.shape[0] // 2
    tok, cache = decode(params, cache, tokens.at[half:].set(tokens[0]), *args)
    return tok.at[half:].set(tok[0]), cache


FAULTS = {"state_unchanged": _state_unchanged, "token_altered": _token_altered,
          "half_batch": _half_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault):
    out = _run(on_engine=_break_decode(FAULTS[fault]))
    assert not out["checks"]["widest_gap"]["ok"], out["checks"]
