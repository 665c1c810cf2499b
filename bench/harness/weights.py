"""Random weights made from the seed, in the form the program serves them.

The program decides that form: :func:`program_tree` is ``Model.init``'s
tree packed by the program's own ``quantize_params``, with the policy
that ``serve --pvq`` uses, traced abstractly (``jax.eval_shape``), so
that nothing is encoded.  One jitted call on the device then fills every
leaf of that tree from the seed (:func:`maker`):

* a ``PackedPVQ`` leaf gets, per group of its ``group`` weights
  (consecutive rows of a ``matmul``-layout code, one row of a ``flat``
  one), the PVQ code of a Gaussian vector: the int8 pulse vector of L1
  norm ``k`` nearest its direction (floor, then the largest remainders)
  and the least-squares rho that fits it to the Gaussian weight it stands
  for (std ``1 / sqrt(d_in)`` for a matmul code, 0.02 for a flat one, the
  embedding); weights past the logical shape are zero;
* any other floating leaf (the RMS norm scales) ``1 + 0.1 N(0, 1)``.

The reference reads the same arrays through its module's ``view``
(``bench/references``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

from harness import draw

EMBED_STD = 0.02


def model_config(config: Dict):
    """The program's configuration of ``config["arch"]`` with the keys of
    ``config["model"]`` replaced; a nested dict replaces fields of the
    base's own group (``"moe": {"n_experts": 8}`` keeps every other field
    of its ``MoEConfig``)."""
    from repro.configs import get_config

    base = get_config(config["arch"])
    changes = {}
    for k, v in config["model"].items():
        if isinstance(v, dict):
            group = getattr(base, k)
            if not hasattr(group, "_replace"):
                raise TypeError(f"model.{k}: {config['arch']} has no group of fields there ({group!r})")
            v = group._replace(**v)
        changes[k] = v
    return dataclasses.replace(base, **changes)


def program_tree(config: Dict):
    """The program's packed parameter tree, abstract: shapes, dtypes and
    the packing metadata ``quantize_params`` gives ``Model.init``'s tree."""
    from repro.core.packed import quantize_params
    from repro.core.quantize import QuantPolicy
    from repro.nn.models import build_model

    w = config["weights"]
    policy = QuantPolicy(
        rules=(("embedding", float(w["n_over_k_embed"]), int(w["group"])),
               ("kernel|experts", float(w["n_over_k"]), int(w["group"]))),
        scale_mode="ls",
    )
    model = build_model(model_config(config))
    max_seq = int(config["engine"]["max_len"])
    return jax.eval_shape(lambda k: quantize_params(model.init(k, max_seq=max_seq), policy),
                          jax.random.PRNGKey(0))


def pulses(x: jax.Array, k: int, axis: int) -> jax.Array:
    """Per group along ``axis``, the integer vector of L1 norm ``k``
    nearest the direction of ``x``: each ``k |x_i| / |x|_1`` floored, then
    one more pulse on the largest remainders until the norm is ``k``."""
    a = jnp.abs(x)
    t = a * (k / jnp.maximum(jnp.sum(a, axis=axis, keepdims=True), 1e-30))
    y = jnp.floor(t)
    rem = t - y
    left = k - jnp.sum(y, axis=axis, keepdims=True)
    g = x.shape[axis]
    idx = jnp.clip(g - left.astype(jnp.int32), 0, g - 1)
    nth = jnp.take_along_axis(jnp.sort(rem, axis=axis), idx, axis=axis)
    y = y + ((rem >= nth) & (left > 0) & (a > 0))
    return jnp.clip(jnp.sign(x) * y, -127, 127)


def _code(x: jax.Array, k: int, axis: int, std: float):
    """int8 pulses and the least-squares rho of ``std * x``, per group."""
    y = pulses(x, k, axis)
    yy = jnp.sum(y * y, axis=axis)
    rho = jnp.where(yy > 0, std * jnp.sum(x * y, axis=axis) / jnp.maximum(yy, 1.0), 0.0)
    return y.astype(jnp.int8), rho.astype(jnp.float32)


def _matmul_code(key, k_pad: int, n: int, g: int, k: int, d_in: int):
    x = jax.random.normal(key, (k_pad // g, g, n), jnp.float32)
    x = jnp.where((jnp.arange(k_pad) < d_in).reshape(k_pad // g, g, 1), x, 0.0)
    y, rho = _code(x, k, 1, d_in ** -0.5)
    return y.reshape(k_pad, n), rho


def _flat_code(key, groups: int, g: int, k: int, size: int):
    x = jax.random.normal(key, (groups, g), jnp.float32)
    x = jnp.where(jnp.arange(groups * g).reshape(groups, g) < size, x, 0.0)
    return _code(x, k, 1, EMBED_STD)


def _fill(key, leaf):
    from repro.core.packed import is_packed

    if not is_packed(leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            raise ValueError(f"no rule to make a {leaf.dtype} leaf")
        return (1.0 + 0.1 * jax.random.normal(key, leaf.shape, jnp.float32)).astype(leaf.dtype)
    lead = tuple(leaf.pulses.shape[:-2])
    rows, cols = leaf.pulses.shape[-2:]
    if leaf.layout == "matmul":
        one = lambda kk: _matmul_code(kk, rows, cols, leaf.group, leaf.k, leaf.shape[-2])  # noqa: E731
    elif leaf.layout == "flat":
        one = lambda kk: _flat_code(kk, rows, cols, leaf.k, math.prod(leaf.shape))  # noqa: E731
    else:
        raise ValueError(f"no rule to make a PVQ code of layout {leaf.layout!r}")
    if lead:
        p, s = jax.lax.map(one, jax.random.split(key, math.prod(lead)))
        p, s = p.reshape(lead + p.shape[1:]), s.reshape(lead + s.shape[1:])
    else:
        p, s = one(key)
    if (p.shape, p.dtype, s.shape, s.dtype) != (leaf.pulses.shape, leaf.pulses.dtype,
                                                 leaf.scales.shape, leaf.scales.dtype):
        raise ValueError(f"the program packs {leaf} otherwise than as int8 pulses and f32 rho per group")
    return dataclasses.replace(leaf, pulses=p, scales=s)


def maker(tree):
    """The jitted ``key -> params`` that fills every leaf of ``tree`` (an
    abstract :func:`program_tree`) on the device in one call."""
    from repro.core.packed import is_packed

    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_packed)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [_fill(kk, leaf) for kk, leaf in zip(keys, leaves)])

    return jax.jit(build)


def key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(draw.rng_for(seed, 0).integers(0, 2**31 - 1)))


def make(arch: Dict, config: Dict, seed: int, ref):
    """``(params, raw)``: the program's parameters made from the seed, and
    the view of the same arrays that the reference module ``ref`` reads
    (``arch``: its ``ref.arch`` of the configuration)."""
    params = maker(program_tree(config))(key(seed))
    return params, ref.view(arch, params)
