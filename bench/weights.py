"""Seeded random weights for a benchmarked model, made on the device.

The benchmark makes the weights and hands them to the program, so the
plain reference (the configuration's model module, ``bench/models/``) can
make the very same values again from the seed without taking anything the
program made.  One jitted call draws every leaf of the module's
``layout(conf)`` in the served dtype, leaf ``i`` of the sorted paths from
``fold_in(key, i)``.

Scales follow the program's own init where it has one (embedding std
0.02, 1/sqrt(fan_in) projections, conv std 1/sqrt(taps)); biases and
norm scales, which the program starts at 0 and 1, are drawn around those
values so that the comparison with the reference exercises them too.  A
``residual`` leaf is a projection drawn like ``dense`` and then centred,
each output's weights over its inputs; a module says why its family
needs it.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _draw(key, shape, kind):
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "embed":
        return 0.02 * n
    if kind == "dense":
        return n / math.sqrt(shape[-2])
    if kind == "residual":
        n = n / math.sqrt(shape[-2])
        return n - jnp.mean(n, axis=-2, keepdims=True)
    if kind == "conv":
        return n / math.sqrt(shape[-2])
    if kind == "bias":
        return 0.1 * n
    if kind == "scale":
        return 1.0 + 0.1 * n
    raise ValueError(kind)


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the low and high 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=None)
def _maker(model, conf_json: str):
    conf = json.loads(conf_json)
    lay = model.layout(conf)
    dtype = _DTYPES[conf["param_dtype"]]

    def make(key):
        tree = {}
        for i, (path, (shape, kind)) in enumerate(sorted(lay.items())):
            leaf = _draw(jax.random.fold_in(key, i), shape, kind)
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = leaf.astype(dtype)
        return tree

    return jax.jit(make)


def make(model, conf: dict, seed: int):
    """The served weights for ``seed``, in ``conf['param_dtype']``, laid
    out by the model module ``model``."""
    return _maker(model, json.dumps(conf, sort_keys=True))(seed_key(seed))
