"""Seeded random weights for a benchmarked minRNN LM, made on the device.

The benchmark makes the weights and hands them to the program, so the
plain reference (``reference.py``) can make the very same values again
from the seed without taking anything the program made.  One jitted call
draws every leaf in the served dtype.  The layout is the one the
program's ``lm.init_params`` builds for ``block_kind="minrnn"``; the
harness checks the two trees agree leaf for leaf before a run.

Scales follow the program's own init where it has one (embedding std
0.02, 1/sqrt(fan_in) projections, conv std 1/sqrt(taps)); biases and
norm scales, which the program starts at 0 and 1, are drawn around those
values so that the comparison with the reference exercises them too.

The two projections that write into the residual stream (``down`` and
``mlp_out``) have each output's weights centred over their inputs.  The
gelu and the minRNN's g() have positive means, so with plain draws every
layer adds one constant vector to the stream; twelve of them swamp the
tokens, and the logits barely depend on the context (on seed 13 of
``minlstm-lm``, 3 distinct greedy tokens over 320 corpus positions,
against 69 with centred draws).  Greedy requests then loop on one
context, and a check of served tokens sees a lower precision only on
the seeds whose constant logits happen to hold a near-tie.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

_GATES = {"mingru": ("wz", "wh"), "minlstm": ("wf", "wi", "wh")}
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def layout(conf: dict) -> dict:
    """{path tuple: (shape, kind)} for every leaf; kind picks the draw."""
    mr = conf["minrnn"]
    L, d, ff, v = (conf["n_layers"], conf["d_model"], conf["d_ff"],
                   conf["vocab_size"])
    dh = int(d * mr["expansion"])
    vp = -(-v // 128) * 128
    out = {("embed", "table"): ((vp, d), "embed"),
           ("final_norm", "scale"): ((d,), "scale")}
    blk = {("norm_rnn", "scale"): ((d,), "scale"),
           ("down", "kernel"): ((dh, d), "residual"),
           ("conv", "kernel"): ((mr["conv_kernel"], d), "conv"),
           ("conv", "bias"): ((d,), "bias"),
           ("norm_mlp", "scale"): ((d,), "scale"),
           ("mlp_in", "kernel"): ((d, ff), "dense"),
           ("mlp_in", "bias"): ((ff,), "bias"),
           ("mlp_out", "kernel"): ((ff, d), "residual"),
           ("mlp_out", "bias"): ((d,), "bias")}
    for g in _GATES[mr["cell"]]:
        blk[("rnn", g, "kernel")] = ((d, dh), "dense")
        blk[("rnn", g, "bias")] = ((dh,), "bias")
    for path, (shape, kind) in blk.items():
        out[("layers", "blocks") + path] = ((L,) + shape, kind)
    return out


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
def _maker(conf_json: str):
    conf = json.loads(conf_json)
    lay = layout(conf)
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


def make(conf: dict, seed: int):
    """The served weights for ``seed``, in ``conf['param_dtype']``."""
    return _maker(json.dumps(conf, sort_keys=True))(seed_key(seed))
