"""The minRNN model module's plain reference against the program's
pure-jnp path, at a small size on the CPU, with the benchmark's own
weights."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import weights
from conftest import BENCH, smoke_conf
from models import minrnn

CELLS = ("mingru", "minlstm")


def setup(cell):
    from repro.configs import archs
    cfg = archs.smoke(f"{cell}-lm").replace(scan_strategy="associative")
    conf = smoke_conf(json.load(open(os.path.join(
        BENCH, "configs", f"{cell}-lm.json"))))
    params = weights.make(minrnn, conf, 2**35 + 9)
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, 256, (2, 48)), jnp.int32)
    return cfg, conf, params, toks


@pytest.mark.parametrize("cell", CELLS)
def test_forward_matches_program(cell):
    from repro.models import lm
    cfg, conf, params, toks = setup(cell)
    want, _ = lm.forward(params, cfg, toks)
    got = minrnn.forward(params, toks, conf)
    np.testing.assert_allclose(got, np.asarray(want)[..., :256], rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("cell", CELLS)
def test_loss_and_gradients_match_program(cell):
    from repro.models import lm
    cfg, conf, params, toks = setup(cell)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    (loss, _), grads = jax.value_and_grad(
        lambda p: lm.loss_fn(p, cfg, batch), has_aux=True)(params)
    rloss, rgrads = minrnn.loss_and_grad(params, batch, conf, rows=1)
    np.testing.assert_allclose(float(rloss), float(loss), rtol=1e-5)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(rgrads)):
        np.testing.assert_allclose(r, g, rtol=2e-3, atol=2e-6)


def test_control_differs_from_reference():
    cfg, conf, params, toks = setup("mingru")
    exact = minrnn.forward(params, toks, conf)
    low = minrnn.forward(params, toks, conf, control=True)
    rel = float(jnp.max(jnp.abs(low - exact)) / jnp.max(jnp.abs(exact)))
    assert 1e-3 < rel < 0.5
