"""A run with the timed path broken underneath reads ``correct: false``.

Each test drives a whole run at a small size past the look for a chip,
with one fault planted in the program below the benchmark: a token
altered where it is sampled, a superstep or train step that hands back
its state unchanged, a train step that leaves out half of its batch.
The cells run on one chip each, so there is no exchange between chips
to leave out.
"""

from __future__ import annotations

import json
import os

import pytest

from conftest import REPO, rehearse

BENCH_JSON = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
SERVING = [w["name"] for w in BENCH_JSON["workloads"]
           if not w["traffic"].startswith("train")]
TRAINING = [w["name"] for w in BENCH_JSON["workloads"]
            if w["traffic"].startswith("train")]


@pytest.mark.parametrize("workload", SERVING)
def test_altered_token_is_caught(rehearsal_root, workload, monkeypatch):
    import jax.numpy as jnp
    from repro.serving import sampling
    orig = sampling.sample_tokens

    def worst_token(logits, keys, *a):
        _, new_keys = orig(logits, keys, *a)
        return jnp.argmin(logits, -1).astype(jnp.int32), new_keys

    monkeypatch.setattr(sampling, "sample_tokens", worst_token)
    res = rehearse(rehearsal_root, workload)
    assert res["correct"] is False, res


@pytest.mark.parametrize("workload", SERVING)
def test_superstep_returning_its_state_is_caught(rehearsal_root, workload,
                                                 monkeypatch):
    import jax.numpy as jnp
    from drivers import serve
    from repro.models import lm
    setup = serve.Session.setup

    def frozen(params, cfg, state, n, **kw):
        b = state["tok"].shape[0]
        none = jnp.full((b, n), -1, jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        return none, none, state, {
            "prefill_steps": zero, "prefill_rounds": zero,
            "wasted_slot_steps": zero, "nonfinite_decode_rounds": zero,
            "nonfinite": jnp.zeros((b, n), bool)}

    def setup_then_break(self):
        setup(self)                         # warm up the sound program
        monkeypatch.setattr(lm, "superstep", frozen)
        self.eng._superstep_fns.clear()

    monkeypatch.setattr(serve.Session, "setup", setup_then_break)
    res = rehearse(rehearsal_root, workload)
    assert res["correct"] is False, res


def _patch_train_step(monkeypatch, wrap):
    from repro.training import train_step
    orig = train_step.make_train_step
    monkeypatch.setattr(train_step, "make_train_step",
                        lambda cfg, ocfg, **kw: wrap(orig(cfg, ocfg, **kw)))


@pytest.mark.parametrize("workload", TRAINING)
def test_train_step_returning_its_state_is_caught(rehearsal_root, workload,
                                                  monkeypatch):
    def unchanged(step):
        def run(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return run

    _patch_train_step(monkeypatch, unchanged)
    res = rehearse(rehearsal_root, workload)
    assert res["correct"] is False, res


@pytest.mark.parametrize("workload", TRAINING)
def test_half_batch_is_caught(rehearsal_root, workload, monkeypatch):
    def half(step):
        def run(params, opt_state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt_state, {k: v[:n] for k, v in
                                            batch.items()})
        return run

    _patch_train_step(monkeypatch, half)
    res = rehearse(rehearsal_root, workload)
    assert res["correct"] is False, res
