"""The harness is driven by data: what a later PR adds is found by name,
a configuration of another architecture included, and every cell runs end
to end on the CPU at a small size."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO, rehearse, smoke_conf

BENCH_JSON = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
WORKLOADS = [w["name"] for w in BENCH_JSON["workloads"]]

# A model module for a configuration of another block kind, written into
# a rehearsal root only.  It wraps the program, as no real one may.
SSM_MODULE = '''"""A model module for the harness's test of its own plumbing: a
configuration of the program's SSM block kind, found by name.  It wraps
the program's ``lm.init_params`` and ``lm.forward`` at the arch's smoke
preset, since it tests the plumbing and not a model.  A real model module
is a plain reference that imports nothing of the program."""

import math

import jax
import jax.numpy as jnp


def _cfg(conf):
    from repro.configs import archs
    return archs.smoke(conf["arch"])


def layout(conf):
    from repro.models import lm
    tree = jax.eval_shape(lambda k: lm.init_params(k, _cfg(conf)),
                          jax.random.PRNGKey(0))
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = tuple(p.key for p in path)
        kind = ("embed" if keys[0] == "embed" else
                "scale" if keys[-1] == "scale" else
                "bias" if keys[-1] == "bias" or leaf.ndim < 2 else "dense")
        out[keys] = (leaf.shape, kind)
    return out


def source_values(cfg):
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
            "mamba_d_state": cfg.ssm.d_state}


def forward(params, tokens, conf, control=False):
    from repro.models import lm
    logits, _ = lm.forward(params, _cfg(conf), tokens)
    return logits[..., :conf["vocab_size"]].astype(jnp.float32)


class Counts:
    def __init__(self, n):
        self.n = n

    def flops_per_token(self):
        return 2.0 * self.n

    def train_flops_per_token(self):
        return 6.0 * self.n


def counts(conf):
    return Counts(sum(math.prod(shape) for shape, _ in layout(conf).values()
                      if len(shape) >= 2))
'''


def test_new_cell_and_metric_found_by_name(rehearsal_root):
    import harness
    root = rehearsal_root
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["workloads"].append({"name": "mingru-lm.chat-copy",
                               "config": "mingru-lm", "traffic": "chat",
                               "chips": 1, "why": "added by a test"})
    bench["per_layer"].append({"name": "fake_probe.chat", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": "ttft_p95_ms",
                               "workloads": ["mingru-lm.chat-copy"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    with open(os.path.join(root, "bench", "cells", "mingru-lm.chat.json")) \
            as f:
        settings = f.read()
    with open(os.path.join(root, "bench", "cells",
                           "mingru-lm.chat-copy.json"), "w") as f:
        f.write(settings)
    with open(os.path.join(root, "bench", "metrics", "fake_probe.py"),
              "w") as f:
        f.write("def read(ctx, suffix):\n    return 42.0 if suffix == "
                "'chat' else None\n")
    sys.path.insert(0, os.path.join(root, "bench"))
    try:
        cell = harness.Cell("mingru-lm.chat-copy", root=root)
        assert cell.settings == json.loads(settings)
        assert [m["name"] for m in cell.per_layer] == ["fake_probe.chat"]
        got = harness.per_layer_metrics(cell, {})
        assert got == {"fake_probe.chat": {"value": 42.0, "unit": "ms"}}
    finally:
        sys.path.remove(os.path.join(root, "bench"))
        sys.modules.pop("metrics.fake_probe", None)


def test_readers_declare_what_benchmark_json_says():
    import harness
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for m in bench["per_layer"]:
        mod = harness.reader(m["name"])
        suffix = m["name"].split(".", 1)[1]
        for key, want in (("layer", m["layer"]), ("moves", m["moves"])):
            have = getattr(mod, key.upper())
            have = have[suffix] if isinstance(have, dict) else have
            assert have == want, (m["name"], key)
        assert (mod.UNIT, mod.SOURCE, mod.BETTER) == \
            (m["unit"], m["source"], m["better"]), m["name"]
        for w in m["workloads"]:
            assert w.endswith(suffix) or w.split(".", 1)[1].startswith(
                suffix), (m["name"], w)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         WORKLOADS[0], "--seed", str(2**33), "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, cwd=REPO,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_exits_nonzero_in_a_bare_checkout(tmp_path):
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", WORKLOADS)
def test_rehearsal_is_correct(rehearsal_root, workload):
    res = rehearse(rehearsal_root, workload)
    assert res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("config", [c["name"] for c in BENCH_JSON["configs"]])
def test_configuration_states_program_and_published_values(config):
    """The file matches the program's registered config, its meta keys
    aside, and states the source's value of every key it cuts."""
    import harness
    import models
    entry = [c for c in BENCH_JSON["configs"] if c["name"] == config][0]
    conf = json.load(open(os.path.join(REPO, entry["file"])))
    assert harness.program_config(conf, models.load(conf)).name == \
        conf["arch"]
    assert conf["reduced"] == entry["reduced"]
    for key in conf["reduced"]:
        assert key in conf["published"], key
        assert conf["published"][key] != conf[key], key
    assert harness.Cell([w["name"] for w in BENCH_JSON["workloads"]
                         if w["config"] == config][0]).model.__name__ \
        == "models." + conf["model"]


@pytest.mark.parametrize("key", ["num_hidden_layers", "d_modle"])
def test_configuration_key_the_program_lacks_is_refused(key):
    """A key outside the meta keys that names no field of the program's
    config and no source value of the model module (a source's own name
    the module does not give, a misspelt size) stops the run and is
    named; the source's values of cut keys belong under ``published``."""
    import harness
    import models
    conf = json.load(open(os.path.join(BENCH, "configs", "mingru-lm.json")))
    model = models.load(conf)
    with pytest.raises(SystemExit, match=repr(key)):
        harness.program_config(dict(conf, **{key: 12}), model)
    assert harness.program_config(
        dict(conf, published={**conf["published"], key: 12}), model)


def test_missing_model_module_names_its_file():
    import models
    with pytest.raises(SystemExit, match="bench/models/no_such_family.py"):
        models.load({"arch": "x", "model": "no_such_family"})


def test_configuration_of_another_block_kind_added_as_files(
        rehearsal_root, monkeypatch):
    """A configuration of the program's SSM block kind comes as new files
    in the rehearsal root (configuration, model module, cell) and entries
    in its BENCHMARK.json, with reason's traffic: the run is correct, and
    the traced context's counts come from its module.  Its file keeps some
    of the source's own key names, as a catalog model's must: each is
    compared with the program through the module's ``source_values``."""
    import harness
    import models
    from repro.configs import archs
    root = rehearsal_root
    b = os.path.join(root, "bench")
    conf = {"arch": "mamba2-370m", "model": "ssm_plumbing",
            "source": "arXiv:2405.21060", "reduced": [], "published": {},
            "why": "a test of the harness", "n_layers": 48, "d_model": 1024,
            "vocab_size": 50280, "norm": "rmsnorm", "tie_embeddings": True,
            "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
            "remat": "full",
            "ssm": {"d_state": 128, "expand": 2, "head_dim": 64,
                    "n_groups": 1, "conv_kernel": 4, "chunk": 256},
            "hidden_size": 1024, "num_hidden_layers": 48,
            "mamba_d_state": 128}
    with open(os.path.join(b, "models", "ssm_plumbing.py"), "w") as f:
        f.write(SSM_MODULE)
    # the rehearsal root's bench/models, as a checkout's own would be
    monkeypatch.setattr(models, "__path__",
                        [os.path.join(b, "models"), *models.__path__])
    try:
        model = models.load(conf)
        full = archs.get(conf["arch"])
        assert harness.stated(full, conf, model) == {
            k: v for k, v in conf.items() if k not in harness.META}
        assert harness.stated(full, dict(conf, hidden_size=999),
                              model)["hidden_size"] == 1024
        with pytest.raises(SystemExit, match="'hidden_sise'"):
            harness.stated(full, dict(conf, hidden_sise=1024), model)
        small = smoke_conf(conf)
        assert small["n_layers"] == small["num_hidden_layers"] == \
            archs.smoke(conf["arch"]).n_layers < 48
        assert small["hidden_size"] == small["d_model"] < 1024
        with open(os.path.join(b, "configs", "ssm-plumbing.json"),
                  "w") as f:
            json.dump(small, f)
        with open(os.path.join(b, "cells", "ssm-plumbing.reason.json"),
                  "w") as f:
            json.dump({"engine": {"slots": 4, "decode_block": 8,
                                  "prompt_chunk": 1, "max_len": 64,
                                  "mesh": None},
                       "limits": {"max_logit_gap": 0.01}}, f)
        bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
        bench["configs"].append({"name": "ssm-plumbing",
                                 "source": conf["source"],
                                 "file": "bench/configs/ssm-plumbing.json",
                                 "reduced": [], "why": conf["why"]})
        bench["workloads"].append({"name": "ssm-plumbing.reason",
                                   "config": "ssm-plumbing",
                                   "traffic": "reason", "chips": 1,
                                   "why": "added by a test"})
        for m in bench["end_to_end"]:
            if "minlstm-lm.reason" in m.get("workloads", []):
                m["workloads"].append("ssm-plumbing.reason")
        json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
        res = rehearse(root, "ssm-plumbing.reason")
        assert res["correct"] is True, res
        assert res["attempted"] > 0 and res["failed"] == 0
        assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
        cell = harness.Cell("ssm-plumbing.reason", root=root)
        assert cell.model.__name__ == "models.ssm_plumbing"
        shape = cell.model.counts(cell.conf)
        assert shape.train_flops_per_token() == \
            3 * shape.flops_per_token() > 0
    finally:
        sys.modules.pop("models.ssm_plumbing", None)
