"""The harness is driven by data: what a later PR adds is found by name,
and every cell runs end to end on the CPU at a small size."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO, rehearse

WORKLOADS = [w["name"] for w in
             json.load(open(os.path.join(REPO, "BENCHMARK.json")))
             ["workloads"]]


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
