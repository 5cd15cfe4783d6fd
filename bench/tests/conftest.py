"""The benchmark's own tests: run by hand on the CPU, not in tier-1
(``pytest.ini`` collects only ``tests/``).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

``rehearsal_root`` copies the benchmark into a temporary directory and
cuts every cell to a size the CPU's Pallas interpreter runs in seconds:
each configuration to its program's own smoke preset (``smoke_conf``);
``rehearse`` then drives a whole run past the look for a chip, through
``harness.execute``, and returns its result line.
"""

from __future__ import annotations

import io
import json
import os
import shutil
import sys
import time
import types
from contextlib import redirect_stdout

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(REPO, "src"))
sys.path.insert(0, BENCH)


def smoke_conf(conf: dict) -> dict:
    """``conf`` with every key it states set to the value of the program's
    smoke preset for its arch, ``archs.smoke(conf["arch"])``."""
    import harness
    import models
    from repro.configs import archs
    return dict(conf, **harness.stated(archs.smoke(conf["arch"]), conf,
                                       models.load(conf)))


def _edit(path, fn):
    with open(path) as f:
        data = json.load(f)
    fn(data)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture
def rehearsal_root(tmp_path, monkeypatch):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    b = os.path.join(root, "bench")
    for name in os.listdir(os.path.join(b, "configs")):
        _edit(os.path.join(b, "configs", name),
              lambda c: c.update(smoke_conf(c)))

    def small_traffic(t):
        if "prompt_bytes" in t:
            t["prompt_bytes"].update(median=12, min=4, max=40)
            t["output_bytes"].update(min=4, max=12)
            if "median" in t["output_bytes"]:
                t["output_bytes"]["median"] = 6
        if "seq_len" in t:
            t["seq_len"] = 32

    def small_cell(c):
        if "engine" in c:
            c["engine"].update(slots=4, max_len=64)
        if "rate_per_s" in c:
            c["rate_per_s"] = 3.0
        if "batch" in c:
            c["batch"] = 4

    for name in os.listdir(os.path.join(b, "traffic")):
        _edit(os.path.join(b, "traffic", name), small_traffic)
    for name in os.listdir(os.path.join(b, "cells")):
        _edit(os.path.join(b, "cells", name), small_cell)
    import harness
    from repro.configs import archs
    monkeypatch.setattr(harness, "program_config",
                        lambda conf, model: archs.smoke(conf["arch"]))
    return root


def rehearse(root: str, workload: str, seconds: float = 2.0,
             seed: int = 2**33 + 5) -> dict:
    import jax

    import harness
    cell = harness.Cell(workload, root=root)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.execute(cell, args, jax.devices(), time.perf_counter())
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
