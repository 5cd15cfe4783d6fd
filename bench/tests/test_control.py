"""The float8 control fails the comparison that decides ``correct``.

``calibrate.py`` reads the control on the chip at each cell's own size;
these tests read it at a size the CPU holds: the calibration paths run
end to end on the small rehearsal cells, and at the cell's width the
control's readings exceed every cell's limits on corpus text.
"""

from __future__ import annotations

import json
import os

import pytest

from conftest import REPO

BENCH_JSON = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def _cell(root, name):
    import harness
    return harness.Cell(name, root=root)


@pytest.mark.parametrize("workload", [
    w["name"] for w in BENCH_JSON["workloads"]])
def test_calibration_runs_at_small_size(rehearsal_root, workload):
    import calibrate
    cell = _cell(rehearsal_root, workload)
    if cell.traffic["driver"] == "train":
        row = calibrate.training(cell, 2**32 + 3, faults=True)
        assert set(row) >= {"program", "half_batch", "control"}
        assert row["program"]["loss_gap"] < 1e-5
    else:
        row = calibrate.serving(cell, 2**32 + 3, 2.0)
        assert row["tokens"] > 0 and row["program_max"] < 1e-5


SERVING = [w for w in BENCH_JSON["workloads"]
           if not w["traffic"].startswith("train")]


@pytest.mark.parametrize("workload", [w["name"] for w in SERVING])
def test_control_fails_serving_limit_at_cell_width(workload):
    """Corpus text at the cell's width: a token the float8 control puts
    first lies further below the reference's best than the cell's limit
    allows."""
    import numpy as np

    import corpus
    import harness
    from drivers import serve
    cell = harness.Cell(workload)
    rng = np.random.default_rng(7)
    seqs = [(corpus.prompt(rng, 48), corpus.prompt(rng, 208), 0)
            for _ in range(4)]
    gaps = serve.logit_gaps(cell.model, cell.conf, 2**32 + 17, seqs, 256,
                            control=True)
    assert gaps["control_max"] > cell.settings["limits"]["max_logit_gap"], \
        gaps


TRAINING = [w for w in BENCH_JSON["workloads"]
            if w["traffic"].startswith("train")]


@pytest.mark.parametrize("workload", [w["name"] for w in TRAINING])
def test_control_fails_training_limits_at_cell_width(workload):
    """Two rows of 128 bytes at the cell's width: the float8 control's
    three steps depart from the float32 reference's by more than one of
    the cell's limits allows."""
    import harness
    from drivers import train
    cell = harness.Cell(workload)
    args = (cell.model, cell.conf, 2**32 + 19, 2, 128,
            cell.traffic["optimizer"], 2)
    ref = train.follow_reference(*args)
    ctrl = train.follow_reference(*args, control=True)
    got = train.compare(ctrl, ref, cell.settings["limits"])
    assert any(c["value"] > c["limit"] for c in got.values()), got
