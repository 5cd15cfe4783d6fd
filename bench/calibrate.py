#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, on the chip, in one
process: the program's reading on each seed, the float8 control's on the
same outputs, and (training) the readings of planted faults.

    python bench/calibrate.py --workload mingru-lm.chat --seconds 12 \\
        --seeds 11 12 13 ...

Serving: each seed sets the cell up, runs a short window at the cell's
own load, and then reads the widest logit gap of the served greedy tokens
(the program) and of the tokens the float8 reference would put first at
the same positions (the control).
Training: each seed drives the train step through the checked steps and
compares the program with the float32 reference; on the first
``FAULT_SEEDS`` seeds it also compares the float8 reference and the
program with half of each batch left out.  One JSON line per seed; the
benchmark's own runs never run this.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# training: the half-batch fault and the control are read on this many of
# the seeds, the program on all of them
FAULT_SEEDS = 4
ALL = {"loss_gap": None, "grad_norm_gap": None, "change_norm_gap": None,
       "grad_diff": None}
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def serving(cell, seed, seconds):
    import harness
    from drivers import serve
    run = harness.Run(cell, types.SimpleNamespace(seed=seed, seconds=seconds,
                                                  trace=0))
    s = serve.Session(run)
    s.setup()
    s.window()
    seqs = s.finished_greedy()
    s.eng = None
    gc.collect()
    pick = [seqs[i] for i in serve.sample(
        seqs, cell.traffic["check"]["sample_requests"], seed)]
    gaps = serve.logit_gaps(cell.model, cell.conf, seed, pick,
                            cell.settings["engine"]["max_len"],
                            control=True)
    return {"seed": seed, "requests": len(pick), **gaps}


def training(cell, seed, faults: bool):
    import harness
    from drivers import train
    from repro.training import train_step
    out = {"seed": seed}
    s = cell.settings
    opt = cell.traffic["optimizer"]
    ref = None
    for label in ("program", "half_batch")[:1 + faults]:
        run = harness.Run(cell, types.SimpleNamespace(seed=seed, seconds=1,
                                                      trace=0))
        sess = train.Session(run)
        orig = train_step.make_train_step
        if label == "half_batch":
            def half(cfg, ocfg, **kw):
                step = orig(cfg, ocfg, **kw)

                def run_half(params, opt_state, batch):
                    n = batch["tokens"].shape[0] // 2
                    return step(params, opt_state,
                                {k: v[:n] for k, v in batch.items()})
                return run_half
            train_step.make_train_step = half
        try:
            sess.setup()
        finally:
            train_step.make_train_step = orig
        readings = sess.readings
        sess.params = sess.opt_state = sess.step_fn = None
        gc.collect()
        if ref is None:
            ref = train.follow_reference(cell.model, cell.conf, seed,
                                         s["batch"], cell.traffic["seq_len"],
                                         opt, s["reference_rows"])
        out[label] = {k: v["value"] for k, v in
                      train.compare(readings, ref, ALL).items()}
    if not faults:
        return out
    ctrl = train.follow_reference(cell.model, cell.conf, seed, s["batch"],
                                  cell.traffic["seq_len"], opt,
                                  s["reference_rows"], control=True)
    out["control"] = {k: v["value"] for k, v in
                      train.compare(ctrl, ref, ALL).items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import harness
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        harness.log("calibration needs a TPU")
        return 3
    cell = harness.Cell(args.workload)
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        row = training(cell, seed, i < FAULT_SEEDS) \
            if cell.traffic["driver"] == "train" \
            else serving(cell, seed, args.seconds)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
