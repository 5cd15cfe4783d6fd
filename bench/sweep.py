#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell once, on the chip.

    python bench/sweep.py --workload mingru-lm.chat --seconds 20 \\
        --rates 10 15 20 25 30 40

One process sets the cell up once, then offers each rate in turn for
``--seconds`` and drains the engine before the next.  For each rate it
prints the requests due and completed, the queue's growth (requests
waiting at the end of the window less at its middle, per second) and the
95th percentile of time to first token.  The knee is the highest rate at
which the queue does not grow; the cell runs at four fifths of it.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2**31 + 77)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import harness
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.log("the sweep needs a TPU")
        return 3
    from drivers import serve
    cell = harness.Cell(args.workload)
    run = harness.Run(cell, types.SimpleNamespace(
        seed=args.seed, seconds=args.seconds, trace=0))
    session = serve.Session(run)
    session.setup()
    rows = []
    for rate in args.rates:
        cell.settings["rate_per_s"] = rate
        session.records, session.queue = [], []
        res = session.window()
        half = [q for t, q in session.queue if t >= args.seconds / 2]
        growth = (half[-1] - half[0]) / (args.seconds / 2) if half else 0.0
        done = sum(r["status"] == "COMPLETED" for r in session.records)
        row = {"rate_per_s": rate, "due": res["attempted"],
               "completed": done, "queued_at_end": half[-1] if half else 0,
               "queue_growth_per_s": growth,
               "ttft_p95_ms": res["metrics"]["ttft_p95_ms"],
               "tpot_p95_ms": res["metrics"]["tpot_p95_ms"],
               "output_tok_s": res["metrics"]["output_tok_s"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        session.eng.run_to_completion()
    steady = [r["rate_per_s"] for r in rows if r["queue_growth_per_s"] <= 0]
    knee = max(steady) if steady else None
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
