#!/usr/bin/env python3
"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python bench/tests/record_trace.py OUT.xplane.pb

Sets up ``mingru-lm.chat``, submits a few requests, and traces one
``ServingEngine.step`` call (one superstep of 8 rounds) under the
benchmark's spans.  Run it on a TPU; the file is kept in
``bench/tests/data/``.
"""

import glob
import os
import shutil
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def main(out: str) -> int:
    import harness
    from repro.launch import compile_cache
    compile_cache.enable()
    import numpy as np

    import corpus
    from drivers import serve
    cell = harness.Cell("mingru-lm.chat")
    run = harness.Run(cell, types.SimpleNamespace(seed=2**31 + 5, seconds=1,
                                                  trace=1))
    s = serve.Session(run)
    s.setup()
    rng = np.random.default_rng(0)
    for _ in range(6):
        s.eng.submit(corpus.prompt(rng, 300), max_new=40)
    s.eng.step()
    run.window_open = True
    run.trace_start()
    with run.span("bench.submit"):
        s.eng.submit(corpus.prompt(rng, 100), max_new=40)
    with run.span("bench.step"):
        s.eng.step()
    with run.span("bench.idle"):
        time.sleep(0.002)
    run.trace_stop()
    src = sorted(glob.glob(os.path.join(harness.OUT_DIR, "trace", "plugins",
                                        "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(src, out)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
