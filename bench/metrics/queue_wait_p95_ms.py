"""95th percentile of a request's wait between ``submit()`` and the
engine call whose drain saw it armed on a row -- queued, then parked in
a staging buffer -- over the ``engine.arm`` events in the traced window.
The drain reads the clock once a call, so the wait resolves to one
engine call.  The rest of a request's time to first token is its packed
prompt rounds."""

import numpy as np

import engine_spans

LAYER = "load generator and engine admission"
UNIT = "ms"
SOURCE = "host_clock"
BETTER = "lower"
MOVES = {"chat": "ttft_p95_ms"}


def read(ctx, suffix):
    red = engine_spans.read(ctx)
    if red is None or not red["arm_wait_us"]:
        return None
    return 1e-3 * float(np.percentile(red["arm_wait_us"], 95))
