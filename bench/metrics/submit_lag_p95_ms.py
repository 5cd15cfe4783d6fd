"""95th percentile of the time from a request's due time on the open-loop
schedule to the return of its ``submit()`` call, over the requests
submitted in the traced window.  The engine takes requests only between
supersteps, so this is how late the load generator ran."""

import numpy as np

LAYER = "load generator and engine admission"
UNIT = "ms"
SOURCE = "host_clock"
BETTER = "lower"
MOVES = {"chat": "ttft_p95_ms"}


def read(ctx, suffix):
    lags = ctx.get("submit_lag_s") or []
    return 1e3 * float(np.percentile(lags, 95)) if lags else None
