"""The yardstick's peaks and the least time a piece of work can take.

Every roofline share and utilisation the benchmark reports divides the
work the algorithm needs by what the chip could do.  The work is counted
from the configuration by its model module's ``counts(conf)``
(``bench/models/``): the operations and bytes the algorithm needs, not
what a kernel's padded shapes happen to compute.

``peaks(device_kind)`` reads ``peaks.json``; a device that is not listed
there is an error.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip of ``device_kind``."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def ideal_seconds(flops: float, nbytes: float, peak: dict):
    """(least time, which bound sets it): the larger of operations over
    peak FLOP/s and bytes over peak bandwidth."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")
