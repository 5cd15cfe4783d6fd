#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print its result.

    python bench/run.py --workload mingru-lm.chat --seed 7 --seconds 30 \\
        --trace 0

Everything a cell needs is found by name: the cell's entry in
``BENCHMARK.json`` names its configuration (``bench/configs/<config>.json``,
whose ``model`` key names the model module ``bench/models/<model>.py``:
the family's weight layout, plain reference and work counts) and its
traffic mix (``bench/traffic/<traffic>.json``, whose ``driver`` key picks
the general generator ``bench/drivers/<driver>.py``); the cell's own
engine settings and limits are in ``bench/cells/<cell>.json``, and each
per-layer metric is read by ``bench/metrics/<family>.py``, where the family
is the metric's name up to its first dot.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the last seconds of the window run under the profiler and
the result carries the per-layer metrics, the device's busy time and a
breakdown.  Either way the window's output is compared with the plain
reference of the configuration's model module once the window has closed,
and the numbers compared are printed beside their limits, last on
standard error and last in the result line.

The last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, the run prints no result and
exits 3.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NO_CHIP = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    import harness
    cell = harness.Cell(args.workload)
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    # small programs (the staging scatters) are cached too, so that a
    # run after the first finds every program it uses
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    harness.log(f"platform {devices[0].platform}, device_kind "
                f"{devices[0].device_kind}, {len(devices)} devices; cell "
                f"{cell.name} asks for {cell.chips}; compile cache "
                f"{cache_dir}")
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        harness.log(f"{cell.name} needs {cell.chips} TPU chips; JAX "
                    f"found {len(devices)} {devices[0].platform} devices")
        return NO_CHIP
    return harness.execute(cell, args, devices, T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
