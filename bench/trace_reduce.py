"""Reduce a profiler trace of a benchmark window to the numbers it reports.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  A TPU's plane is named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
run on the device and its ``XLA Modules`` line one per program run.  The
host's plane holds the benchmark's own spans (``bench.*``), on the same
clock.

``reduce_trace`` clips everything to the traced window, the interval
between the first and last host span of the benchmark, and returns:

* ``busy_s``: the union of the device's operation intervals, averaged
  over the chips used, and ``window_s``;
* ``ops``: device self seconds per operation name (an operation's time
  less that of the operations nested in it, such as a loop's body) and
  ``modules``: device seconds and run counts per program name, both
  averaged over the chips and keyed by short names (``block_chunk_kernel``
  for ``%block_chunk_kernel.3 = ...``);
* ``breakdown``: the ten operations that took most device time, and the
  device's idle gaps summed by the benchmark span the host was in at the
  time (``host: none`` when it was in none).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def load(trace_dir: str):
    """The newest trace the profiler wrote under ``trace_dir``."""
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return load_file(files[-1])


def load_file(path: str):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def short_name(name: str) -> str:
    """``%block_chunk_kernel.3 = (bf16[...]) custom-call(...)`` ->
    ``block_chunk_kernel``; ``jit__lambda(1756...)`` -> ``jit__lambda``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"\.\d+$", "", name)


def self_times(evs):
    """[(name, start, end, self ns)]: each event's duration less that of
    the events nested directly inside it on the same line."""
    out, stack = [], []
    for name, a, b in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            stack.pop()
        rec = [name, a, b, b - a]
        if stack:
            stack[-1][3] -= b - a
        stack.append(rec)
        out.append(rec)
    return out


def events(profile) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "spans":
    [...]} with each event as (short name, start_ns, end_ns)."""
    devices, spans = {}, []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (short_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
            if lines:
                devices[plane.name] = {"ops": lines.get(OPS_LINE, []),
                                       "modules": lines.get(MODULES_LINE,
                                                            [])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(evs, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in evs
            if b > lo and a < hi]


def reduce_events(ev: dict, chips: int) -> dict:
    """The reduction proper, on ``events()`` output; see the module doc."""
    spans = sorted(ev["spans"], key=lambda s: s[1])
    if not spans:
        raise ValueError("no benchmark spans in the trace")
    lo, hi = spans[0][1], max(s[2] for s in spans)
    planes = sorted(ev["devices"])[:chips]
    if not planes:
        raise ValueError("no device plane in the trace")
    ops = defaultdict(float)
    modules = defaultdict(lambda: [0.0, 0])
    busy = 0.0
    gaps = []
    for plane in planes:
        d = ev["devices"][plane]
        o = clip(d["ops"], lo, hi)
        for n, _, _, own in self_times(o):
            ops[n] += own / 1e9 / len(planes)
        for n, a, b in clip(d["modules"], lo, hi):
            modules[n][0] += (b - a) / 1e9 / len(planes)
            modules[n][1] += 1
        merged = union((a, b) for _, a, b in o)
        busy += sum(b - a for a, b in merged) / 1e9 / len(planes)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    by_host = defaultdict(float)
    starts = [s for _, s, _ in spans]
    for a, b in gaps:
        _attribute(a, b, spans, starts, by_host, len(planes))
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(((k, v) for k, v in by_host.items() if v > 0),
                      key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy, "window_s": (hi - lo) / 1e9, "ops": dict(ops),
            "modules": {k: tuple(v) for k, v in modules.items()},
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                          "idle_gaps": [[k, v] for k, v in top_gaps]}}


def _attribute(a, b, spans, starts, by_host, n_planes):
    """Split the idle gap [a, b) among the host spans that overlap it
    (the benchmark's spans never nest); what none covers goes to
    ``host: none``."""
    covered = 0.0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(spans) and spans[i][1] < b:
        n, s, e = spans[i]
        part = min(e, b) - max(s, a)
        if part > 0:
            by_host["host: " + n] += part / 1e9 / n_planes
            covered += part
        i += 1
    by_host["host: none"] += (b - a - covered) / 1e9 / n_planes


def reduce_trace(trace_dir: str, chips: int) -> dict:
    return reduce_events(events(load(trace_dir)), chips)


def main_program(red: dict):
    """(name, device seconds, runs) of the program that took most device
    time in the window: the superstep, or the train step."""
    if not red["modules"]:
        return None
    name, (secs, runs) = max(red["modules"].items(), key=lambda kv: kv[1][0])
    return name, secs, runs
