"""The serving engine's own spans in a traced window, for the per-layer
readers of its host phases.

``ServingEngine.step`` records each host phase as a profiler span named
``engine.<phase>`` (``EngineStats.timed``: ``step`` holds ``sweep``,
``stage``, ``upload``, ``decode`` -- itself holding ``dispatch`` and
``fetch`` --, ``drain`` -- holding a second ``fetch`` -- and
``journal``), with counts as the span's stats, and one zero-length
``engine.arm`` event for each request it sees armed.  ``reduce`` clips
them to the window ``trace_reduce.reduce_events`` uses (the first to the
last ``bench.*`` span), sums each phase's span time, and puts each device
idle gap down to the innermost ``engine.*`` span the host was in.

``read(ctx)`` loads the run's trace once, through ``trace_reduce``, and
keeps the result in ``ctx`` for the other readers.  It is None when the
trace holds no ``engine.*`` span: a program without the engine's spans.
"""

from __future__ import annotations

import os
from collections import defaultdict

import harness
import trace_reduce

PREFIX = "engine."
CACHE_KEY = "engine_spans"


def engine_events(profile) -> list:
    """[(name, start_ns, end_ns, {stat: value})] of the ``engine.*``
    events on the host's planes."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def innermost(spans) -> list:
    """Disjoint [(name, start, end)] covering the spans' extent, each
    piece named by the innermost span over it (the spans nest, as one
    thread's do)."""
    out, stack = [], []
    t = None
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= a:
            top = stack.pop()
            if top[2] > t:
                out.append((top[0], t, top[2]))
                t = top[2]
        if stack and a > t:
            out.append((stack[-1][0], t, a))
        stack.append((name, a, b))
        t = a
    while stack:
        top = stack.pop()
        if top[2] > t:
            out.append((top[0], t, top[2]))
            t = top[2]
    return out


def device_gaps(ev: dict, chips: int, lo, hi) -> list:
    """The device's idle intervals in [lo, hi), on each of the first
    ``chips`` device planes, as ``trace_reduce.reduce_events`` finds
    them."""
    gaps = []
    for plane in sorted(ev["devices"])[:chips]:
        ops = trace_reduce.clip(ev["devices"][plane]["ops"], lo, hi)
        merged = trace_reduce.union((a, b) for _, a, b in ops)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    return gaps


def reduce(ev: dict, eng: list, chips: int):
    """The engine's phases in the window of ``ev`` (``trace_reduce.
    events``), from its events ``eng`` (``engine_events``); None when
    there are none.  Returns ``calls`` (``engine.decode`` spans),
    ``time_s`` and ``idle_s`` by span name (idle averaged over the
    chips; ``none`` is idle under no engine span), ``drain_stats`` (the
    stats of each ``engine.drain`` span) and ``arm_wait_us`` (queued +
    parked per ``engine.arm`` event)."""
    bench = ev["spans"]
    if not bench:
        return None
    lo, hi = min(s[1] for s in bench), max(s[2] for s in bench)
    eng = [e for e in eng if e[1] >= lo and e[2] <= hi]
    if not eng:
        return None
    time_s = defaultdict(float)
    drains, arm_wait = [], []
    for name, a, b, st in eng:
        time_s[name] += (b - a) / 1e9
        if name == PREFIX + "drain":
            drains.append({k: float(v) for k, v in st.items()})
        elif name == PREFIX + "arm":
            arm_wait.append(float(st["queued_us"])
                            + float(st["parked_us"]))
    # an engine.arm event marks a point: it holds no time to put idle
    # under
    pieces = innermost([(n, a, b) for n, a, b, _ in eng
                        if n != PREFIX + "arm"])
    starts = [a for _, a, _ in pieces]
    n_planes = max(1, min(chips, len(ev["devices"])))
    idle = defaultdict(float)       # "host: <innermost span>" or "host: none"
    for a, b in device_gaps(ev, chips, lo, hi):
        trace_reduce._attribute(a, b, pieces, starts, idle, n_planes)
    return {"calls": sum(1 for e in eng if e[0] == PREFIX + "decode"),
            "time_s": dict(time_s),
            "idle_s": {k.removeprefix("host: "): v for k, v in idle.items()},
            "drain_stats": drains,
            "arm_wait_us": arm_wait}


def log_table(red: dict, ctx: dict) -> None:
    """The idle-by-phase table; the share of the idle time under
    ``bench.step`` that lies in a named phase inside ``engine.step``;
    and the engine's host time a call beside the benchmark's own
    reading of it (``host_ms_per_call``)."""
    calls, t, idle = max(red["calls"], 1), red["time_s"], red["idle_s"]
    harness.log(f"engine spans: {red['calls']} calls; per call, span time "
                f"and device idle under it as the innermost span (ms):")
    for name in sorted(t, key=lambda n: -t[n]):
        harness.log(f"  {name:<16} {1e3 * t[name] / calls:10.4f} "
                    f"{1e3 * idle.get(name, 0.0) / calls:10.4f}")
    gaps = dict(ctx["trace"]["breakdown"]["idle_gaps"])
    named = sum(v for k, v in idle.items()
                if k not in ("none", PREFIX + "step"))
    own = idle.get(PREFIX + "step", 0.0)
    harness.log(f"  idle in named phases {named:.6g} s of "
                f"{gaps.get('host: bench.step', 0.0):.6g} s under "
                f"bench.step; engine.step's own {own:.6g} s, no engine "
                f"span {idle.get('none', 0.0):.6g} s")
    bench = ctx.get("calls") or []
    if bench:
        own = t.get(PREFIX + "step", 0.0) - t.get(PREFIX + "decode", 0.0)
        host = sum((t1 - t0) - dec for t0, t1, dec in bench) / len(bench)
        harness.log(f"  engine.step - engine.decode {1e3 * own / calls:.4f}"
                    f" ms/call; host_ms_per_call {1e3 * host:.4f} ms/call")


def read(ctx: dict):
    """The reduction of this run's trace (see ``reduce``), made once."""
    if CACHE_KEY not in ctx:
        red = None
        path = os.path.join(harness.OUT_DIR, "trace")
        try:
            profile = trace_reduce.load(path)
        except FileNotFoundError:
            profile = None
        if profile is not None:
            red = reduce(trace_reduce.events(profile),
                         engine_events(profile), ctx["chips"])
        if red is not None:
            log_table(red, ctx)
        ctx[CACHE_KEY] = red
    return ctx[CACHE_KEY]


def per_call_ms(ctx: dict, seconds) -> float | None:
    """``seconds(reduction)`` in milliseconds per engine call, or None."""
    red = read(ctx)
    if red is None or not red["calls"]:
        return None
    return 1e3 * seconds(red) / red["calls"]
