"""The benchmark's harness: finds a cell's files and its configuration's
model module by name, keeps the run's clock, spans and profiler, and turns
a driver's window into the result line.  ``run.py`` is the command; tests
call ``execute`` directly, past the look for a chip, to drive a whole run
on the CPU at a small size.
"""

import dataclasses
import importlib
import json
import os
import sys
import time

import models

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")
TRACE_SECONDS = 6.0      # the traced part of a --trace 1 window, at most
# keys of a configuration file that describe it, not the program's config:
# ``model`` names its module under ``bench/models/``; ``published`` gives
# the source's value of each key in ``reduced``; ``deployment`` says how
# many chips share a layer, and how
META = {"arch", "model", "source", "reduced", "published", "assumed",
        "deployment", "why"}


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Cell:
    """Everything ``BENCHMARK.json`` and the cell's files say about one
    workload, and its configuration's model module, looked up by name."""

    def __init__(self, name: str, root: str = ROOT):
        bench = load_json(root, "BENCHMARK.json")
        found = [w for w in bench["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = found[0]
        self.chips = int(self.entry["chips"])
        conf_entry = [c for c in bench["configs"]
                      if c["name"] == self.entry["config"]][0]
        self.conf = load_json(root, conf_entry["file"])
        self.model = models.load(self.conf)
        self.traffic = load_json(root, "bench", "traffic",
                                 self.entry["traffic"] + ".json")
        self.settings = load_json(root, "bench", "cells", name + ".json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def reader(metric_name: str):
    """The module that reads a per-layer metric, by the metric's family."""
    family = metric_name.split(".")[0]
    return importlib.import_module(f"metrics.{family}")


class Run:
    """One process's run of one cell: the clock, the spans around the
    benchmark's calls into the program, the traced part of the window,
    and a count of compilations made while the window is open."""

    def __init__(self, cell: Cell, args):
        self.cell = cell
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.spans = []             # (name, t0, t1) on the perf_counter
        self.compiles = 0
        self.window_open = False
        self.traced = None          # (t0, t1) of the traced part
        self._trace_on = False

    # -- clock and spans ----------------------------------------------
    def span(self, name):
        """A host span around a call into the program: kept on the
        perf_counter for the per-layer readers and, under --trace 1,
        written into the profiler's trace for the idle-gap breakdown."""
        run = self

        class _Span:
            def __enter__(self):
                self.ann = None
                if run._trace_on:
                    import jax
                    self.ann = jax.profiler.TraceAnnotation(name)
                    self.ann.__enter__()
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                t1 = time.perf_counter()
                if self.ann is not None:
                    self.ann.__exit__(*exc)
                if run.window_open:
                    run.spans.append((name, self.t0, t1))
                return False

        return _Span()

    def on_compile(self, *_a, **_k):
        if self.window_open:
            self.compiles += 1

    # -- profiler -----------------------------------------------------
    def trace_start(self):
        import shutil

        import jax
        shutil.rmtree(os.path.join(OUT_DIR, "trace"), ignore_errors=True)
        jax.profiler.start_trace(os.path.join(OUT_DIR, "trace"))
        self._trace_on = True
        self.traced = [time.perf_counter(), None]

    def trace_stop(self):
        import jax
        self.traced[1] = time.perf_counter()
        self._trace_on = False
        jax.profiler.stop_trace()


def device_info(devices, chips):
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}


def per_layer_metrics(cell: Cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        suffix = m["name"].split(".", 1)[1] if "." in m["name"] else ""
        value = reader(m["name"]).read(ctx, suffix)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def stated(cfg, conf: dict, model) -> dict:
    """The program's value of every key of the configuration file ``conf``
    outside ``META``, as the file writes it: a field of the program's
    config ``cfg`` (a nested group as a dict of the keys the file gives
    it), or a key of the model module's ``source_values(cfg)``, the
    source's own name for a value of the program, as a catalog model's
    file keeps them.  Any other key stops the run, so every key the file
    states is checked against the program."""
    source = (model.source_values(cfg) if hasattr(model, "source_values")
              else {})
    out = {}
    for key, want in conf.items():
        if key in META:
            continue
        if hasattr(cfg, key):
            have = getattr(cfg, key)
            if dataclasses.is_dataclass(have):
                have = dataclasses.asdict(have)
                have = {k: have[k] for k in want}
        elif key in source:
            have = source[key]
        else:
            raise SystemExit(f"{conf['arch']}: the configuration file states "
                             f"{key!r}, which is neither a field of the "
                             f"program's config nor a source value of "
                             f"bench/models/{conf['model']}.py")
        out[key] = have
    return out


def program_config(conf: dict, model):
    """The program's registered config for ``conf['arch']``, checked
    against every size and setting the configuration file states."""
    from repro.configs import archs
    cfg = archs.get(conf["arch"])
    for key, have in stated(cfg, conf, model).items():
        if have != conf[key]:
            raise SystemExit(f"{conf['arch']}: {key} is {have!r} in the "
                             f"program, {conf[key]!r} in the configuration "
                             f"file")
    return cfg


def execute(cell: Cell, args, devices, t_process: float) -> int:
    """Everything after the look for the chip: set up, run the window,
    compare with the reference, print the result.  Tests drive this on
    the CPU at a small size."""
    import jax
    import work
    run = Run(cell, args)
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, *_a, **_k: run.on_compile()
        if event == "/jax/core/compile/backend_compile_duration" else None)
    driver = importlib.import_module(f"drivers.{cell.traffic['driver']}")
    session = driver.Session(run)
    session.setup()
    setup_s = time.perf_counter() - t_process
    log(f"setup_s {setup_s:.3f}")
    run.window_open = True
    window = session.window()
    run.window_open = False
    log(f"compiles in window: {run.compiles}")
    device = device_info(devices, cell.chips)
    result = {"correct": None, "attempted": window["attempted"],
              "failed": window["failed"]}
    if args.trace:
        from trace_reduce import reduce_trace
        red = reduce_trace(os.path.join(OUT_DIR, "trace"), cell.chips)
        ctx = dict(window["layer_ctx"], trace=red,
                   shape=cell.model.counts(cell.conf),
                   peak=work.peaks(device["kind"]), chips=cell.chips,
                   spans=run.spans, traced=run.traced)
        metrics = per_layer_metrics(cell, ctx)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = red["breakdown"]
    else:
        metrics = {m["name"]: {"value": float(window["metrics"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        result["metrics"] = metrics
        result["device"] = device
    checks = session.check()
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
