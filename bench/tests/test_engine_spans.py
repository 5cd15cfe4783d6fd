"""The engine-span reduction (``engine_spans.py``) and the per-layer
readers built on it, on events made by hand and on the recorded chip
trace, which predates the engine's spans."""

from __future__ import annotations

import os

import pytest

import engine_spans as es
import harness
import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "superstep.xplane.pb")
READERS = ["stage_ms_per_call", "drain_ms_per_call",
           "fetch_idle_ms_per_call", "queue_wait_p95_ms", "packed_fill"]

DRAIN = {"rounds": 8, "emitted": 5, "prefill_tokens": 30,
         "packed_rounds": 2, "packed_tokens": 12, "slots": 4, "chunk": 4}
# one engine call, times in ns: the device runs the superstep 12-40 and
# a staging scatter 7-8
EVENTS = {"devices": {"/device:TPU:0": {
    "ops": [("superstep", 12, 40), ("scatter", 7, 8)],
    "modules": [("jit_superstep", 12, 40)]}},
    "spans": [("bench.step", 0, 100)]}
ENGINE = [("engine.step", 5, 95, {}),
          ("engine.sweep", 5, 6, {}),
          ("engine.stage", 6, 7, {}),
          ("engine.upload", 7, 9, {"rows": 1}),
          ("engine.decode", 10, 50, {}),
          ("engine.dispatch", 10, 12, {}),
          ("engine.fetch", 12, 50, {}),
          ("engine.drain", 50, 90, DRAIN),
          ("engine.fetch", 50, 55, {}),
          ("engine.arm", 60, 60, {"rid": 3, "queued_us": 1000.0,
                                  "parked_us": 500.0})]


def test_innermost_names_each_piece_by_the_deepest_span():
    pieces = es.innermost([(n, a, b) for n, a, b, _ in ENGINE
                           if n != "engine.arm"])
    assert pieces == [
        ("engine.sweep", 5, 6), ("engine.stage", 6, 7),
        ("engine.upload", 7, 9), ("engine.step", 9, 10),
        ("engine.dispatch", 10, 12), ("engine.fetch", 12, 50),
        ("engine.fetch", 50, 55), ("engine.drain", 55, 90),
        ("engine.step", 90, 95)]


def test_idle_gaps_go_to_the_innermost_span():
    red = es.reduce(EVENTS, ENGINE, chips=1)
    # idle 0-7, 8-12 and 40-100
    assert red["idle_s"] == pytest.approx({
        "none": 10e-9, "engine.sweep": 1e-9, "engine.stage": 1e-9,
        "engine.upload": 1e-9, "engine.step": 6e-9,
        "engine.dispatch": 2e-9, "engine.fetch": 15e-9,
        "engine.drain": 35e-9})
    assert red["calls"] == 1
    assert red["time_s"]["engine.fetch"] == pytest.approx(43e-9)
    assert red["drain_stats"] == [DRAIN]
    assert red["arm_wait_us"] == [1500.0]
    # the same idle time as the reduction of the whole window
    whole = dict(tr.reduce_events(EVENTS, 1)["breakdown"]["idle_gaps"])
    assert sum(red["idle_s"].values()) == pytest.approx(
        whole["host: bench.step"])


def test_events_outside_the_window_are_left_out():
    late = [(n, a + 1000, b + 1000, s) for n, a, b, s in ENGINE]
    assert es.reduce(EVENTS, late, chips=1) is None


def _ctx(red):
    return {es.CACHE_KEY: red, "chips": 1, "calls": [],
            "trace": tr.reduce_events(EVENTS, 1)}


def test_readers_on_one_call():
    ctx = _ctx(es.reduce(EVENTS, ENGINE, chips=1))
    got = {name: harness.reader(name).read(ctx, "chat") for name in READERS}
    assert got == pytest.approx({
        "stage_ms_per_call": 3e-6,          # stage 1 ns + upload 2 ns
        "drain_ms_per_call": 40e-6,
        "fetch_idle_ms_per_call": 15e-6,
        "queue_wait_p95_ms": 1.5,
        "packed_fill": 100.0 * 12 / (2 * 4 * 4)})


@pytest.mark.parametrize("name", READERS)
def test_readers_without_engine_spans_read_none(name):
    assert es.reduce(EVENTS, [], chips=1) is None
    assert harness.reader(name).read(_ctx(None), "chat") is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded chip trace")
def test_recorded_trace_has_no_engine_spans():
    profile = tr.load_file(RECORDED)
    assert es.engine_events(profile) == []
    assert es.reduce(tr.events(profile), es.engine_events(profile),
                     chips=1) is None
