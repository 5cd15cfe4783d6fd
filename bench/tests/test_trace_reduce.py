"""The trace reduction, on a small trace recorded on a TPU v5e
(``record_trace.py``: one superstep of ``mingru-lm.chat``) and on events
made by hand."""

from __future__ import annotations

import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "superstep.xplane.pb")


def test_short_names():
    assert tr.short_name("%block_chunk_kernel.3 = (bf16[16,64,768]) "
                         "custom-call(...)") == "block_chunk_kernel"
    assert tr.short_name("jit__lambda(17569558391769328950)") == \
        "jit__lambda"
    assert tr.short_name("%while.24 = (s32[]) while(...)") == "while"


def test_self_times_subtract_nested_ops():
    got = tr.self_times([("loop", 0, 100), ("cond", 10, 50),
                         ("kernel", 20, 40), ("fusion", 60, 90)])
    assert [(n, own) for n, _, _, own in got] == [
        ("loop", 30), ("cond", 20), ("kernel", 20), ("fusion", 30)]


def test_reduction_by_hand():
    ev = {"devices": {"/device:TPU:0": {
        "ops": [("while", 0, 100), ("kernel", 20, 40), ("fusion", 120, 130)],
        "modules": [("jit_step", 0, 100), ("jit_small", 120, 130)]}},
        "spans": [("bench.step", 0, 110), ("bench.submit", 110, 125),
                  ("bench.step", 125, 140)]}
    red = tr.reduce_events(ev, chips=1)
    assert red["window_s"] == pytest.approx(140e-9)
    assert red["busy_s"] == pytest.approx(110e-9)
    assert red["ops"] == pytest.approx({"while": 80e-9, "kernel": 20e-9,
                                        "fusion": 10e-9})
    assert tr.main_program(red) == ("jit_step", pytest.approx(100e-9), 1)
    gaps = dict(red["breakdown"]["idle_gaps"])
    # idle 100-120 (10 in bench.step, 10 in bench.submit), 130-140
    assert gaps == pytest.approx({"host: bench.step": 20e-9,
                                  "host: bench.submit": 10e-9})


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded chip trace")
def test_recorded_superstep_trace():
    red = tr.reduce_events(tr.events(tr.load_file(RECORDED)), chips=1)
    name, secs, runs = tr.main_program(red)
    assert runs == 1 and 0 < secs <= red["busy_s"] <= red["window_s"]
    # eight rounds of twelve layers, each one block kernel call
    kernels = [k for k in red["ops"] if k.startswith("block_")]
    assert kernels and sum(red["ops"][k] for k in kernels) < secs
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"],
                                                     rel=0.05)
    names = dict(red["breakdown"]["idle_gaps"])
    assert set(names) <= {"host: bench.step", "host: bench.submit",
                          "host: bench.idle", "host: none"}
