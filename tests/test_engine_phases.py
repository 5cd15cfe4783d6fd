"""The serving engine's host phases -- timers in ``EngineStats`` and
``engine.*`` profiler spans on the device trace's clock -- and the
superstep's packed-round counters.

The nesting contract (``ServingEngine.step``): ``engine.step`` holds
``sweep``, ``stage``, ``upload``, ``decode`` (holding ``dispatch`` and
``fetch``) and ``drain`` (holding a second ``fetch`` and the zero-length
``engine.arm`` events); the drain span's stats are the call's counter
deltas.
"""

import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import archs
from repro.models import lm
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import PHASES, EngineStats

MAX_LEN = 48
PROMPTS = [[1, 2, 3, 4, 5, 6, 7], [5, 6], [9, 8, 7, 6, 5, 4, 3, 2, 1, 2]]
PARENT = {"engine.sweep": "engine.step", "engine.stage": "engine.step",
          "engine.upload": "engine.step", "engine.decode": "engine.step",
          "engine.drain": "engine.step", "engine.dispatch": "engine.decode",
          "engine.arm": "engine.drain"}
DRAIN_STATS = {"rounds": "decode_steps", "emitted": "decode_tokens",
               "prefill_tokens": "prefill_tokens",
               "packed_rounds": "packed_rounds",
               "packed_tokens": "packed_tokens",
               "bulk_slots": "drain_bulk_slots"}


@pytest.fixture(scope="module")
def setup():
    cfg = archs.smoke("mingru-lm")
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)


def _snap(st: EngineStats) -> dict:
    out = {k: getattr(st, f"{k}_time_s") for k in PHASES}
    out.update({v: getattr(st, v) for v in DRAIN_STATS.values()})
    return out


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    """A small engine (2 slots, K=2, C=4) driven call by call under the
    profiler: its ``engine.*`` host events, each call's stats deltas and
    wall time, and the rids submitted."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                        decode_block=2, prompt_chunk=4)
    eng.submit([3, 4], max_new=2)         # compile outside the trace
    eng.run_to_completion()
    out = str(tmp_path_factory.mktemp("trace"))
    calls = []
    jax.profiler.start_trace(out)
    try:
        rids = [eng.submit(p, max_new=4) for p in PROMPTS]
        left = True
        while left:
            before = _snap(eng.stats)
            t0 = time.perf_counter()
            left = eng.step()
            wall = time.perf_counter() - t0
            after = _snap(eng.stats)
            calls.append(({k: after[k] - before[k] for k in after}, wall))
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    prof = jax.profiler.ProfileData.from_file(path)
    events = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats))
                     for p in prof.planes if p.name.startswith("/host:")
                     for line in p.lines for e in line.events
                     if e.name.startswith("engine.")),
                    key=lambda e: (e[1], -e[2]))
    return events, calls, rids


def _parent(ev, events):
    """The innermost other event whose interval holds ``ev``."""
    holders = [p for p in events if p is not ev and p[1] <= ev[1]
               and ev[2] <= p[2] and p[0] != "engine.arm"]
    return min(holders, key=lambda p: p[2] - p[1], default=None)


def test_engine_spans_nest_as_the_phases_do(traced):
    events, calls, _ = traced
    names = {e[0] for e in events}
    assert names == {f"engine.{p}" for p in PHASES if p != "journal"} \
        | {"engine.arm"}
    steps = [e for e in events if e[0] == "engine.step"]
    assert len(steps) == len(calls) and all(
        _parent(e, events) is None for e in steps)
    for ev in events:
        if ev[0] == "engine.step":
            continue
        parent = _parent(ev, events)
        assert parent is not None, ev
        if ev[0] == "engine.fetch":
            assert parent[0] in ("engine.decode", "engine.drain"), ev
        else:
            assert parent[0] == PARENT[ev[0]], (ev, parent)
    # every call did work: one decode and one drain each, and one fetch
    # in each of those
    for step in steps:
        kids = [e for e in events if _parent(e, events) is step]
        assert sorted(k[0] for k in kids) == sorted(
            ["engine.sweep", "engine.stage", "engine.upload",
             "engine.decode", "engine.drain"])
        for k in kids:
            if k[0] in ("engine.decode", "engine.drain"):
                inner = [e[0] for e in events if _parent(e, events) is k
                         and e[0] != "engine.arm"]
                assert inner.count("engine.fetch") == 1, (k, inner)


def test_drain_stats_equal_the_counter_deltas(traced):
    events, calls, rids = traced
    drains = [e[3] for e in events if e[0] == "engine.drain"]
    assert len(drains) == len(calls)
    for stats, (delta, _) in zip(drains, calls):
        for stat, field in DRAIN_STATS.items():
            assert stats[stat] == delta[field], (stat, stats, delta)
        assert (stats["slots"], stats["chunk"]) == (2, 4)
    assert sum(d["packed_rounds"] for d in drains) > 0
    # every prompt row went up once, and every request was seen armed
    assert sum(e[3]["rows"] for e in events
               if e[0] == "engine.upload") == len(PROMPTS)
    arms = [e[3] for e in events if e[0] == "engine.arm"]
    assert sorted(a["rid"] for a in arms) == sorted(rids)
    assert all(a["queued_us"] >= 0 and a["parked_us"] >= 0 for a in arms)


def test_drain_span_counts_the_bulk_slots(traced):
    """Each ``engine.drain`` span says how many of the pool's rows the
    drain handled in one go; over the calls they add up to the
    ``EngineStats`` total, and plain decode rows do take the fast case."""
    events, calls, _ = traced
    drains = [e[3] for e in events if e[0] == "engine.drain"]
    assert all(0 <= d["bulk_slots"] <= d["slots"] for d in drains), drains
    total = sum(d["bulk_slots"] for d in drains)
    assert total == sum(delta["drain_bulk_slots"] for delta, _ in calls)
    assert 0 < total < sum(d["slots"] for d in drains)


def test_phase_times_fit_inside_the_call(traced):
    _, calls, _ = traced
    for delta, wall in calls:
        times = {k: delta[k] for k in PHASES}
        assert all(v >= 0 for v in times.values()), times
        children = sum(times[k] for k in ("sweep", "stage", "upload",
                                          "decode", "drain", "journal"))
        assert children <= times["step"] <= wall, (times, wall)
        assert times["dispatch"] <= times["decode"]
        assert times["fetch"] <= times["decode"] + times["drain"]


def test_phase_timers_reach_the_snapshot():
    st = EngineStats()
    with st.timed("upload") as span:
        span.set_metadata(rows=3)
    snap = st.snapshot()
    assert all(f"{k}_time_s" in snap for k in PHASES)
    assert snap["upload_time_s"] > 0 and snap["step_time_s"] == 0


def _staged_state(cfg, prompts, max_new, bsz):
    """Slot state with ``prompts`` parked in the staging buffers."""
    state = lm.init_slot_state(cfg, bsz, MAX_LEN, seed=0)
    for i, p in enumerate(prompts):
        state["s_valid"] = state["s_valid"].at[i].set(True)
        state["s_prompt"] = state["s_prompt"].at[i, :len(p)].set(
            jnp.asarray(p, jnp.int32))
        state["s_prompt_len"] = state["s_prompt_len"].at[i].set(len(p))
        state["s_rid"] = state["s_rid"].at[i].set(i)
        state["s_remaining"] = state["s_remaining"].at[i].set(max_new)
    return state


@pytest.mark.parametrize("chunk,rounds,tokens", [
    # C=4, prompts of 7, 2 and 10 tokens, 3 outputs each:
    #   round 0: takes 4, 2, 4                   -> 10 (row 1 emits)
    #   round 1: takes 3, row 1 decodes, takes 4 ->  8
    #   round 2: rows 0 and 1 decode, takes 2    ->  4 (row 1 retires)
    #   rounds 3-4: nothing prefills, the plain branch
    (4, 3, 22),
    (1, 0, 0),          # no packed branch at C=1
])
def test_packed_counters_exact(setup, chunk, rounds, tokens):
    cfg, params = setup
    state = _staged_state(cfg, PROMPTS, 3, len(PROMPTS))
    _, _, _, ct = jax.jit(lambda p, s: lm.superstep(
        p, cfg, s, 5, prompt_chunk=chunk))(params, state)
    assert int(ct["packed_rounds"]) == rounds
    assert int(ct["packed_tokens"]) == tokens


def test_packed_counters_under_speculation(setup):
    """Drafting changes when rows finish decoding, not when prompts are
    consumed: with every request armed at once the packed rounds match
    the plain engine, and a verified draft only adds positions."""
    cfg, params = setup

    def run(**kw):
        eng = ServingEngine(cfg, params, max_batch=len(PROMPTS),
                            max_len=MAX_LEN, decode_block=4,
                            prompt_chunk=4, **kw)
        for p in PROMPTS:
            eng.submit(p * 2, max_new=8)
        eng.run_to_completion()
        return eng.stats

    plain, spec = run(), run(speculative="ngram")
    assert spec.packed_rounds == plain.packed_rounds > 0
    assert spec.packed_tokens >= plain.packed_tokens > 0
