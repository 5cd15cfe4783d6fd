"""The drain's fast case: a slot whose superstep planes hold plain decode
(``ServingEngine._bulk_ok``) has its tokens appended in one go, every
other slot is walked round by round.  Which way a slot goes must change
nothing anyone can see -- streams, statuses, first rounds, the order of
finishes and arms, every round-clock counter, the journal -- so each
case drives two engines on the same submissions: one as built, one whose
predicate refuses every slot, so that it drains round by round."""

import dataclasses
import os

import jax
import pytest

from repro.configs import archs
from repro.models import lm
from repro.serving import recovery
from repro.serving.engine import COMPLETED, ServingEngine
from repro.serving.faults import FaultInjector

MAX_LEN = 64
PROMPTS = [[1, 2, 3, 4], [5, 6, 7], [2, 4, 6, 8, 10, 1], [9, 9],
           [3, 1, 4, 1, 5], [7, 7, 7]]


@pytest.fixture(scope="module")
def setup():
    cfg = archs.smoke("mingru-lm")
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)


def _greedy(i):
    return {"max_new": 12}


# name -> (engine kwargs, per-request submit kwargs); "eos" is filled in
# from the plain run's streams (a token each request really emits)
CASES = {
    "plain": ({"max_batch": 6}, _greedy),
    "short_max_new": ({"max_batch": 6}, lambda i: {"max_new": 3 + i % 4}),
    "eos": ({"max_batch": 6}, None),
    "armed_mid_call": ({"max_batch": 2, "prompt_chunk": 4},
                       lambda i: {"max_new": 2 + 3 * (i % 3)}),
    "sampled": ({"max_batch": 4},
                lambda i: {"max_new": 10, "temperature": 0.8 * (i % 2),
                           "top_k": 40 * (i % 2)}),
    "speculative": ({"max_batch": 6, "speculative": "ngram",
                     "draft_len": 3, "prompt_chunk": 4}, _greedy),
    "quarantine": ({"max_batch": 6, "max_retries": 2, "retry_backoff": 2},
                   _greedy),
    "journaled": ({"max_batch": 3, "snapshot_every": 8}, _greedy),
}


def _drive(cfg, params, case, bulk, tmp_path, eos=None):
    kw, req_kw = CASES[case]
    kw = dict(kw)
    if case == "quarantine":
        kw["faults"] = FaultInjector(nan_at=((6, 0), (9, 2), (13, 4)))
    if case == "journaled":
        kw["recover_dir"] = str(tmp_path / ("bulk" if bulk else "rounds"))
    eng = ServingEngine(cfg, params, max_len=MAX_LEN, decode_block=4, **kw)
    if not bulk:
        eng._bulk_ok = lambda *a: False
    arms = []
    eng.stats.mark = lambda kind, **st: arms.append((kind, st["rid"]))
    for i, p in enumerate(PROMPTS):
        sub = req_kw(i) if req_kw is not None else \
            {"max_new": 12, "eos": eos[i]}
        eng.submit(p, **sub)
    eng.run_to_completion(max_steps=200)
    return eng, arms


def _counters(stats) -> dict:
    """Every round-clock counter of ``stats`` (wall-clock fields and the
    drain's own fast-case count left out)."""
    d = dataclasses.asdict(stats)
    return {k: v for k, v in d.items()
            if not k.endswith("_time_s")
            and k not in ("ttft_s", "itl_s", "drain_bulk_slots")}


def _journal(eng):
    eng.journal.close()
    _, records, dropped, _ = recovery.read_journal(
        os.path.join(eng.recover_dir, recovery.JOURNAL_NAME))
    assert dropped == 0
    return records


@pytest.fixture(scope="module")
def plain_streams(setup, tmp_path_factory):
    eng, _ = _drive(*setup, "plain", True, tmp_path_factory.mktemp("p"))
    return [eng.requests[rid].out for rid in range(len(PROMPTS))]


@pytest.mark.parametrize("case", list(CASES))
def test_bulk_drain_matches_the_round_by_round_drain(setup, case, tmp_path,
                                                     plain_streams):
    cfg, params = setup
    eos = [s[5] for s in plain_streams]
    fast, arms_fast = _drive(cfg, params, case, True, tmp_path, eos)
    slow, arms_slow = _drive(cfg, params, case, False, tmp_path, eos)
    assert list(fast.finished) == list(slow.finished)
    assert arms_fast == arms_slow and arms_fast
    for rid, a in fast.requests.items():
        b = slow.requests[rid]
        assert (a.out, a.status, a.first_round, a.retries) == \
            (b.out, b.status, b.first_round, b.retries), rid
    assert _counters(fast.stats) == _counters(slow.stats)
    assert slow.stats.drain_bulk_slots == 0
    assert fast.stats.drain_bulk_slots > 0
    if case == "plain":
        assert [fast.requests[r].out for r in range(len(PROMPTS))] == \
            plain_streams
    if case == "short_max_new":
        assert all(len(r.out) == r.max_new for r in fast.requests.values())
    if case == "eos":
        stopped = [r for r in fast.requests.values()
                   if r.status == COMPLETED and len(r.out) < r.max_new]
        assert stopped and all(r.out[-1] == r.eos for r in stopped)
    if case == "speculative":
        assert 0 < fast.stats.draft_accepted < fast.stats.draft_proposed
    if case == "quarantine":
        assert fast.stats.quarantined > 0 and fast.stats.retried > 0
    if case == "journaled":
        records = _journal(fast)
        assert any(r.get("emits") for r in records)
        assert records == _journal(slow)
