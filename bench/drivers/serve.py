"""The serving driver: one general load generator for every serving mix.

A mix file (``bench/traffic/<mix>.json``) states its arrivals, lengths and
sampling; the cell file states the engine's settings and, for an open
loop, the offered rate.  Two kinds of arrivals:

* ``poisson`` -- an open loop.  ``round(rate * seconds)`` requests are
  due inside the window, at exponential gaps scaled to fill it.  Each is
  timed from its due time, whether or not the engine has taken it.
* ``backlog`` -- a closed offline batch.  At least ``backlog_per_slot``
  requests per slot wait in the engine's queue at all times, drawn in
  turn from a pool of ``pool_per_slot`` requests per slot.

Sizes and gaps are stratified quantiles of the mix's distributions, the
same set for every seed; the seed shuffles their order, cuts the prompts
from the corpus and seeds the engine's sampler.  Every
``greedy_every``-th request is greedy, so that its tokens can be checked
against the reference.

The window drives ``ServingEngine.submit`` and ``ServingEngine.step``,
the program's own entry points, from one thread.  After each call it
reads the tokens of the requests in a slot and of those finished since
the call before, and never walks the queued backlog.  Once it has closed, a
seeded sample of the greedy requests finished in the window, the longest
among them, is run through the float32 reference, the ``forward`` of the
configuration's model module (``bench/models/``).  For each served
token it reads the gap by which the token's reference logit lies below
the reference's best logit at that position; the widest gap over the
sample is compared with the cell's limit.  A lower precision shows in
served tokens only where the reference's two best logits nearly tie, so
the sample is large: every finished greedy request, up to the mix's
``sample_requests``.
"""

from __future__ import annotations

import gc
import itertools
import math
import time
from statistics import NormalDist

import numpy as np

import corpus
import harness
import weights

FAILED = ("FAILED", "SHED", "TIMED_OUT")
# staging uploads warmed in set-up: every count of rows up to this, and a
# full pool.  Each count is a program of its own, and warming all of them
# at 256 slots takes the chip's compiler many minutes
WARM_ROWS = 24


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution, as integers."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "loguniform":
        v = np.exp(np.log(spec["min"])
                   + u * (np.log(spec["max"]) - np.log(spec["min"])))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def make_requests(traffic: dict, n: int, seed: int) -> list:
    """``n`` requests; the multiset of sizes and of sampling settings is
    the same for every seed, their order and the prompt text are not."""
    rng = np.random.default_rng(seed)
    prompts = quantiles(traffic["prompt_bytes"], n)
    outputs = quantiles(traffic["output_bytes"], n)
    samp = traffic["sampling"]
    every = samp.get("greedy_every", 1)
    reqs = []
    for i in rng.permutation(n):
        sampled = samp["temperature"] > 0 and i % every != 0
        reqs.append({"prompt": corpus.prompt(rng, int(prompts[i])),
                     "max_new": int(outputs[i]),
                     "temperature": samp["temperature"] if sampled else 0.0,
                     "top_p": samp.get("top_p", 1.0) if sampled else 1.0})
    return reqs


def poisson_due(n: int, seconds: float, seed: int) -> np.ndarray:
    """Due times of ``n`` arrivals filling ``[0, seconds)``: stratified
    exponential gaps in a seeded order, scaled to the window."""
    u = (np.arange(n) + 0.5) / n
    gaps = np.random.default_rng([seed, 1]).permutation(-np.log1p(-u))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())


class Session:
    """Set-up, window and check of one serving cell in one process."""

    def __init__(self, run):
        self.run = run
        self.cell = run.cell
        self.traffic = run.cell.traffic
        self.engine_cfg = run.cell.settings["engine"]
        self.records = []
        self.queue = []             # (window time, requests queued)
        self.by_rid = {}            # engine request id -> record
        self.open = 0               # records submitted and not yet done
        self.n_finished = 0         # eng.finished entries already read

    # -- set-up -------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.models import lm
        from repro.serving.engine import ServingEngine

        conf, e = self.cell.conf, self.engine_cfg
        cfg = harness.program_config(conf, self.cell.model)
        params = weights.make(self.cell.model, conf, self.run.seed)
        want = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                              jax.random.PRNGKey(0))
        if jax.tree.structure(want) != jax.tree.structure(params) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(params))):
            raise SystemExit("the benchmark's weights do not match the "
                             "program's parameter layout")
        self.eng = eng = ServingEngine(
            cfg, params, max_batch=e["slots"], max_len=e["max_len"],
            decode_block=e["decode_block"], prompt_chunk=e["prompt_chunk"],
            seed=self.run.seed, mesh=e.get("mesh"))
        del params
        # the staging upload scatters the newly staged prompt rows, one
        # program per count of rows; warm the counts a call stages in
        # these mixes (a few at a time, and every slot at the first call)
        mirror = eng._smirror["s_prompt"]
        for n in sorted({*range(1, min(WARM_ROWS, e["slots"]) + 1),
                         e["slots"]}):
            rows = list(range(n))
            eng.state["s_prompt"].at[jnp.asarray(rows)].set(
                jnp.asarray(mirror[np.asarray(rows)]))
        # throwaway requests compile the superstep, whose one program
        # holds both the packed and the plain round, and the sampler
        for i in range(min(4, e["slots"])):
            eng.submit(corpus.prompt(np.random.default_rng(i),
                                     e["prompt_chunk"] + 3),
                       max_new=2, temperature=1.0, top_p=0.9)
        eng.run_to_completion()
        jax.effects_barrier()

    # -- the window ---------------------------------------------------
    def counters(self) -> dict:
        s = self.eng.stats
        return {"prefill_tokens": s.prefill_tokens,
                "prefill_rounds": s.prefill_rounds,
                "decode_tokens": s.decode_tokens,
                "first_tokens": len(s.ttft_s),
                "decode_steps": s.decode_steps,
                "decode_calls": s.decode_calls,
                "slot_steps": s.slot_steps,
                "wasted_slot_steps": s.wasted_slot_steps}

    def window(self) -> dict:
        run, eng, t = self.run, self.eng, self.traffic
        secs = run.seconds
        slots = self.engine_cfg["slots"]
        if t["arrivals"] == "poisson":
            n = int(round(self.cell.settings["rate_per_s"] * secs))
            due = poisson_due(n, secs, run.seed)
            reqs = make_requests(t, n, run.seed)
            keep = 0
        elif t["arrivals"] == "backlog":
            reqs = make_requests(t, int(t["pool_per_slot"] * slots),
                                 run.seed)
            due, keep = None, int(t["backlog_per_slot"] * slots)
        else:
            raise ValueError(f"unknown arrivals {t['arrivals']!r}")
        trace_at = max(0.0, secs - harness.TRACE_SECONDS) \
            if run.trace else math.inf
        calls = []
        self.by_rid, self.open = {}, 0
        self.n_finished = len(eng.finished)
        nxt = 0
        c0 = self.counters()
        ct0 = ct1 = None
        t0 = time.perf_counter()
        t_end = t0 + secs
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if now - t0 >= trace_at and ct0 is None:
                run.trace_start()
                ct0 = self.counters()
            if due is None:
                target = keep + (slots if nxt == 0 else 0)
                while len(eng.scheduler) < target:
                    self._submit(reqs[nxt % len(reqs)], now - t0, t0)
                    nxt += 1
            else:
                while nxt < len(due) and t0 + due[nxt] <= now:
                    self._submit(reqs[nxt], due[nxt], t0)
                    nxt += 1
            if not self.open:        # an open loop between arrivals
                wake = t_end if nxt >= len(due) else t0 + due[nxt]
                with run.span("bench.idle"):
                    time.sleep(max(0.0, min(t_end, wake)
                                   - time.perf_counter()))
                continue
            before = eng.stats.decode_time_s
            with run.span("bench.step") as sp:
                eng.step()
            t_ret = time.perf_counter()
            calls.append((sp.t0, t_ret, eng.stats.decode_time_s - before))
            self._collect(t_ret - t0, t_ret <= t_end)
            self.queue.append((t_ret - t0, len(eng.scheduler)))
        if ct0 is not None:
            ct1 = self.counters()
            run.trace_stop()
        c1 = self.counters()
        return self._summarise(c0, c1, ct0, ct1, calls, t0)

    def _submit(self, spec, due_s, t0):
        with self.run.span("bench.submit"):
            rid = self.eng.submit(spec["prompt"], max_new=spec["max_new"],
                                  temperature=spec["temperature"],
                                  top_p=spec["top_p"])
        r = {"rid": rid, "due_s": due_s,
             "submit_s": time.perf_counter() - t0,
             "greedy": spec["temperature"] == 0.0, "seen": 0,
             "first_s": None, "win_n": 0, "win_first_s": None,
             "win_last_s": None, "status": None, "done_s": None,
             "slot": None}
        self.records.append(r)
        self.by_rid[rid] = r
        self.open += 1

    def _collect(self, now_s, in_window):
        """Tokens received by the host at ``now_s`` (window time).  Only a
        request in a slot, or one finished since the last call (armed and
        done inside it, perhaps), can have new tokens."""
        eng = self.eng
        done = list(itertools.islice(eng.finished.values(),
                                     self.n_finished, None))
        self.n_finished += len(done)
        for req in itertools.chain(
                [q for q in eng.current if q is not None], done):
            r = self.by_rid.get(req.rid)
            if r is None or r["status"] is not None:
                continue
            if req.slot is not None:
                r["slot"] = req.slot
            n = len(req.out)
            if n > r["seen"]:
                if r["seen"] == 0:
                    r["first_s"] = now_s
                if in_window:
                    if r["win_n"] == 0:
                        r["win_first_s"] = now_s
                    r["win_last_s"] = now_s
                    r["win_n"] += n - r["seen"]
                r["seen"] = n
            if req.done:
                r["status"], r["done_s"] = req.status, now_s
                self.open -= 1

    def _summarise(self, c0, c1, ct0, ct1, calls, t0) -> dict:
        secs, recs = self.run.seconds, self.records
        due = [r for r in recs if r["due_s"] < secs]
        ttft = [(r["first_s"] if r["first_s"] is not None
                 and r["first_s"] <= secs else secs) - r["due_s"]
                for r in due]
        tpot = [(r["win_last_s"] - r["win_first_s"]) / (r["win_n"] - 1)
                for r in recs if r["win_n"] >= 2]
        tokens = sum(r["win_n"] for r in recs)
        # a percentile with no sample reads the whole window: nothing
        # came in it
        metrics = {"output_tok_s": tokens / secs,
                   "ttft_p95_ms": 1e3 * float(
                       np.percentile(ttft, 95) if ttft else secs),
                   "tpot_p95_ms": 1e3 * float(
                       np.percentile(tpot, 95) if tpot else secs)}
        layer = {"counters": None, "calls": [], "submit_lag_s": []}
        if ct0 is not None:
            a, b = self.run.traced
            layer["counters"] = {k: ct1[k] - ct0[k] for k in ct0}
            layer["calls"] = [c for c in calls if c[0] >= a and c[1] <= b]
            if self.traffic["arrivals"] == "poisson":
                layer["submit_lag_s"] = [
                    r["submit_s"] - r["due_s"] for r in recs
                    if a - t0 <= r["submit_s"] <= b - t0]
        harness.log(
            f"window: {len(recs)} requests submitted, {len(due)} due, "
            f"{sum(r['status'] == 'COMPLETED' for r in recs)} completed, "
            f"{tokens} tokens received; counters "
            f"{ {k: c1[k] - c0[k] for k in c0} }; "
            f"{len(calls)} engine calls")
        return {"attempted": len(due),
                "failed": sum(r["status"] in FAILED for r in recs),
                "metrics": metrics, "layer_ctx": layer}

    # -- the comparison with the reference ------------------------------
    def finished_greedy(self) -> list:
        """(prompt, served tokens, data shard) of every greedy request
        finished in the window; the shard is -1 for a request that was
        armed and finished inside one engine call, unseen in a slot."""
        reqs = self.eng.requests
        per_shard = self.engine_cfg["slots"] // self.eng.dp
        return [(list(reqs[r["rid"]].prompt), list(reqs[r["rid"]].out),
                 -1 if r["slot"] is None else r["slot"] // per_shard)
                for r in self.records
                if r["greedy"] and r["status"] == "COMPLETED"
                and r["done_s"] <= self.run.seconds]

    def check(self) -> dict:
        seqs = self.finished_greedy()
        self.eng = None            # free the program's state first
        gc.collect()
        limit = self.cell.settings["limits"]["max_logit_gap"]
        if not seqs:
            harness.log("no greedy request finished in the window")
            return {"max_logit_gap": {"value": math.inf, "limit": limit}}
        pick = [seqs[i] for i in sample(
            seqs, self.traffic["check"]["sample_requests"], self.run.seed)]
        gaps = logit_gaps(self.cell.model, self.cell.conf, self.run.seed,
                          pick, self.engine_cfg["max_len"])
        harness.log(f"compared {gaps['tokens']} served tokens of "
                    f"{len(pick)} requests with the reference")
        return {"max_logit_gap": {"value": gaps["program_max"],
                                  "limit": limit}}


def sample(seqs: list, n: int, seed: int) -> list:
    """Indices of up to ``n`` (prompt, served, shard) sequences: the
    longest, then one from each data shard not yet in, then a seeded draw
    of the rest."""
    rng = np.random.default_rng([seed, 2])
    pick = [max(range(len(seqs)),
                key=lambda i: len(seqs[i][0]) + len(seqs[i][1]))]
    for shard in sorted({s[2] for s in seqs}):
        if shard not in {seqs[i][2] for i in pick}:
            pick.append(int(rng.choice(
                [i for i, s in enumerate(seqs) if s[2] == shard])))
    rest = [i for i in range(len(seqs)) if i not in pick]
    k = min(n - len(pick), len(rest))
    if k > 0:
        pick += [int(i) for i in rng.choice(rest, k, replace=False)]
    return pick


def logit_gaps(model, conf: dict, seed: int, seqs: list, max_len: int,
               control: bool = False) -> dict:
    """For each served token, the gap between the reference's best logit
    at its position and the reference's logit of that token.  Returns,
    over ``seqs`` [(prompt, served tokens, ...)], the widest gap
    (``program_max``) and, with ``control``, the widest gap of the tokens
    that the float8 control puts first at the same positions
    (``control_max``).
    Every sequence is padded to ``max_len`` so that one program serves
    them all."""
    import jax.numpy as jnp
    params = weights.make(model, conf, seed)
    out = {"program_max": 0.0, "tokens": 0}
    if control:
        out["control_max"] = 0.0
    for prompt, served, *_ in seqs:
        toks = (prompt + served)[:-1]
        x = np.zeros((1, max_len), np.int32)
        x[0, :len(toks)] = toks
        pos = np.arange(len(prompt) - 1, len(toks))
        rows = np.arange(len(served))
        ref = np.asarray(model.forward(params, jnp.asarray(x), conf))[0, pos]
        best = ref.max(-1)
        picks = {"program": np.asarray(served)}
        if control:
            picks["control"] = np.asarray(model.forward(
                params, jnp.asarray(x), conf, control=True))[0, pos].argmax(-1)
        for who, tok in picks.items():
            gap = best - ref[rows, tok]
            out[who + "_max"] = max(out[who + "_max"], float(gap.max()))
        out["tokens"] += len(served)
    return out
