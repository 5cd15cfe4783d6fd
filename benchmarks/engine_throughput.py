"""Serving-engine throughput: the superstep engine versus its ancestors.

Three scenarios:

  * default -- the superstep engine versus the vendored v1 seed engine
    (per-request prefill + host-side sampling) across batch sizes on a
    mixed-prompt workload; end-to-end tokens/s.
  * ``--decode`` -- decode-block sweep: tokens/s per block size K (K=1
    is the per-token baseline row kept for the trajectory), writing
    BENCH_decode.json.  Greedy streams must be identical across K.
  * ``--mixed`` -- the acceptance scenario for the superstep refactor:
    a mixed **arrival trace** (staggered arrivals, mixed prompt/output
    lengths, queue pressure) served by (a) a round-level simulation of
    the PR 3 *per-phase* engine (admission prefill barrier -> K-token
    decode buffer -> retire at buffer end) and (b) the same trace under
    the superstep loop (prefill rides the decode rounds, dead rows
    re-arm in-loop) swept over ``--prompt-chunks`` C values (packed
    prefill: a prefilling row consumes up to C prompt tokens per weight
    stream -- C=1 is the unpacked PR 4 row, the full-config entry is
    the weight-bound metric packing exists to move past 1.0x), both on
    the shared structural latency model -- plus the REAL superstep
    engine replaying the trace at every C for wall-clock, with greedy
    streams asserted bit-identical across chunk sizes.
    Writes BENCH_serve.json (``--tiny`` -> BENCH_serve.tiny.json).
  * ``--faults`` -- the robustness scenario: the same mixed trace
    replayed under a seeded chaos injector sweep (NaN state corruption,
    dropped staging uploads, stragglers; every request must reach a
    terminal status with the slot-step identity and terminal accounting
    exact, and the zero-rate replay bit-identical to a no-injector
    replay), plus a 2x-arrival overload replay against a bounded queue
    (the engine must shed/reject instead of growing without bound).
    Merges a ``robustness`` section into BENCH_serve.json.
  * ``--crash`` -- the crash-recovery acceptance lane: the mixed trace
    served by a journaling engine (``recover_dir`` + snapshot cadence),
    killed at each ``--kill-rounds`` round, restored on a "fresh
    process" via ``ServingEngine.restore`` (newest snapshot +
    journal-tail replay) and driven to completion -- 100% of requests
    must finish with greedy streams bit-identical to an uninterrupted
    reference, recording recovery time and replayed rounds per kill.
    With >= 2 devices a 2x1-mesh leg crashes a data shard mid-trace
    (``shard_crash``) and asserts the failover drain completes every
    request with per-shard slot-step identity intact and streams equal
    to a no-crash mesh run.  Merges ``recovery`` + ``shard_failover``
    rows into the ``robustness`` section of BENCH_serve.json.
  * ``--speculative`` (implies ``--mixed``) -- the same trace replayed
    under n-gram speculative decoding over the (prompt-chunk,
    draft-length) grid: accept rate, inter-token latency in rounds, and
    counter-derived structural decode tokens/s per row, greedy streams
    asserted bit-identical to the non-speculative replays.  Multi-emit
    shrinks device rounds per token, which is a speedup exactly where
    rounds are the cost -- the round-trip-bound regime.

Structural latency model (shared with the decode bench, mirroring
train_throughput.py's convention): decode at serving batch sizes is
weight-bound, so one device round streams the trunk + unembed weights
once -- t_step = weight_bytes / HBM_BW -- and each host call pays one
round-trip.  Wall-clock on CPU runs the Pallas kernels in interpret
mode: honest but not the TPU story; the structural column is.

    PYTHONPATH=src python -m benchmarks.engine_throughput \
        --arch mingru-lm --batches 1 2 4 8
    PYTHONPATH=src python -m benchmarks.engine_throughput --decode
    PYTHONPATH=src python -m benchmarks.engine_throughput --mixed
    PYTHONPATH=src python -m benchmarks.engine_throughput --mixed --tiny
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bench_utils import dump_json, header, row
from repro.configs import archs
from repro.distributed import serve_mesh
from repro.models import lm
from repro.serving.engine import ServingEngine, generate_one, replay_trace
from repro.serving.faults import FaultInjector


# ---------------------------------------------------------------------------
# The seed (v1) engine, vendored as the baseline under test
# ---------------------------------------------------------------------------

class SeedEngine:
    """v1 behavior: per-request prefill, host-side per-slot sampling."""

    def __init__(self, cfg, params, *, max_batch=8, max_len=2048, seed=0):
        self.cfg, self.params = cfg, params
        self.max_batch, self.max_len = max_batch, max_len
        self.cache = lm.init_cache(cfg, max_batch, max_len)
        self.free = list(range(max_batch))
        self.active: Dict[int, dict] = {}
        self.queue: List[dict] = []
        self.finished: Dict[int, list] = {}
        self._rid = 0
        self._rng = np.random.default_rng(seed)
        self._last = np.zeros((max_batch,), np.int32)
        self._decode = jax.jit(
            lambda p, tok, cache: lm.decode_step(p, cfg, tok, cache))

        def _splice(big, one, slot):
            def upd(b, s):
                if b.ndim == 1:
                    return b.at[slot].set(s[0])
                return b.at[:, slot].set(s[:, 0])
            return jax.tree.map(upd, big, one)

        self._splice = jax.jit(_splice, static_argnums=(2,))

    def submit(self, prompt, max_new=32, temperature=0.0):
        rid = self._rid
        self._rid += 1
        self.queue.append(dict(rid=rid, prompt=list(prompt), max_new=max_new,
                               temperature=temperature, out=[]))
        return rid

    def _sample(self, logits, temperature):
        logits = logits[:self.cfg.vocab_size]
        if temperature <= 0:
            return int(logits.argmax())
        p = np.exp((logits - logits.max()) / temperature)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    def step(self):
        while self.queue and self.free:
            req = self.queue.pop(0)
            slot = self.free.pop(0)
            req["slot"] = slot
            logits, one = lm.prefill(
                self.params, self.cfg,
                jnp.asarray([req["prompt"]], jnp.int32), self.max_len)
            self.cache = self._splice(self.cache, one, slot)
            tok = self._sample(np.asarray(logits)[0], req["temperature"])
            req["out"].append(tok)
            self._last[slot] = tok
            self.active[slot] = req
        if not self.active:
            return 0
        logits, self.cache = self._decode(self.params,
                                          jnp.asarray(self._last),
                                          self.cache)
        logits = np.asarray(logits)
        for slot, req in list(self.active.items()):
            t = self._sample(logits[slot], req["temperature"])
            req["out"].append(t)
            self._last[slot] = t
            if len(req["out"]) >= req["max_new"]:
                self.finished[req["rid"]] = req["out"]
                del self.active[slot]
                self.free.append(slot)
        return len(self.active)

    def run_to_completion(self, max_steps=100_000):
        steps = 0
        while (self.queue or self.active) and steps < max_steps:
            self.step()
            steps += 1
        return self.finished


# ---------------------------------------------------------------------------
# Workload + measurement
# ---------------------------------------------------------------------------

def mixed_prompts(n: int, seed: int = 0) -> List[List[int]]:
    """Mixed-length workload: short chat-y prompts + a long tail."""
    rng = np.random.default_rng(seed)
    lens = np.clip(rng.lognormal(mean=2.5, sigma=0.8, size=n), 3, 96
                   ).astype(int)
    return [list(rng.integers(1, 250, size=int(l))) for l in lens]


def run_engine(make_engine, prompts, max_new, temperature):
    """Returns (wall_s, total_tokens) for one full drain of the workload."""
    engine = make_engine()
    for p in prompts:
        engine.submit(p, max_new=max_new, temperature=temperature)
    t0 = time.perf_counter()
    outs = engine.run_to_completion()
    dt = time.perf_counter() - t0
    n_prompt = sum(len(p) for p in prompts)
    n_out = sum(len(o) for o in outs.values())
    assert len(outs) == len(prompts)
    return dt, n_prompt + n_out


def bench(arch: str, batches, n_requests: int, max_new: int,
          temperature: float, out_path: str = "BENCH_engine.json"):
    cfg = archs.smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    max_len = 160
    prompts = mixed_prompts(n_requests)
    header(f"engine throughput {arch}: {n_requests} reqs, "
           f"max_new={max_new}, T={temperature}")

    results = {}
    for mb in batches:
        for name, make in [
            ("seed_v1", lambda mb=mb: SeedEngine(
                cfg, params, max_batch=mb, max_len=max_len)),
            ("v2", lambda mb=mb: ServingEngine(
                cfg, params, max_batch=mb, max_len=max_len)),
        ]:
            run_engine(make, prompts[:2], 4, temperature)   # compile warmup
            dt, toks = run_engine(make, prompts, max_new, temperature)
            tps = toks / dt
            results[(name, mb)] = tps
            row(f"engine_{name}_b{mb}", dt * 1e6, f"{tps:.1f} tok/s")

    speedups = {}
    for mb in batches:
        if ("seed_v1", mb) in results and ("v2", mb) in results:
            speedups[mb] = results[("v2", mb)] / results[("seed_v1", mb)]
            row(f"engine_speedup_b{mb}", 0.0, f"{speedups[mb]:.2f}x v2/v1")
    dump_json(out_path, {
        "arch": arch,
        "n_requests": n_requests,
        "max_new": max_new,
        "tokens_per_s": {f"{name}_b{mb}": tps
                         for (name, mb), tps in results.items()},
        "speedup_v2_over_v1": speedups,
    })
    return results


# ---------------------------------------------------------------------------
# Decode-path bench: per-token baseline vs multi-token on-device decode
# ---------------------------------------------------------------------------

# nominal numbers for the structural latency model; the tracked quantity
# is the RATIO between block sizes, which is insensitive to both
NOMINAL_HBM_GBPS = 819.0        # TPU v5e HBM bandwidth
NOMINAL_ROUNDTRIP_US = 100.0    # dispatch + D2H sync per engine decode call


def decode_weight_bytes_per_step(cfg) -> float:
    """HBM bytes of weights streamed per decode step (minRNN trunk +
    tied unembed).  Activations are (B, D) vectors -- negligible next to
    the weight traffic at serving batch sizes, so this is the whole
    structural cost of one device step."""
    mr = cfg.minrnn
    dx = cfg.d_model
    dh = int(dx * mr.expansion)
    n_proj = 2 if mr.cell == "mingru" else 3
    per_layer = (n_proj + 1) * dx * dh            # gate projections + down
    if mr.use_conv:
        per_layer += mr.conv_kernel * dx
    if mr.use_mlp:
        per_layer += 2 * dx * cfg.d_ff
    total = cfg.n_layers * per_layer + dx * cfg.padded_vocab   # + unembed
    return float(total * jnp.dtype(cfg.cdtype).itemsize)


def structural_decode_tokens_per_s(cfg, batch: int, k: int) -> float:
    t_step = decode_weight_bytes_per_step(cfg) / (NOMINAL_HBM_GBPS * 1e9)
    t_call = k * t_step + NOMINAL_ROUNDTRIP_US * 1e-6
    return batch * k / t_call


# Tier-aware extension (the --timed lane): the whole-block decode kernel
# collapses the per-layer kernel chain (norm / conv step / cell / down /
# MLP) into ONE pallas_call, so telling the tiers apart needs costs the
# weight-stream model deliberately ignores -- each fusion boundary pays
# a kernel-launch latency plus an HBM round-trip of the (B, d_model)
# activation it hands to the next kernel.  As with the other NOMINALs,
# the tracked quantity is the RATIO between kernel tiers at fixed
# config, which is insensitive to the absolute numbers.
NOMINAL_DISPATCH_US = 2.0       # per kernel launch / XLA fusion boundary


def decode_fusion_boundaries(cfg, tier: str) -> int:
    """Kernel-launch / fusion boundaries per decode step under a kernel
    tier, plus one for the embed/head seam.

    ``"block-fused"`` -- one whole-block megakernel per layer.
    ``"cell-fused"`` (the PR 6 baseline, ``fuse_block="off"``) -- the
    cell is one Pallas call but the norm, causal-conv step, down
    projection and the two-dot MLP remain separate fusions (7 per layer
    with conv + MLP).  ``"unfused"`` -- the cell splinters into its gate
    projections and update arithmetic as well."""
    mr = cfg.minrnn
    if tier == "block-fused":
        per_layer = 1
    else:
        # norm + cell + down (+ conv step) (+ MLP norm, in-dot+gelu,
        # out-dot)
        per_layer = 3 + (1 if mr.use_conv else 0) + (3 if mr.use_mlp else 0)
        if tier == "unfused":
            per_layer += 2 if mr.cell == "mingru" else 3
    return cfg.n_layers * per_layer + 1


def decode_activation_bytes_per_step(cfg, tier: str, batch: int) -> float:
    """Boundary-crossing activation traffic per decode step: each fusion
    boundary writes then re-reads one (B, d_model)-scale fp32 tensor."""
    return float(decode_fusion_boundaries(cfg, tier)
                 * 2 * batch * cfg.d_model * 4)


def t_step_for_tier(cfg, tier: str, batch: int) -> float:
    """Structural seconds per device decode round under a kernel tier:
    weight stream + boundary activation traffic + per-boundary dispatch.
    With ``tier="cell-fused"`` and the dispatch/activation terms this
    strictly extends the plain ``decode_weight_bytes_per_step`` model
    the earlier PR rows used; ratios between tiers are the point."""
    bw = NOMINAL_HBM_GBPS * 1e9
    bytes_total = (decode_weight_bytes_per_step(cfg)
                   + decode_activation_bytes_per_step(cfg, tier, batch))
    return (bytes_total / bw
            + decode_fusion_boundaries(cfg, tier) * NOMINAL_DISPATCH_US
            * 1e-6)


def bench_decode(arch: str, batch: int, n_requests: int, max_new: int,
                 blocks, out_path: str = "BENCH_decode.json"):
    """Decode-dominated workload (short prompts, long completions) under
    each decode block size; K=1 is the per-token baseline row."""
    cfg = archs.smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, 250, size=6)) for _ in range(n_requests)]
    header(f"decode throughput {arch}: {n_requests} reqs x {max_new} new "
           f"tokens, batch={batch}, blocks={list(blocks)}, "
           f"backend={jax.default_backend()}")

    results = {}
    outs_by_k = {}
    for k in blocks:
        def make(k=k):
            return ServingEngine(cfg, params, max_batch=batch,
                                 max_len=160, decode_block=k)
        run_engine(make, prompts[:2], 4, 0.0)          # compile warmup
        engine = make()
        for p in prompts:
            engine.submit(p, max_new=max_new, temperature=0.0)
        outs_by_k[k] = engine.run_to_completion()
        s = engine.stats
        wall = s.decode_tokens_per_second()
        structural = structural_decode_tokens_per_s(cfg, batch, k)
        results[str(k)] = {
            "decode_block": k,
            "decode_tokens": s.decode_tokens,
            "decode_calls": s.decode_calls,
            "host_roundtrips_per_decode_token":
                s.decode_calls / max(s.decode_tokens, 1),
            "decode_tokens_per_s_wallclock": wall,
            "decode_tokens_per_s_structural": structural,
        }
        row(f"decode_{arch}_k{k}", s.decode_time_s * 1e6 / max(
                s.decode_calls, 1),
            f"{wall:.1f} tok/s wallclock;{structural:.0f} tok/s structural;"
            f"{s.decode_calls} roundtrips")

    # all block sizes must produce identical greedy streams -- a mismatch
    # means a superstep masking/carry regression, fail loudly
    base_k = blocks[0]
    for k in blocks[1:]:
        if outs_by_k[k] != outs_by_k[base_k]:
            raise SystemExit(
                f"greedy output mismatch between decode_block={base_k} "
                f"and decode_block={k}")

    payload = {
        "arch": arch,
        "batch": batch,
        "n_requests": n_requests,
        "max_new": max_new,
        "nominal_hbm_gbps": NOMINAL_HBM_GBPS,
        "nominal_roundtrip_us": NOMINAL_ROUNDTRIP_US,
        "weight_bytes_per_step": decode_weight_bytes_per_step(cfg),
        "decode_blocks": results,
    }
    if "1" in results:
        base = results["1"]
        best_k = max(results, key=lambda k: int(k))
        best = results[best_k]
        payload["speedup_structural"] = (
            best["decode_tokens_per_s_structural"]
            / base["decode_tokens_per_s_structural"])
        payload["speedup_wallclock"] = (
            best["decode_tokens_per_s_wallclock"]
            / max(base["decode_tokens_per_s_wallclock"], 1e-9))
        row(f"decode_speedup_k{best_k}", 0.0,
            f"{payload['speedup_structural']:.2f}x structural;"
            f"{payload['speedup_wallclock']:.2f}x wallclock vs per-token")
    dump_json(out_path, payload)
    return payload


# ---------------------------------------------------------------------------
# --mixed: arrival-trace scenario, per-phase baseline vs superstep
# ---------------------------------------------------------------------------

def make_trace(n: int, batch: int, seed: int = 0, rate: float = 2.0):
    """Heavy mixed traffic: staggered arrivals at ``rate`` x service
    capacity (so admission stays continuous and the queue never drains
    until the tail), mixed prompt lengths with a long-ish tail, mixed
    completion lengths.  Arrival times are in *device rounds*; both
    simulators and the real engine replay the same trace."""
    rng = np.random.default_rng(seed)
    lens = np.clip(rng.lognormal(mean=1.8, sigma=0.7, size=n), 3, 48
                   ).astype(int)
    news = rng.integers(12, 33, size=n)
    gaps = rng.exponential(scale=float(news.mean()) / (batch * rate),
                           size=n)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    return [dict(arrival=int(a), prompt_len=int(l), max_new=int(m))
            for a, l, m in zip(arrivals, lens, news)]


def simulate_per_phase(trace, batch: int, k: int, t_step: float, rt: float):
    """Round-level simulation of the PR 3 per-phase engine: each step()
    is (admission: one batched parallel-prefill call that barriers
    decode) then (one K-round decode_many call).  First tokens are
    sampled from prefill logits; a slot that finishes mid-buffer stops
    emitting but is retired -- and its slot refillable -- only when the
    buffer drains.  Returns (generated_tokens, virtual_seconds)."""
    pending = list(trace)
    slots: List[Optional[dict]] = [None] * batch
    t, emitted = 0.0, 0
    round_cost = t_step + rt / k            # arrival-clock conversion
    while pending or any(s is not None for s in slots):
        free = [i for i, s in enumerate(slots) if s is None]
        group = []
        while free and pending and pending[0]["arrival"] * round_cost <= t:
            r = pending.pop(0)
            group.append((free.pop(0), r))
        if group:
            # one batched whole-prompt parallel prefill (weight-stream
            # cost, generous to the baseline) + its host round-trip
            t += rt + t_step
            for slot, r in group:
                emitted += 1                # first token from prefill
                rem = r["max_new"] - 1
                slots[slot] = {"rem": rem} if rem > 0 else None
        if any(s is not None for s in slots):
            t += rt + k * t_step
            for _ in range(k):
                for s in slots:
                    if s is not None and s["rem"] > 0:
                        s["rem"] -= 1
                        emitted += 1
            for i, s in enumerate(slots):   # retire at buffer end only
                if s is not None and s["rem"] <= 0:
                    slots[i] = None
        elif not group and pending:         # idle until the next arrival
            t = max(t, pending[0]["arrival"] * round_cost)
    return emitted, t


def simulate_superstep(trace, batch: int, k: int, t_step: float, rt: float,
                       prompt_chunk: int = 1):
    """Round-level simulation of the superstep engine: staging between
    calls, in-loop arming, teacher-forced prompt consumption riding the
    decode rounds, immediate re-admission.  A prefilling slot consumes
    ``min(prompt_chunk, prompt_left)`` tokens per round (the packed-
    prefill branch; 1 = the unpacked PR 4 behaviour); each round still
    costs one weight stream -- activations are negligible next to the
    weights at serving batch sizes, which is exactly why packing wins
    the weight-bound regime.  Returns (generated_tokens,
    virtual_seconds)."""
    pending = list(trace)
    slots: List[Optional[dict]] = [None] * batch
    staged: List[Optional[dict]] = [None] * batch
    t, emitted = 0.0, 0
    round_cost = t_step + rt / k
    while pending or any(slots) or any(s is not None for s in staged):
        order = sorted(range(batch),
                       key=lambda i: (slots[i] is not None, i))
        for i in order:
            if staged[i] is None and pending and \
                    pending[0]["arrival"] * round_cost <= t:
                staged[i] = pending.pop(0)
        if not any(s is not None for s in slots) and \
                not any(s is not None for s in staged):
            t = max(t, pending[0]["arrival"] * round_cost)
            continue
        t += rt + k * t_step
        for _ in range(k):
            for i in range(batch):
                if slots[i] is None and staged[i] is not None:
                    r = staged[i]
                    staged[i] = None
                    slots[i] = {"p": r["prompt_len"], "rem": r["max_new"]}
                s = slots[i]
                if s is None:
                    continue
                if s["p"] > 0:
                    s["p"] -= min(prompt_chunk, s["p"])   # packed prefill
                    if s["p"] > 0:
                        continue            # prompt straddles the chunk
                # reached the last prompt token (or already decoding):
                # this round emits
                s["rem"] -= 1
                emitted += 1
                if s["rem"] <= 0:
                    slots[i] = None
    return emitted, t


def _trace_prompt(i: int, n: int):
    return list(np.random.default_rng(i).integers(1, 250, size=n))


def replay_real_engine(cfg, params, trace, batch: int, k: int,
                       max_len: int = 160, prompt_chunk: int = 1,
                       speculative=None, draft_len: int = 4, mesh=None,
                       **engine_kw):
    """Run the actual superstep engine over the arrival trace (arrival
    clock = engine device rounds) and return (stats snapshot, greedy
    streams by trace index).  Greedy streams are spot-checked
    bit-identical to ``generate_one`` -- except under tensor parallelism
    (``mesh`` with model > 1), where the contract is argmax-equivalence
    (the mesh bench records full-stream equality separately).  Extra
    keywords (``fuse_block``, ``tune``, ...) pass through to the
    engine."""
    engine = ServingEngine(cfg, params, max_batch=batch, max_len=max_len,
                           decode_block=k, prompt_chunk=prompt_chunk,
                           speculative=speculative, draft_len=draft_len,
                           mesh=mesh, **engine_kw)
    rids = []
    replay_trace(engine, trace, lambda i, r: rids.append(engine.submit(
        _trace_prompt(i, r["prompt_len"]), max_new=r["max_new"],
        temperature=0.0)))
    assert engine.stats.completed == len(trace)
    # mid-flight admission / re-admission must not perturb streams:
    # spot-check a few against the single-request reference, loudly
    strict = engine.mesh_plan is None or engine.mesh_plan.model <= 1
    if strict:
        for j in list(range(0, len(trace), max(1, len(trace) // 3)))[:3]:
            ref = generate_one(cfg, params, _trace_prompt(
                j, trace[j]["prompt_len"]), max_new=trace[j]["max_new"],
                max_len=max_len)
            if engine.finished[rids[j]].out != ref:
                raise SystemExit(
                    f"greedy stream mismatch vs generate_one for request "
                    f"{j} at prompt_chunk={prompt_chunk} "
                    f"speculative={speculative!r} mesh={mesh!r}")
    outs = [engine.finished[rid].out for rid in rids]
    snap = engine.stats.snapshot()
    snap["_kernel_tier"] = engine.kernel_tier     # dropped by key filters
    snap["_tune_plan"] = engine.tune_plan
    return snap, outs


def structural_decode_tps_from_counters(snap, t_step: float,
                                        rt: float) -> float:
    """Structural decode tokens/s of a REAL replay: the counted device
    rounds each stream the weights once (the varlen chunk kernels keep
    one weight stream per round whatever the verify/prefill width) and
    each host call pays one round-trip.  Speculation shrinks
    ``decode_steps`` at fixed ``decode_tokens`` -- multi-emit rounds --
    which is exactly the round-trip-bound-regime win this metric
    measures."""
    t = snap["decode_steps"] * t_step + snap["decode_calls"] * rt
    return snap["decode_tokens"] / max(t, 1e-12)


_REAL_ENGINE_KEYS = (
    "decode_tokens_per_second", "tokens_per_second", "decode_tokens",
    "prefill_tokens", "prefill_rounds", "decode_calls", "decode_steps",
    "slot_steps", "wasted_slot_steps", "wasted_slot_fraction",
    "host_roundtrips_per_decode_token", "ttft_rounds_mean", "ttft_s_mean",
    "ttft_s_p95", "itl_s_mean", "itl_rounds_mean", "queue_peak",
    "prompt_chunk", "draft_proposed", "draft_accepted", "non_spec_tokens",
    "accept_rate")


def bench_mixed(arch: str, batch: int, n_requests: int, k: int,
                chunks=(1, 4, 16), out_path: str = "BENCH_serve.json",
                spec_drafts=()):
    """Arrival-trace scenario with a ``--prompt-chunk`` sweep: for each C
    the superstep simulator (smoke + full-config weight bytes) runs
    against the shared per-phase baseline, and the REAL engine replays
    the trace.  Greedy streams must be bit-identical across every C --
    packing may only change *when* prompt tokens are consumed, never
    what gets generated.

    With ``spec_drafts`` (draft lengths S) the REAL engine additionally
    replays the trace speculatively (n-gram self-draft) over the
    (C, S) grid: accept rate, inter-token latency in rounds, and the
    counter-derived structural decode tokens/s land in the payload's
    ``speculative`` section, with greedy streams asserted bit-identical
    to the non-speculative replays -- drafts may only change *when*
    tokens emit, never what gets generated."""
    cfg = archs.smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    trace = make_trace(n_requests, batch)
    t_step = decode_weight_bytes_per_step(cfg) / (NOMINAL_HBM_GBPS * 1e9)
    rt = NOMINAL_ROUNDTRIP_US * 1e-6
    chunks = sorted({max(1, int(c)) for c in chunks} | {1})
    header(f"mixed arrival-trace serving {arch}: {n_requests} reqs, "
           f"batch={batch}, K={k}, prompt chunks {chunks}, "
           f"backend={jax.default_backend()}")

    full = archs.get(arch)
    t_step_full = (decode_weight_bytes_per_step(full)
                   / (NOMINAL_HBM_GBPS * 1e9))
    n_expect = sum(r["max_new"] for r in trace)

    tok_pp, t_pp = simulate_per_phase(trace, batch, k, t_step, rt)
    tok_pp_f, t_pp_f = simulate_per_phase(trace, batch, k, t_step_full, rt)
    tps_pp = tok_pp / t_pp
    tps_pp_f = tok_pp_f / t_pp_f
    assert tok_pp == tok_pp_f == n_expect
    row(f"serve_per_phase_k{k}", t_pp * 1e6, f"{tps_pp:.0f} tok/s structural")

    per_chunk = {}
    outs_by_chunk = {}
    for c in chunks:
        tok_ss, t_ss = simulate_superstep(trace, batch, k, t_step, rt,
                                          prompt_chunk=c)
        tok_ss_f, t_ss_f = simulate_superstep(trace, batch, k, t_step_full,
                                              rt, prompt_chunk=c)
        assert tok_ss == tok_ss_f == n_expect
        tps_ss = tok_ss / t_ss
        speedup = tps_ss / tps_pp
        speedup_full = (tok_ss_f / t_ss_f) / tps_pp_f
        snap, outs = replay_real_engine(cfg, params, trace, batch, k,
                                        prompt_chunk=c)
        outs_by_chunk[c] = outs
        per_chunk[str(c)] = {
            "prompt_chunk": c,
            "superstep_tokens_per_s_structural": tps_ss,
            "speedup_structural": speedup,
            "speedup_structural_full_config": speedup_full,
            # counter-derived structural decode tok/s of the REAL replay
            # (small config = round-trip-bound regime, full config =
            # weight-bound) -- the non-speculative baselines the
            # speculative sweep compares against
            "real_structural_decode_tokens_per_s":
                structural_decode_tps_from_counters(snap, t_step, rt),
            "real_structural_decode_tokens_per_s_full_config":
                structural_decode_tps_from_counters(snap, t_step_full, rt),
            "real_engine": {key: snap[key] for key in _REAL_ENGINE_KEYS},
        }
        row(f"serve_superstep_k{k}_c{c}", t_ss * 1e6,
            f"{tps_ss:.0f} tok/s structural;{speedup:.2f}x small;"
            f"{speedup_full:.2f}x full-config")
        row(f"serve_wallclock_k{k}_c{c}",
            snap["decode_time_s"] * 1e6 / max(snap["decode_calls"], 1),
            f"{snap['decode_tokens_per_second']:.1f} decode tok/s wall;"
            f"waste {snap['wasted_slot_fraction']:.1%};"
            f"ttft {snap['ttft_rounds_mean']:.1f} rounds")

    # packing must not change WHAT is generated, for any chunk size
    for c in chunks[1:]:
        if outs_by_chunk[c] != outs_by_chunk[chunks[0]]:
            raise SystemExit(
                f"greedy stream mismatch between prompt_chunk="
                f"{chunks[0]} and prompt_chunk={c}")

    # ---- speculative sweep: n-gram self-draft over the (C, S) grid ----
    speculative = {}
    if spec_drafts:
        # compare against the strongest NON-spec replay in each regime
        base_rt = max(per_chunk.values(), key=lambda r: r[
            "real_structural_decode_tokens_per_s"])
        base_wb = max(per_chunk.values(), key=lambda r: r[
            "real_structural_decode_tokens_per_s_full_config"])
        for c in chunks:
            for s in sorted({max(1, int(s)) for s in spec_drafts}):
                snap, outs = replay_real_engine(
                    cfg, params, trace, batch, k, prompt_chunk=c,
                    speculative="ngram", draft_len=s)
                if outs != outs_by_chunk[chunks[0]]:
                    raise SystemExit(
                        f"greedy stream mismatch: speculative C={c} S={s} "
                        f"vs non-speculative")
                tps_rt = structural_decode_tps_from_counters(snap, t_step,
                                                             rt)
                tps_wb = structural_decode_tps_from_counters(
                    snap, t_step_full, rt)
                speculative[f"c{c}_s{s}"] = {
                    "prompt_chunk": c,
                    "draft_len": s,
                    "accept_rate": snap["accept_rate"],
                    "itl_rounds_mean": snap["itl_rounds_mean"],
                    "itl_s_mean": snap["itl_s_mean"],
                    "structural_decode_tokens_per_s": tps_rt,
                    "structural_decode_tokens_per_s_full_config": tps_wb,
                    "speedup_vs_nonspec_best": tps_rt / base_rt[
                        "real_structural_decode_tokens_per_s"],
                    "speedup_vs_nonspec_best_full_config": tps_wb / base_wb[
                        "real_structural_decode_tokens_per_s_full_config"],
                    "real_engine": {key: snap[key]
                                    for key in _REAL_ENGINE_KEYS},
                }
                r = speculative[f"c{c}_s{s}"]
                row(f"serve_spec_k{k}_c{c}_s{s}",
                    snap["decode_time_s"] * 1e6 / max(
                        snap["decode_calls"], 1),
                    f"accept {r['accept_rate']:.2f};"
                    f"itl {r['itl_rounds_mean']:.2f} rounds;"
                    f"{r['speedup_vs_nonspec_best']:.2f}x round-trip-bound;"
                    f"{r['speedup_vs_nonspec_best_full_config']:.2f}x "
                    f"weight-bound")

    best_c = max(chunks, key=lambda c: per_chunk[str(c)][
        "speedup_structural_full_config"])
    best = per_chunk[str(best_c)]
    row(f"serve_speedup_k{k}", 0.0,
        f"{per_chunk['1']['speedup_structural']:.2f}x small-config C=1;"
        f"{best['speedup_structural_full_config']:.2f}x full-config "
        f"C={best_c}")

    payload = {
        "arch": arch,
        "batch": batch,
        "n_requests": n_requests,
        "decode_block": k,
        "prompt_chunks": per_chunk,
        "nominal_hbm_gbps": NOMINAL_HBM_GBPS,
        "nominal_roundtrip_us": NOMINAL_ROUNDTRIP_US,
        "trace_generated_tokens": n_expect,
        "per_phase_tokens_per_s_structural": tps_pp,
        # trajectory continuity: the C=1 rows keep their PR 4 meaning
        "superstep_tokens_per_s_structural":
            per_chunk["1"]["superstep_tokens_per_s_structural"],
        "speedup_structural": per_chunk["1"]["speedup_structural"],
        # the packed headline: best-chunk full-config speedup (the PR 4
        # regression this sweep exists to erase was 0.91 at C=1)
        "speedup_structural_full_config":
            best["speedup_structural_full_config"],
        "speedup_structural_full_config_unpacked":
            per_chunk["1"]["speedup_structural_full_config"],
        "prompt_chunk_best": best_c,
        "real_engine": per_chunk[str(best_c)]["real_engine"],
    }
    if speculative:
        best_spec_key = max(speculative, key=lambda key: speculative[key][
            "speedup_vs_nonspec_best"])
        best_spec = speculative[best_spec_key]
        payload["speculative"] = speculative
        payload["speculative_best"] = best_spec_key
        # the speculative headline: best (C, S) vs the best non-spec row
        # in the round-trip-bound regime (multi-emit shrinks rounds per
        # token; the weight-bound column rides along for the trajectory)
        payload["speculative_speedup_structural"] = best_spec[
            "speedup_vs_nonspec_best"]
        payload["speculative_accept_rate"] = best_spec["accept_rate"]
        row(f"serve_spec_speedup_k{k}", 0.0,
            f"{best_spec['speedup_vs_nonspec_best']:.2f}x round-trip-bound "
            f"{best_spec_key};accept {best_spec['accept_rate']:.2f}")
    dump_json(out_path, payload)
    return payload


# ---------------------------------------------------------------------------
# --timed: block-fused vs cell-fused decode, wall-clock + tier-aware model
# ---------------------------------------------------------------------------

def bench_timed(arch: str, batch: int, n_requests: int, k: int,
                prompt_chunk: int = 16,
                out_path: str = "BENCH_serve.json", tune="auto"):
    """The whole-block megakernel acceptance lane: replay the mixed
    arrival trace twice on the REAL engine -- ``fuse_block="off"`` (the
    PR 8 cell-fused engine, byte-for-byte the configuration behind the
    existing ``prompt_chunks`` best row: same trace, same C, same K) and
    ``fuse_block="auto"`` (the block-fused tier) -- assert the greedy
    streams BIT-IDENTICAL between tiers, and record for each tier both
    the measured wall-clock decode tokens/s and the tier-aware
    structural tokens/s (weight stream + per-boundary dispatch +
    boundary activation traffic) on the smoke and full configs.  The
    headline ``speedup_structural_full_config`` is block-fused over
    cell-fused on the full config, i.e. over the PR 8 single-device best
    re-derived under the extended model (the extension is what lets the
    model see fusion at all -- the plain weight-stream model is
    tier-blind by construction).  Wall-clock on CPU is interpret-mode
    Pallas: recorded honestly alongside, but the structural column is
    the TPU story.  Merges a ``block_fused`` section into
    BENCH_serve.json."""
    cfg = archs.smoke(arch)
    full = archs.get(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    trace = make_trace(n_requests, batch)
    rt = NOMINAL_ROUNDTRIP_US * 1e-6
    header(f"timed block-fused decode {arch}: {n_requests} reqs, "
           f"batch={batch}, K={k}, C={prompt_chunk}, tune={tune!r}, "
           f"backend={jax.default_backend()}")

    tiers = {}
    outs_by_tier = {}
    plan_used = None
    for fuse in ("off", "auto"):
        snap, outs = replay_real_engine(cfg, params, trace, batch, k,
                                        prompt_chunk=prompt_chunk,
                                        fuse_block=fuse, tune=tune)
        tier = snap["_kernel_tier"]
        if fuse == "auto" and snap["_tune_plan"] is not None:
            plan_used = snap["_tune_plan"]
        outs_by_tier[fuse] = outs
        t_smoke = t_step_for_tier(cfg, tier, batch)
        t_full = t_step_for_tier(full, tier, batch)
        tiers[tier] = {
            "fuse_block": fuse,
            "kernel_tier": tier,
            "fusion_boundaries_per_step":
                decode_fusion_boundaries(cfg, tier),
            "fusion_boundaries_per_step_full_config":
                decode_fusion_boundaries(full, tier),
            "t_step_us": t_smoke * 1e6,
            "t_step_us_full_config": t_full * 1e6,
            "wallclock_decode_tokens_per_s":
                snap["decode_tokens_per_second"],
            "wallclock_decode_time_s": snap["decode_time_s"],
            "structural_decode_tokens_per_s":
                structural_decode_tps_from_counters(snap, t_smoke, rt),
            "structural_decode_tokens_per_s_full_config":
                structural_decode_tps_from_counters(snap, t_full, rt),
            "real_engine": {key: snap[key] for key in _REAL_ENGINE_KEYS},
        }
        r = tiers[tier]
        row(f"serve_timed_{tier}_k{k}_c{prompt_chunk}",
            snap["decode_time_s"] * 1e6 / max(snap["decode_calls"], 1),
            f"{r['wallclock_decode_tokens_per_s']:.1f} tok/s wall;"
            f"{r['structural_decode_tokens_per_s_full_config']:.0f} "
            f"full-config structural;"
            f"{r['fusion_boundaries_per_step_full_config']} boundaries")

    # the acceptance bit: fusing the whole block may change HOW a round
    # runs, never WHAT gets generated
    if outs_by_tier["auto"] != outs_by_tier["off"]:
        raise SystemExit(
            "greedy stream mismatch between block-fused and cell-fused "
            "decode -- the megakernel broke the parity contract")
    if "block-fused" not in tiers:
        raise SystemExit(
            f"fuse_block='auto' did not engage the block kernel "
            f"(tiers seen: {sorted(tiers)}) -- dispatch regression")

    blk = tiers["block-fused"]
    cell = tiers["cell-fused"]
    section = {
        "arch": arch,
        "batch": batch,
        "n_requests": n_requests,
        "decode_block": k,
        "prompt_chunk": prompt_chunk,
        "nominal_dispatch_us": NOMINAL_DISPATCH_US,
        "greedy_streams_identical": True,
        "tune": tune if isinstance(tune, str) or tune is None else "dict",
        "tune_plan": plan_used,
        "tiers": tiers,
        # baseline provenance: the cell-fused replay IS the PR 8 engine
        # (fuse_block="off") on the PR 8 best configuration, re-scored
        # under the tier-aware model
        "speedup_wallclock":
            blk["wallclock_decode_tokens_per_s"]
            / max(cell["wallclock_decode_tokens_per_s"], 1e-9),
        "speedup_structural":
            blk["structural_decode_tokens_per_s"]
            / cell["structural_decode_tokens_per_s"],
        "speedup_structural_full_config":
            blk["structural_decode_tokens_per_s_full_config"]
            / cell["structural_decode_tokens_per_s_full_config"],
    }
    row(f"serve_timed_speedup_k{k}", 0.0,
        f"{section['speedup_structural_full_config']:.2f}x full-config "
        f"structural;{section['speedup_wallclock']:.2f}x wallclock "
        f"(interpret on CPU)")

    merged = {}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                merged = json.load(f)
        except ValueError:
            merged = {}
    merged["block_fused"] = section
    dump_json(out_path, merged)
    return section


# ---------------------------------------------------------------------------
# --faults: chaos replay + overload shedding (the robustness scenario)
# ---------------------------------------------------------------------------

def _identity_ok(snap) -> bool:
    """Extended slot-step identity of a (non-speculative) replay.  The
    overlap term is the number of recorded first tokens (one per service
    epoch that emitted anything); the snapshot drops list fields, so the
    replay carries the count along as ``_n_first_tokens``."""
    return snap["slot_steps"] == (
        snap["prefill_rounds"] + snap["decode_tokens"]
        - snap["_n_first_tokens"] + snap["wasted_slot_steps"]
        + snap["nonfinite_decode_rounds"])


def _replay_under_faults(cfg, params, trace, batch: int, k: int,
                         injector, max_len: int = 160, **engine_kw):
    """Replay the arrival trace on a fresh engine (optionally with a
    chaos injector) until every request is terminal.  Returns
    (stats snapshot + derived robustness metrics, streams by index)."""
    engine = ServingEngine(cfg, params, max_batch=batch, max_len=max_len,
                           decode_block=k, faults=injector, **engine_kw)
    rids = []
    replay_trace(engine, trace, lambda i, r: rids.append(engine.submit(
        _trace_prompt(i, r["prompt_len"]), max_new=r["max_new"],
        temperature=0.0, deadline=r.get("deadline"))))
    if len(engine.finished) != len(trace):
        raise SystemExit(
            f"chaos replay leaked requests: {len(engine.finished)} "
            f"terminal of {len(trace)} submitted")
    snap = engine.stats.snapshot()
    snap["_n_first_tokens"] = len(engine.stats.ttft_rounds)
    if not _identity_ok(snap):
        raise SystemExit(
            f"slot-step identity violated under faults: {snap}")
    s = engine.stats
    if s.submitted != (s.completed + s.cancelled + s.timed_out + s.failed
                       + s.shed + s.rejected):
        raise SystemExit(f"terminal accounting violated: {snap}")
    good_toks = sum(len(r.out) for r in engine.finished.values()
                    if r.status == "COMPLETED")
    snap["goodput_tokens"] = good_toks
    snap["goodput_tokens_per_s"] = good_toks / max(s.decode_time_s, 1e-9)
    if injector is not None:
        snap["faults_injected"] = injector.counts()
    return snap, [engine.finished[rid].out for rid in rids]


_ROBUST_KEYS = (
    "submitted", "completed", "completion_rate", "cancelled", "timed_out",
    "failed", "retried", "shed", "rejected", "quarantined",
    "nonfinite_decode_rounds", "queue_peak", "goodput_tokens",
    "goodput_tokens_per_s", "decode_tokens", "wasted_slot_fraction")


def bench_robustness(arch: str, batch: int, n_requests: int, k: int,
                     fault_rates=(0.0, 0.002, 0.01),
                     out_path: str = "BENCH_serve.json"):
    """Chaos + overload scenario (the fault-tolerance acceptance run).

    Replays the mixed arrival trace under a seeded ``FaultInjector``
    sweep (NaN state corruption + dropped staging uploads + stragglers
    at each rate): every submitted request must reach a terminal status,
    the extended slot-step identity and terminal accounting must hold
    exactly, and the rate-0.0 replay must be bit-identical to a
    no-injector replay (the harness is inert when idle).  Then replays a
    2x-arrival overload trace against a bounded queue: the engine must
    shed/reject instead of queueing without bound.  Results land in the
    ``robustness`` section of BENCH_serve.json, merged into the existing
    payload when present.
    """
    cfg = archs.smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    trace = make_trace(n_requests, batch)
    header(f"chaos + overload serving {arch}: {n_requests} reqs, "
           f"batch={batch}, K={k}, fault rates {list(fault_rates)}, "
           f"backend={jax.default_backend()}")

    base_snap, base_outs = _replay_under_faults(cfg, params, trace, batch,
                                                k, None)
    by_rate = {}
    for rate in sorted({float(r) for r in fault_rates}):
        inj = FaultInjector(seed=1, nan_rate=rate, drop_rate=rate,
                            straggler_rate=rate, straggler_s=0.002)
        snap, outs = _replay_under_faults(cfg, params, trace, batch, k,
                                          inj, max_retries=2,
                                          retry_backoff=4)
        if rate == 0.0 and outs != base_outs:
            raise SystemExit("zero-rate injector perturbed streams -- "
                             "the fault harness is not inert")
        by_rate[f"{rate:g}"] = {key: snap[key] for key in _ROBUST_KEYS}
        by_rate[f"{rate:g}"]["faults_injected"] = snap["faults_injected"]
        row(f"serve_chaos_rate{rate:g}",
            snap["decode_time_s"] * 1e6 / max(snap["decode_calls"], 1),
            f"completion {snap['completion_rate']:.2f};"
            f"quarantined {snap['quarantined']};"
            f"retried {snap['retried']};failed {snap['failed']};"
            f"goodput {snap['goodput_tokens_per_s']:.1f} tok/s")

    # ---- overload: 2x the arrival rate against a bounded queue --------
    overload = make_trace(n_requests, batch, seed=1, rate=4.0)
    for i, r in enumerate(overload):    # a deadline slice exercises
        if i % 4 == 0:                  # SHED_UNMEETABLE at admission
            r["deadline"] = 2 * (r["prompt_len"] + r["max_new"])
    max_queue = max(4, 2 * batch)
    snap, _ = _replay_under_faults(cfg, params, overload, batch, k, None,
                                   max_queue=max_queue,
                                   high_watermark=1.0, low_watermark=0.5)
    if snap["queue_peak"] > max_queue:
        raise SystemExit(
            f"bounded queue exceeded its bound: peak "
            f"{snap['queue_peak']} > {max_queue}")
    if snap["rejected"] + snap["shed"] + snap["timed_out"] == 0:
        raise SystemExit("overload replay shed nothing -- backpressure "
                         "is not engaging")
    over = {key: snap[key] for key in _ROBUST_KEYS}
    over["max_queue"] = max_queue
    row(f"serve_overload_q{max_queue}",
        snap["decode_time_s"] * 1e6 / max(snap["decode_calls"], 1),
        f"completion {snap['completion_rate']:.2f};"
        f"rejected {snap['rejected']};shed {snap['shed']};"
        f"timed_out {snap['timed_out']};queue_peak {snap['queue_peak']}")

    robustness = {
        "arch": arch, "batch": batch, "n_requests": n_requests,
        "decode_block": k, "max_retries": 2,
        "fault_rates": by_rate,
        "fault_free": {key: base_snap[key] for key in _ROBUST_KEYS},
        "overload_2x": over,
    }
    merged = {}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                merged = json.load(f)
        except ValueError:
            merged = {}
    # the crash lane co-owns this section: keep its rows when re-running
    prior = merged.get("robustness") or {}
    for keep in ("recovery", "shard_failover"):
        if keep in prior:
            robustness[keep] = prior[keep]
    merged["robustness"] = robustness
    dump_json(out_path, merged)
    return robustness


# ---------------------------------------------------------------------------
# --crash: kill/restore replay + DP-shard failover (the recovery lane)
# ---------------------------------------------------------------------------

def _merge_robustness(out_path: str, key: str, section) -> None:
    """Merge one sub-section into BENCH_serve.json's ``robustness``
    block without clobbering the chaos/overload rows."""
    merged = {}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                merged = json.load(f)
        except ValueError:
            merged = {}
    merged.setdefault("robustness", {})[key] = section
    dump_json(out_path, merged)


def bench_crash(arch: str, batch: int, n_requests: int, k: int,
                kill_rounds=None, snapshot_every: int = 8,
                out_path: str = "BENCH_serve.json"):
    """Crash-recovery acceptance run (see module docstring ``--crash``).

    For each kill round: serve the mixed trace on a journaling engine,
    abandon it mid-trace (the process "crashes" -- the journal is
    already durable, the engine object is simply dropped), restore via
    ``ServingEngine.restore`` and drive the remaining trace.  Every
    request must reach COMPLETED and every greedy stream must be
    bit-identical to the uninterrupted reference -- recovery is only
    recovery if nobody downstream can tell it happened.  Then, with
    >= 2 devices, the DP-shard failover leg kills shard 1 of a 2x1 mesh
    mid-trace and asserts the drain onto shard 0 completes everything
    with per-shard identity intact.
    """
    cfg = archs.smoke(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    trace = make_trace(n_requests, batch)
    header(f"crash recovery {arch}: {n_requests} reqs, batch={batch}, "
           f"K={k}, snapshot every {snapshot_every} rounds, "
           f"backend={jax.default_backend()}")

    def driver(engine):
        return lambda i, r: engine.submit(
            _trace_prompt(i, r["prompt_len"]), max_new=r["max_new"],
            temperature=0.0)

    # uninterrupted reference (no journal): the oracle every restored
    # run must match stream for stream
    ref = ServingEngine(cfg, params, max_batch=batch, max_len=160,
                        decode_block=k)
    replay_trace(ref, trace, driver(ref))
    assert ref.stats.completed == len(trace)
    ref_outs = [ref.finished[i].out for i in range(len(trace))]
    total_rounds = ref.stats.decode_steps

    if not kill_rounds:
        kill_rounds = sorted({max(1, total_rounds // 4),
                              max(2, total_rounds // 2),
                              max(3, (3 * total_rounds) // 4)})
    kills = []
    for kill in kill_rounds:
        d = tempfile.mkdtemp(prefix="bench_crash_")
        try:
            eng = ServingEngine(cfg, params, max_batch=batch, max_len=160,
                                decode_block=k, recover_dir=d,
                                snapshot_every=snapshot_every)
            submitted = replay_trace(
                eng, trace, driver(eng),
                stop=lambda e: e.stats.decode_steps >= kill)
            del eng     # the crash: no shutdown, no flush beyond the WAL
            rec = ServingEngine.restore(d, cfg, params)
            report = rec.recovery_report
            assert len(rec.requests) == submitted
            replay_trace(rec, trace, driver(rec),
                         start=len(rec.requests))
            outs = [rec.finished[i].out for i in range(len(trace))]
            if rec.stats.completed != len(trace):
                raise SystemExit(
                    f"kill@{kill}: restored run completed "
                    f"{rec.stats.completed}/{len(trace)} requests")
            if outs != ref_outs:
                raise SystemExit(
                    f"kill@{kill}: restored greedy streams diverge from "
                    f"the uninterrupted reference")
            if rec.stats.decode_steps != total_rounds:
                raise SystemExit(
                    f"kill@{kill}: restored run took "
                    f"{rec.stats.decode_steps} rounds, reference took "
                    f"{total_rounds} -- the round clocks diverged")
            kills.append({
                "kill_round": int(kill),
                "submitted_at_kill": int(submitted),
                "snapshot_round": report["snapshot_round"],
                "replayed_records": report["replayed_records"],
                "replayed_rounds": report["replayed_rounds"],
                "recovery_s": report["recovery_s"],
                "outputs_equal": True,
                "completed": int(rec.stats.completed),
            })
            row(f"serve_crash_kill{kill}", report["recovery_s"] * 1e6,
                f"snapshot @{report['snapshot_round']};"
                f"replayed {report['replayed_rounds']} rounds"
                f" ({report['replayed_records']} records);"
                f"outputs equal")
        finally:
            shutil.rmtree(d, ignore_errors=True)

    section = {
        "arch": arch, "batch": batch, "n_requests": n_requests,
        "decode_block": k, "snapshot_every": snapshot_every,
        "total_rounds": int(total_rounds), "kills": kills,
    }
    _merge_robustness(out_path, "recovery", section)

    # ---- DP-shard failover on a 2x1 mesh ------------------------------
    if len(jax.devices()) < 2 or batch % 2 != 0:
        print(f"# shard-failover leg skipped: needs >= 2 devices and an "
              f"even batch (have {len(jax.devices())} device(s), "
              f"batch={batch})")
        return section
    mesh_ref = ServingEngine(cfg, params, max_batch=batch, max_len=160,
                             decode_block=k, mesh="2x1")
    replay_trace(mesh_ref, trace, driver(mesh_ref))
    mesh_outs = [mesh_ref.finished[i].out for i in range(len(trace))]
    crash_round = max(1, mesh_ref.stats.decode_steps // 3)

    inj = FaultInjector(shard_crash_at=((crash_round, 1),))
    eng = ServingEngine(cfg, params, max_batch=batch, max_len=160,
                        decode_block=k, mesh="2x1", faults=inj)
    replay_trace(eng, trace, driver(eng))
    s = eng.stats
    if s.completed != len(trace):
        raise SystemExit(
            f"shard failover completed {s.completed}/{len(trace)}")
    outs = [eng.finished[i].out for i in range(len(trace))]
    if outs != mesh_outs:
        raise SystemExit("failover streams diverge from the no-crash "
                         "mesh run -- greedy output must be placement-"
                         "independent")
    if not s.shard_identities_ok():
        raise SystemExit("per-shard slot-step identity broken by the "
                         "shard crash")
    if s.submitted != (s.completed + s.cancelled + s.timed_out + s.failed
                       + s.shed + s.rejected):
        raise SystemExit("terminal accounting violated under failover")
    failover = {
        "mesh": "2x1", "crash_round": int(crash_round), "shard": 1,
        "shard_crashes": s.shard_crashes,
        "failover_requeued": s.failover_requeued,
        "completed": s.completed,
        "decode_steps": s.decode_steps,
        "no_crash_decode_steps": mesh_ref.stats.decode_steps,
        "dead_shard_wasted_slot_steps": s.shards[1].wasted_slot_steps,
        "outputs_equal": True, "shard_identity_ok": True,
        "faults_injected": inj.counts(),
    }
    row(f"serve_failover_r{crash_round}",
        s.decode_time_s * 1e6 / max(s.decode_calls, 1),
        f"shard 1 died @{crash_round};"
        f"requeued {s.failover_requeued};"
        f"rounds {s.decode_steps} vs {mesh_ref.stats.decode_steps} "
        f"no-crash;outputs equal")
    _merge_robustness(out_path, "shard_failover", failover)
    section["shard_failover"] = failover
    return section


# ---------------------------------------------------------------------------
# --mesh-shapes: data/tensor-parallel serving sweep (the mesh scenario)
# ---------------------------------------------------------------------------

# cross-shard collective cost for the tensor-parallel structural model:
# each TP psum moves the (B_local, d_model) fp32 partials ring-wise
# (2*(m-1)/m of the payload per chip) over the interconnect, plus a
# fixed per-collective launch latency.  As with the HBM numbers, the
# tracked quantity is the RATIO between mesh shapes.
NOMINAL_ICI_GBPS = 100.0        # TPU v5e ICI per-link bandwidth
NOMINAL_COLLECTIVE_US = 1.0     # per-psum launch/sync latency


def mesh_weight_bytes(cfg):
    """Per-step weight stream split into (shardable, replicated) bytes:
    the gate/down/MLP projections shard d_hidden / d_ff over ``model``;
    the depthwise conv and the unembedding stay replicated per shard
    (serve_mesh whitelist)."""
    mr = cfg.minrnn
    dx = cfg.d_model
    dh = int(dx * mr.expansion)
    n_proj = 2 if mr.cell == "mingru" else 3
    shard_layer = (n_proj + 1) * dx * dh
    if mr.use_mlp:
        shard_layer += 2 * dx * cfg.d_ff
    rep_layer = mr.conv_kernel * dx if mr.use_conv else 0
    item = jnp.dtype(cfg.cdtype).itemsize
    shardable = float(cfg.n_layers * shard_layer * item)
    replicated = float((cfg.n_layers * rep_layer
                        + dx * cfg.padded_vocab) * item)
    return shardable, replicated


def mesh_t_step(cfg, model_shards: int, batch_local: int) -> float:
    """Structural seconds per device round on one chip of a mesh with
    ``model_shards``-way TP: per-shard HBM weight stream + the per-layer
    psum collectives (one per mixer, one per MLP)."""
    shardable, replicated = mesh_weight_bytes(cfg)
    t = (shardable / model_shards + replicated) / (NOMINAL_HBM_GBPS * 1e9)
    if model_shards > 1:
        n_psums = cfg.n_layers * (2 if cfg.minrnn.use_mlp else 1)
        payload = batch_local * cfg.d_model * 4          # fp32 partials
        t += n_psums * (
            payload * 2 * (model_shards - 1) / model_shards
            / (NOMINAL_ICI_GBPS * 1e9)
            + NOMINAL_COLLECTIVE_US * 1e-6)
    return t


_MESH_ENGINE_KEYS = _REAL_ENGINE_KEYS + (
    "n_shards", "shard_identities_ok", "shards")


def bench_mesh(arch: str, batch: int, n_requests: int, k: int, shapes,
               prompt_chunk: int = 1,
               out_path: str = "BENCH_serve.json"):
    """Mesh-sharded serving sweep over ``--mesh-shapes DxM`` shapes.

    Data parallelism serves MORE traffic, it does not shrink a fixed
    workload: shape dxm replays d interleaved copies of the base
    arrival trace (weak scaling -- identical offered load per data
    shard, so the speedup measures the engine rather than
    trace-sampling noise) on a d-times-wider slot pool (per-shard
    batch stays ``batch``).  The structural decode tokens/s is
    computed from the REAL replay's round counters, so scheduling
    imbalance shows up honestly; it should scale ~d under pure DP.  Tensor parallelism attacks per-round latency in
    the weight-bound (full-config) regime instead: each chip streams
    1/m of the shardable weight bytes, paying the per-layer psums.

    Pure-DP (m=1) greedy streams are asserted BIT-IDENTICAL to a
    single-device replay of the same scaled trace; TP streams are
    recorded as ``streams_match`` (argmax-equivalent contract, exact on
    this workload -- tests/test_mesh_serving.py holds the logits-level
    guarantee).  Merges a ``mesh`` section into BENCH_serve.json.
    """
    cfg = archs.smoke(arch)
    full = archs.get(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rt = NOMINAL_ROUNDTRIP_US * 1e-6
    plans = [serve_mesh.MeshPlan.parse(s) for s in shapes]
    need = max(p.size for p in plans)
    if len(jax.devices()) < need:
        raise SystemExit(
            f"mesh sweep needs {need} devices but jax sees "
            f"{len(jax.devices())}: pass --mesh-shapes on the command "
            f"line (the bench forces virtual CPU devices first) or "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={need}")
    header(f"mesh-sharded serving {arch}: shapes "
           f"{[str(p) for p in plans]}, per-shard batch {batch}, "
           f"{n_requests} reqs per data shard, K={k}, C={prompt_chunk}, "
           f"backend={jax.default_backend()}")

    results = {}
    ref_outs = {}           # data size -> single-device streams
    base_trace = make_trace(n_requests, batch)
    for plan in plans:
        d, m = plan.data, plan.model
        total_batch = batch * d
        # weak scaling: d interleaved copies (adjacent duplicates land
        # on different shards via the least-loaded stager)
        trace = [dict(r) for r in base_trace for _ in range(d)]
        if d not in ref_outs:
            _, ref_outs[d] = replay_real_engine(
                cfg, params, trace, total_batch, k,
                prompt_chunk=prompt_chunk)
        snap, outs = replay_real_engine(
            cfg, params, trace, total_batch, k,
            prompt_chunk=prompt_chunk,
            mesh=None if plan.size == 1 else plan)
        match = outs == ref_outs[d]
        if m == 1 and not match:
            raise SystemExit(
                f"pure-DP greedy streams diverged from single device at "
                f"mesh {plan} -- DP must be bit-exact")
        t_small = mesh_t_step(cfg, m, total_batch // d)
        t_full = mesh_t_step(full, m, total_batch // d)
        tps_small = structural_decode_tps_from_counters(snap, t_small, rt)
        tps_full = structural_decode_tps_from_counters(snap, t_full, rt)
        results[str(plan)] = {
            "data": d, "model": m,
            "total_batch": total_batch,
            "n_requests": n_requests * d,
            "streams_match_single_device": match,
            "t_step_us": t_small * 1e6,
            "t_step_us_full_config": t_full * 1e6,
            "structural_decode_tokens_per_s": tps_small,
            "structural_decode_tokens_per_s_full_config": tps_full,
            "real_engine": {key: snap[key] for key in _MESH_ENGINE_KEYS},
        }
        row(f"serve_mesh_{plan}",
            snap["decode_time_s"] * 1e6 / max(snap["decode_calls"], 1),
            f"{tps_small:.0f} tok/s structural;"
            f"{tps_full:.0f} full-config;"
            f"waste {snap['wasted_slot_fraction']:.1%};"
            f"streams {'exact' if match else 'argmax-equiv'}")

    mesh_section = {
        "arch": arch,
        "per_shard_batch": batch,
        "n_requests_per_shard": n_requests,
        "decode_block": k,
        "prompt_chunk": prompt_chunk,
        "nominal_ici_gbps": NOMINAL_ICI_GBPS,
        "nominal_collective_us": NOMINAL_COLLECTIVE_US,
        "shapes": results,
    }
    base = results.get("1x1")
    if base is not None:
        for name, key in (("dp_speedup_2x1", "2x1"),
                          ("dp_speedup_4x1", "4x1")):
            if key in results:
                mesh_section[name] = (
                    results[key]["structural_decode_tokens_per_s"]
                    / base["structural_decode_tokens_per_s"])
                row(f"serve_mesh_{name}", 0.0,
                    f"{mesh_section[name]:.2f}x structural vs 1x1")
        if "1x2" in results:
            mesh_section["tp_speedup_1x2_full_config"] = (
                results["1x2"][
                    "structural_decode_tokens_per_s_full_config"]
                / base["structural_decode_tokens_per_s_full_config"])
            row("serve_mesh_tp_1x2", 0.0,
                f"{mesh_section['tp_speedup_1x2_full_config']:.2f}x "
                f"full-config weight-bound vs 1x1")

    merged = {}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                merged = json.load(f)
        except ValueError:
            merged = {}
    # vs the packed-prefill trajectory: the PR 5 headline was the best-C
    # full-config real row -- record how the TP row compares when both
    # numbers are in the file
    chunks = merged.get("prompt_chunks")
    if chunks and "1x2" in results:
        pr5_best = max(
            r["real_structural_decode_tokens_per_s_full_config"]
            for r in chunks.values())
        mesh_section["tp_1x2_full_config_vs_best_packed"] = (
            results["1x2"]["structural_decode_tokens_per_s_full_config"]
            / pr5_best)
        row("serve_mesh_tp_vs_packed", 0.0,
            f"{mesh_section['tp_1x2_full_config_vs_best_packed']:.2f}x "
            f"vs best packed-prefill full-config row")
    merged["mesh"] = mesh_section
    dump_json(out_path, merged)
    return mesh_section


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mingru-lm")
    ap.add_argument("--batches", type=int, nargs="*", default=[1, 2, 4, 8])
    # scenario-dependent defaults (filled in after parsing, so explicit
    # flags are honoured by every scenario including --mixed/--tiny)
    ap.add_argument("--n-requests", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--out", default=None)
    ap.add_argument("--decode", action="store_true",
                    help="run the decode-block bench instead of the "
                         "v1-vs-v2 engine sweep (writes BENCH_decode.json)")
    ap.add_argument("--mixed", action="store_true",
                    help="arrival-trace scenario: per-phase baseline vs "
                         "superstep engine (writes BENCH_serve.json)")
    ap.add_argument("--decode-blocks", type=int, nargs="*", default=None,
                    help="decode block sizes K; 1 is the per-token "
                         "baseline row (--mixed uses only the largest)")
    ap.add_argument("--prompt-chunks", type=int, nargs="*", default=None,
                    help="--mixed: prompt-packing chunk sizes C to sweep "
                         "(1 is always included as the unpacked baseline "
                         "row; default 1 4 16, tiny 1 4)")
    ap.add_argument("--speculative", action="store_true",
                    help="with --mixed: also replay the trace under "
                         "n-gram speculative decoding over the (C, S) "
                         "grid -- accept rate + ITL + structural "
                         "decode tok/s rows land in BENCH_serve.json "
                         "(implies --mixed)")
    ap.add_argument("--draft-lens", type=int, nargs="*", default=None,
                    help="--speculative: draft lengths S to sweep "
                         "(default 2 4 8, tiny 4)")
    ap.add_argument("--timed", action="store_true",
                    help="block-fused megakernel acceptance lane: replay "
                         "the mixed trace with fuse_block off vs auto, "
                         "assert identical greedy streams, record "
                         "wall-clock AND tier-aware structural decode "
                         "tok/s (dispatch + activation boundary costs); "
                         "merges a 'block_fused' section into "
                         "BENCH_serve.json")
    ap.add_argument("--tune-file", default="auto",
                    help="autotune plan for --timed: 'auto' (default; "
                         "TUNE_<config>.json discovery order), 'none', "
                         "or an explicit path (shape-checked)")
    ap.add_argument("--faults", action="store_true",
                    help="chaos + overload scenario: replay the mixed "
                         "trace under a seeded fault-rate sweep (NaN "
                         "corruption, dropped uploads, stragglers) plus "
                         "a 2x-arrival overload against a bounded "
                         "queue; merges a 'robustness' section into "
                         "BENCH_serve.json")
    ap.add_argument("--fault-rates", type=float, nargs="*", default=None,
                    help="--faults: per-opportunity fault rates to sweep "
                         "(default 0.0 0.002 0.01, tiny 0.0 0.01)")
    ap.add_argument("--crash", action="store_true",
                    help="crash-recovery lane: kill a journaling engine "
                         "at each --kill-rounds round, restore from "
                         "snapshot + journal replay, assert 100%% "
                         "completion with streams bit-identical to an "
                         "uninterrupted run; plus a 2x1-mesh DP-shard "
                         "failover leg.  Merges 'recovery' + "
                         "'shard_failover' into BENCH_serve.json's "
                         "robustness section")
    ap.add_argument("--kill-rounds", type=int, nargs="*", default=None,
                    help="--crash: device rounds to kill at (default: "
                         "1/4, 1/2 and 3/4 of the reference run)")
    ap.add_argument("--snapshot-every", type=int, default=None,
                    help="--crash: snapshot cadence in device rounds "
                         "(default 3*K, so kills land mid-cadence and "
                         "the restore replays a real journal tail)")
    ap.add_argument("--mesh-shapes", nargs="*", default=None,
                    metavar="DxM",
                    help="mesh-sharded serving sweep (e.g. 1x1 2x1 4x1 "
                         "1x2 2x2): data axis serves d-times the "
                         "traffic on d slot shards, model axis shards "
                         "d_hidden.  Forces virtual CPU devices "
                         "pre-import; merges a 'mesh' section into "
                         "BENCH_serve.json.  Combines with --mixed "
                         "(runs after the chunk sweep) or stands alone")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: tiny workload -> BENCH_*.tiny.json "
                         "(never clobbers the tracked trajectory)")
    args = ap.parse_args(argv)
    # virtual CPU devices must be forced before the first device query:
    # the mesh sweep needs its largest shape, the crash lane's
    # shard-failover leg serves on a 2x1 mesh
    if args.mesh_shapes:
        serve_mesh.ensure_host_devices(max(
            serve_mesh.MeshPlan.parse(s).size for s in args.mesh_shapes))
    elif args.crash:
        serve_mesh.ensure_host_devices(2)
    if args.timed:
        n_req = args.n_requests or (24 if args.tiny else 96)
        k = max(args.decode_blocks) if args.decode_blocks else 8
        c = max(args.prompt_chunks) if args.prompt_chunks else (
            4 if args.tiny else 16)
        if args.tiny:
            args.batches = [min(4, max(args.batches))]
        out = args.out or ("BENCH_serve.tiny.json" if args.tiny
                           else "BENCH_serve.json")
        tune = None if args.tune_file == "none" else args.tune_file
        bench_timed(args.arch, max(args.batches), n_req, k,
                    prompt_chunk=c, out_path=out, tune=tune)
        return
    if args.faults:
        n_req = args.n_requests or (24 if args.tiny else 96)
        k = max(args.decode_blocks) if args.decode_blocks else 8
        rates = args.fault_rates if args.fault_rates is not None else (
            [0.0, 0.01] if args.tiny else [0.0, 0.002, 0.01])
        if args.tiny:
            args.batches = [min(4, max(args.batches))]
        out = args.out or ("BENCH_serve.tiny.json" if args.tiny
                           else "BENCH_serve.json")
        bench_robustness(args.arch, max(args.batches), n_req, k,
                         fault_rates=rates, out_path=out)
        return
    if args.crash:
        n_req = args.n_requests or (24 if args.tiny else 96)
        k = max(args.decode_blocks) if args.decode_blocks else 8
        if args.tiny:
            args.batches = [min(4, max(args.batches))]
        out = args.out or ("BENCH_serve.tiny.json" if args.tiny
                           else "BENCH_serve.json")
        bench_crash(args.arch, max(args.batches), n_req, k,
                    kill_rounds=args.kill_rounds,
                    snapshot_every=args.snapshot_every or 3 * k,
                    out_path=out)
        return
    if args.mixed or args.speculative or args.mesh_shapes:
        n_req = args.n_requests or (32 if args.tiny else 96)
        k = max(args.decode_blocks) if args.decode_blocks else 8
        chunks = args.prompt_chunks or ([1, 4] if args.tiny else [1, 4, 16])
        drafts = () if not args.speculative else (
            args.draft_lens or ([4] if args.tiny else [2, 4, 8]))
        if args.tiny:
            args.batches = [min(4, max(args.batches))]
        out = args.out or ("BENCH_serve.tiny.json" if args.tiny
                           else "BENCH_serve.json")
        if args.mixed or args.speculative:
            bench_mixed(args.arch, max(args.batches), n_req, k,
                        chunks=chunks, out_path=out, spec_drafts=drafts)
        if args.mesh_shapes:
            # the mesh sweep scales traffic per data shard: keep the
            # per-shard workload modest so the 4x rows stay tractable
            mesh_req = args.n_requests or (8 if args.tiny else 24)
            bench_mesh(args.arch, max(args.batches), mesh_req, k,
                       args.mesh_shapes, prompt_chunk=max(chunks),
                       out_path=out)
        return
    if args.decode:
        n_req = args.n_requests or (4 if args.tiny else 16)
        max_new = args.max_new or (8 if args.tiny else 24)
        blocks = args.decode_blocks or ([1, 4] if args.tiny else [1, 4, 8])
        out = args.out or ("BENCH_decode.tiny.json" if args.tiny
                           else "BENCH_decode.json")
        bench_decode(args.arch, max(args.batches), n_req, max_new, blocks,
                     out_path=out)
        return
    bench(args.arch, args.batches, args.n_requests or 16,
          args.max_new or 24, args.temperature,
          out_path=args.out or "BENCH_engine.json")


if __name__ == "__main__":
    main()
