"""Serving engine v5: continuous batching as ONE on-device superstep,
with a fault-tolerance layer (admission control, deadlines, cancellation,
NaN-quarantine, deterministic chaos injection).

The paper's serving advantage over Transformers is the O(1) recurrent
state (Were RNNs All We Needed?, section 4.1): a minGRU/minLSTM slot is a
fixed-size hidden vector, so swapping a finished request for a queued one
is a row write, not a KV-cache reshuffle.  This engine exploits that all
the way down: admission, prefill, decode, sampling and retirement ALL
happen inside one jitted device loop (``lm.superstep``), and the host's
only jobs are queueing, staging and draining.

Per engine ``step()``:

  * the host sweeps deadlines (queued, staged and in-flight requests can
    all time out; in-flight kills retire the slot between supersteps and
    preserve partial output), then stages queued requests into per-slot
    **staging buffers** (device-resident ``s_*`` arrays in the slot
    state -- prompt tokens, length cap, stop token, sampling controls,
    request id);
  * ONE ``lm.superstep(params, cfg, state, K)`` call lax.scans K rounds
    of *token select -> fused block step -> sample-or-teacher-force ->
    EOS/retire -> re-admission from staging*.  Prefilling rows consume
    their next prompt token (teacher forcing) and decoding rows feed
    back their last sample, through the SAME ``lm.decode_step`` -- and
    therefore the same fused Pallas cell kernel (``kernels/decode_step``
    under the default ``scan_strategy="auto"``) -- in the same round.
    With ``prompt_chunk=C > 1`` (recurrent-state archs only) a
    prefilling row instead consumes up to C prompt tokens per round via
    the masked varlen chunk kernels (``lm.decode_chunk``);
  * the host drains the returned ``(B, K)`` token + request-id buffers
    (the rid plane demuxes rows that served two requests in one call),
    retires finished requests, quarantines rows the in-loop numerical
    health guard killed (re-enqueueing their request under a bounded
    retry budget with backoff), and restocks staging.

**Failure model** (see README "Failure model" for the full diagram):

  * ``submit`` returns a request id unconditionally; the *admission
    verdict* (``scheduler.ADMITTED`` / ``REJECTED_QUEUE_FULL`` /
    ``SHED_UNMEETABLE_DEADLINE``) lands on ``request.verdict``.  A
    rejected or shed request is terminal immediately (status SHED) --
    under a bounded queue the engine sheds load instead of growing
    without bound.
  * Every request ends in exactly one terminal status: COMPLETED,
    CANCELLED (``engine.cancel(rid)``), TIMED_OUT (per-request round
    deadline), FAILED (non-finite state, retry budget exhausted) or
    SHED.  ``stats`` counts each.
  * A row whose activations go non-finite is killed *in-loop* by the
    superstep's health guard (its emission is suppressed, so garbage
    never reaches a stream) and re-armed through the same state-zeroing
    path normal re-admission uses; the host re-enqueues the poisoned
    request with exponential round backoff until ``max_retries``.
  * ``faults`` (a ``serving.faults.FaultInjector``) injects NaN state
    corruption, dropped staging uploads and straggler stalls at named
    points in ``step`` -- deterministic, seeded, fully inert when None.
  * Speculative decoding degrades gracefully: a rolling accept-rate
    floor (``spec_accept_floor``) disables drafting when a hostile
    input stream makes verify rounds pure overhead.
  * With ``recover_dir`` set the engine is crash tolerant: every
    submit/cancel/step goes to a write-ahead journal and the full
    serving state snapshots every ``snapshot_every`` rounds, so
    ``ServingEngine.restore`` on a fresh process resumes with streams
    bit-identical to an uninterrupted run (serving/recovery.py).
  * A scheduled ``shard_crash`` fault kills a whole data shard of the
    slot pool: the engine marks its rows dead, drains the shard's
    staged + in-flight requests onto the survivors through the requeue
    path (no retry budget burned) and serves degraded --
    ``stats.shard_crashes`` / ``stats.failover_requeued`` count it.

With ``speculative`` set (a ``serving.draft`` source -- ``"ngram"``
self-drafting or a tiny draft model), decoding rows propose up to
``draft_len`` tokens per device round and the superstep verifies them in
ONE pass through the same varlen chunk kernels, rolling the O(1)
recurrent state back to the last accepted position with a single gather.
Streams stay bit-identical to the non-speculative engine -- drafts only
change latency, never content.

There is no separate prefill phase, no chunked-prefill interleave and no
phase barrier: a long prompt occupies one row while every other row keeps
decoding.  Dead rows with nothing staged still step (the batch stays
dense, shapes stay static); ``stats.wasted_slot_steps`` counts exactly
those rows, and ``stats`` also tracks per-request time-to-first-token and
inter-token latency.  Greedy engine output is bit-identical to the
single-request ``generate_one`` reference -- which drives the prompt
through the same ``decode_step`` path -- for every cache kind and block
size, under any admission order, mid-superstep arrival and slot reuse
(tests/test_serving.py, tests/test_decode.py, tests/test_faults.py).
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blocks as minrnn_blocks
from repro.distributed import serve_mesh
from repro.models import lm
from repro.serving import draft as draft_lib
from repro.serving import sampling
from repro.serving import tuning
from repro.serving.scheduler import (ADMITTED, REJECTED_QUEUE_FULL,
                                     AdmissionScheduler, EngineStats,
                                     SchedulerConfig, ShardStats)

# ---------------------------------------------------------------------------
# Request lifecycle: QUEUED -> STAGED -> RUNNING -> one terminal status
# (a quarantine retry moves FAILED-candidate requests back to QUEUED).
# ---------------------------------------------------------------------------
QUEUED = "QUEUED"
STAGED = "STAGED"
RUNNING = "RUNNING"
COMPLETED = "COMPLETED"
CANCELLED = "CANCELLED"
TIMED_OUT = "TIMED_OUT"
FAILED = "FAILED"
SHED = "SHED"
TERMINAL_STATUSES = frozenset(
    {COMPLETED, CANCELLED, TIMED_OUT, FAILED, SHED})


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos: Optional[int] = None
    out: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    done: bool = False
    # robustness: scheduling class, lifecycle and retry bookkeeping
    priority: int = 1             # lower = more urgent (EDF tie-break)
    deadline: Optional[int] = None  # absolute device round, or None
    status: str = QUEUED
    verdict: Optional[str] = None   # admission verdict (scheduler.*)
    retries: int = 0
    not_before: int = 0           # retry-backoff gate (device round)
    # latency bookkeeping (wall clock + device-round clock)
    submitted_s: float = 0.0
    submit_round: int = 0
    first_token_s: float = 0.0
    first_round: int = 0
    admit_seq: int = -1           # staging order (FIFO fairness witness)
    staged_s: float = 0.0         # parked in a staging buffer (_stage)
    armed_s: float = 0.0          # seen armed by the drain (_promote)


class EngineStallError(RuntimeError):
    """``run_to_completion`` exceeded ``max_steps`` with work still
    pending.  ``.report`` carries the queue + per-slot occupancy
    snapshot (``ServingEngine.occupancy_report``) so hangs are
    diagnosable instead of silent."""

    def __init__(self, message: str, report: Dict[str, Any]):
        super().__init__(message)
        self.report = report


# staged request fields mirrored host-side as numpy (uploaded on change;
# the device only *reads* them at arm time and only flips s_valid)
_STAGE_FIELDS = ("s_valid", "s_prompt", "s_prompt_len", "s_rid",
                 "s_remaining", "s_eos", "s_temperature", "s_top_k",
                 "s_top_p")


class ServingEngine:
    def __init__(self, cfg, params, *, max_batch: int = 8,
                 max_len: int = 2048, seed: int = 0,
                 decode_block: Optional[int] = None,
                 prompt_chunk: Optional[int] = None,
                 speculative=None, draft_len: int = 4,
                 draft_params=None,
                 max_queue: int = 0, high_watermark: float = 1.0,
                 low_watermark: float = 0.5, aging_rounds: int = 64,
                 max_retries: int = 1, retry_backoff: int = 8,
                 spec_accept_floor: Optional[float] = None,
                 spec_window: int = 8, spec_cooldown: int = 0,
                 faults=None, mesh=None,
                 fuse_block: Optional[str] = None, tune=None,
                 recover_dir: Optional[str] = None,
                 snapshot_every: int = 8, snapshot_keep: int = 3):
        # autotuned tile plan (serving/tuning.py): ``tune`` is None (no
        # plan -- historical behavior byte for byte), "auto" (TUNE_*.json
        # discovery order), a path, or a plan dict.  The plan supplies
        # kernel tiling (block_dh) and scheduling defaults (decode_block
        # / prompt_chunk) -- explicit constructor arguments always win.
        # ``fuse_block`` ("auto"|"on"|"off") overrides the config knob.
        self.tune_plan = tuning.resolve_plan(cfg, tune)
        if self.tune_plan is not None:
            cfg = tuning.apply_plan(cfg, self.tune_plan)
            if decode_block is None:
                decode_block = self.tune_plan.get("decode_block")
            if prompt_chunk is None:
                prompt_chunk = self.tune_plan.get("prompt_chunk")
        if fuse_block is not None and fuse_block != cfg.fuse_block:
            cfg = cfg.replace(fuse_block=fuse_block)
        decode_block = 1 if decode_block is None else decode_block
        prompt_chunk = 1 if prompt_chunk is None else prompt_chunk
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.seed = int(seed)
        # K = device rounds per host round-trip (lm.superstep scan length)
        self.decode_block = max(1, int(decode_block))
        # C = prompt tokens consumed per round by a prefilling row: the
        # superstep's packed-prefill branch (weight-bound regime win --
        # one weight stream amortises over C prompt tokens).  Without
        # speculation emission stays <= 1 token per slot-round, so the
        # (B, K) drain buffers and greedy streams are identical across C.
        self.prompt_chunk = max(1, int(prompt_chunk))
        if self.prompt_chunk > 1 and not lm.supports_prompt_packing(cfg):
            raise ValueError(
                f"prompt_chunk={self.prompt_chunk} requires a recurrent-"
                f"state arch (block_kind='minrnn'); "
                f"{cfg.name} has block_kind={cfg.block_kind!r}")
        # speculative decoding: a draft source name ("ngram") or instance
        # (serving.draft).  Decoding rows then emit up to draft_len + 1
        # tokens per device round -- the drain buffers grow a plane --
        # with streams still bit-identical to the non-speculative engine.
        if isinstance(speculative, str):
            speculative = draft_lib.make(speculative, draft_len)
        self.draft = speculative
        self.draft_params = draft_params if draft_params is not None \
            else getattr(speculative, "params", None)
        if self.draft is not None and not lm.supports_prompt_packing(cfg):
            raise ValueError(
                f"speculative decoding requires a recurrent-state arch "
                f"(block_kind='minrnn'); "
                f"{cfg.name} has block_kind={cfg.block_kind!r}")
        # mesh-sharded serving (``--mesh dxm``): the slot pool splits
        # into ``data`` contiguous row groups (shard s owns rows
        # [s*B/d, (s+1)*B/d)) and ``model`` shards d_hidden for the gate
        # projections (see distributed/serve_mesh.py).  None keeps the
        # original single-device path byte for byte.
        self.mesh_plan = serve_mesh.MeshPlan.parse(mesh)
        self.mesh = None
        if self.mesh_plan is not None:
            plan = self.mesh_plan
            if max_batch % plan.data != 0:
                raise ValueError(
                    f"max_batch ({max_batch}) must divide over the data "
                    f"axis ({plan.data}): each shard owns "
                    f"max_batch/data contiguous slot rows")
            if plan.model > 1:
                if cfg.block_kind != "minrnn":
                    raise ValueError(
                        f"tensor-parallel serving (model axis "
                        f"{plan.model} > 1) shards d_hidden and requires "
                        f"block_kind='minrnn'; {cfg.name} has "
                        f"block_kind={cfg.block_kind!r}")
                if not serve_mesh._tp_shards_hidden(cfg, plan):
                    raise ValueError(
                        f"d_hidden of {cfg.name} does not divide over "
                        f"the model axis ({plan.model}); pick a model "
                        f"size that divides d_hidden")
            self.mesh = plan.build()
        self.dp = self.mesh_plan.data if self.mesh_plan is not None else 1
        self._rows_per_shard = max_batch // self.dp
        self.state = lm.init_slot_state(cfg, max_batch, max_len, seed=seed,
                                        draft=self.draft)
        if self.mesh is not None:
            # pin the NamedShardings up front so the superstep's shard_map
            # consumes in-place instead of resharding every call
            self.state = jax.device_put(
                self.state, serve_mesh.slot_state_shardings(
                    cfg, self.state, self.mesh_plan, self.mesh))
            self.params = jax.device_put(
                params, serve_mesh.serve_params_shardings(
                    params, cfg, self.mesh_plan, self.mesh))
            if self.draft_params is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                self.draft_params = jax.device_put(
                    self.draft_params,
                    NamedSharding(self.mesh, PartitionSpec()))

        self.scheduler = AdmissionScheduler(SchedulerConfig(
            max_batch=max_batch, max_queue=max_queue,
            high_watermark=high_watermark, low_watermark=low_watermark,
            aging_rounds=aging_rounds))
        self.stats = EngineStats(
            prompt_chunk=self.prompt_chunk,
            shards=[ShardStats() for _ in range(self.dp)])
        # fault tolerance: quarantine retry budget + backoff (rounds),
        # chaos injector (None = fully inert), speculative degradation
        self.max_retries = max(0, int(max_retries))
        self.retry_backoff = max(0, int(retry_backoff))
        self.faults = faults
        self.spec_accept_floor = spec_accept_floor
        self.spec_window = max(1, int(spec_window))
        self.spec_cooldown = max(0, int(spec_cooldown))
        self._spec_active = True
        self._spec_hist: List = []      # (proposed, accepted) per call
        self._spec_off_calls = 0
        # DP-shard failover: data shards whose slot rows a scheduled
        # shard_crash killed.  Dead rows never stage again (they keep
        # stepping as wasted_slot_steps so the slot-step identity holds
        # per shard); their requests drain onto the survivors.
        self.dead_shards: set = set()
        self._next_rid = 0
        # host mirrors of slot occupancy: the request currently armed in
        # each row, and the request parked in each row's staging buffer
        self.current: List[Optional[Request]] = [None] * max_batch
        self.staged: List[Optional[Request]] = [None] * max_batch
        self.finished: Dict[int, Request] = {}
        self.requests: Dict[int, Request] = {}   # rid -> every request

        # numpy mirrors of the device staging arrays (authoritative on
        # the host side: the device only consumes them, flipping s_valid;
        # the mirror is re-synced from the device after every superstep)
        self._smirror = {k: np.asarray(self.state[k]) for k in _STAGE_FIELDS}
        self._smirror = {k: v.copy() for k, v in self._smirror.items()}
        self._dirty_slots: List[int] = []
        # device-progress mirrors (synced after every superstep): how far
        # each row's prompt has actually been consumed, and which request
        # the device thinks the row is running -- the staging ETA reads
        # these instead of assuming the whole prompt is still pending
        self._prompt_pos = np.zeros((max_batch,), np.int32)
        self._rid_dev = np.full((max_batch,), -1, np.int32)

        # one compiled superstep program per (block size, drafting on)
        self._superstep_fns: Dict[Any, Any] = {}

        # crash recovery (serving/recovery.py): with ``recover_dir`` set
        # the engine journals every submit/cancel/step to a write-ahead
        # log and snapshots its full serving state every
        # ``snapshot_every`` device rounds, so ``ServingEngine.restore``
        # on a fresh process resumes bit-identically.  Constructing with
        # recover_dir starts a NEW journal epoch (truncating any prior
        # one) -- resuming goes through ``restore``, never through a
        # fresh construction.  None keeps the engine journal-free.
        self.snapshot_every = max(1, int(snapshot_every))
        self.snapshot_keep = max(1, int(snapshot_keep))
        self.recover_dir = recover_dir
        self._last_snapshot_round = 0
        self.journal = None
        self.recovery_report: Optional[Dict[str, Any]] = None
        if recover_dir is not None:
            from repro.serving import recovery
            os.makedirs(recover_dir, exist_ok=True)
            self.journal = recovery.Journal.create(
                os.path.join(recover_dir, recovery.JOURNAL_NAME),
                recovery.engine_header(self))

    # ------------------------------------------------------------------
    # Submission + admission control
    # ------------------------------------------------------------------
    @property
    def kernel_tier(self) -> str:
        """Which decode kernel tier serves this engine: "block-fused"
        (whole block per pallas_call, kernels/block_step), "cell-fused"
        (cell-only kernel) or "unfused".  Tensor-parallel serving shards
        the row-parallel projections, whose psum must stay outside the
        kernel, so TP meshes report the cell tier.  Surfaced on the
        launch/example stats lines."""
        if self.cfg.block_kind != "minrnn":
            return "unfused"
        tier = minrnn_blocks.fuse_block_tier(lm._minrnn_block_cfg(self.cfg))
        if tier == "block-fused" and self.mesh_plan is not None \
                and self.mesh_plan.model > 1:
            return "cell-fused"
        return tier

    def _service_rounds(self, req: Request) -> int:
        """Rounds a request occupies a row end to end: packed prefill
        plus decode, minus the first-token/last-prefill overlap."""
        return -(-len(req.prompt) // self.prompt_chunk) + req.max_new - 1

    def _est_finish_round(self, req: Request) -> int:
        """Capacity estimate: the absolute device round by which ``req``
        could plausibly finish, built from the ``_row_eta`` rounds-to-
        free machinery.  Queued + staged work ahead of it is placed
        greedily on the earliest-freeing rows; this is an estimate (EDF
        reordering and speculative multi-emit shift it), used only to
        shed requests whose deadline even the estimate cannot meet.
        Rows on a crashed data shard never free up and are excluded --
        a dead row's eta of 0 would otherwise absorb the whole queue and
        the shedder would admit work the survivors cannot serve."""
        live = [s for s in range(self.max_batch)
                if s // self._rows_per_shard not in self.dead_shards]
        if not live:
            return 1 << 62      # total outage: nothing can ever finish
        etas = [self._row_eta(s) for s in live]
        for i, slot in enumerate(live):
            parked = self.staged[slot]
            if parked is not None:
                etas[i] += self._service_rounds(parked)
        heapq.heapify(etas)
        for ahead in self.scheduler.waiting:
            heapq.heappush(etas,
                           heapq.heappop(etas) + self._service_rounds(ahead))
        return (self.stats.decode_steps + min(etas)
                + self._service_rounds(req))

    def submit(self, prompt: List[int], max_new: int = 32,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               eos: Optional[int] = None, priority: int = 1,
               deadline: Optional[int] = None) -> int:
        """Submit a request; always returns its rid.  The admission
        verdict lands on ``engine.requests[rid].verdict``: a request the
        bounded queue rejects or the deadline shedder refuses is
        terminal immediately with status SHED (empty output).

        ``priority`` is the scheduling class (lower = more urgent);
        ``deadline`` is a device-round budget relative to submission --
        the request is TIMED_OUT (partial output preserved) once the
        round clock passes ``submit_round + deadline``, whether it is
        queued, staged or in flight.  Deadline enforcement happens at
        host round-trip boundaries, so it quantises to ``decode_block``.
        """
        if not prompt:
            raise ValueError("empty prompt")
        # a request consumes len(prompt) + max_new - 1 cache positions:
        # the first output token is sampled at the last prompt position,
        # and the final output token is emitted without being fed back
        if len(prompt) + max_new - 1 > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) needs "
                f"{len(prompt) + max_new - 1} cache positions, exceeding "
                f"engine max_len ({self.max_len})")
        sampling.validate_controls(temperature, top_k, top_p)
        if deadline is not None and deadline <= 0:
            raise ValueError(f"deadline must be a positive device-round "
                             f"budget, got {deadline!r}")
        rid = self._next_rid
        if self.journal is not None:
            # write-ahead: the record is durable BEFORE the engine
            # mutates, and can promise the rid because rid assignment is
            # deterministic
            self.journal.record_submit({
                "rid": rid, "round": self.stats.decode_steps,
                "prompt": [int(t) for t in prompt],
                "max_new": int(max_new),
                "temperature": float(temperature), "top_k": int(top_k),
                "top_p": float(top_p),
                "eos": None if eos is None else int(eos),
                "priority": int(priority),
                "deadline": None if deadline is None else int(deadline)})
        self._next_rid += 1
        req = Request(rid, [int(t) for t in prompt], max_new, temperature,
                      top_k, top_p, eos, priority=priority)
        req.submitted_s = time.perf_counter()
        req.submit_round = self.stats.decode_steps
        if deadline is not None:
            req.deadline = req.submit_round + int(deadline)
        self.requests[rid] = req
        self.stats.submitted += 1
        est = self._est_finish_round(req) if req.deadline is not None \
            else None
        req.verdict = self.scheduler.submit(
            req, now_round=req.submit_round, est_finish=est)
        if req.verdict == ADMITTED:
            req.status = QUEUED
            self.stats.observe_queue(len(self.scheduler))
        else:
            self._retire(req, SHED)
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it is in the lifecycle.  Queued and
        staged requests retire with empty output; an in-flight request
        has its slot killed between supersteps and keeps the tokens
        already drained (partial output).  Returns True if the request
        transitioned to CANCELLED, False if it is unknown or already
        terminal."""
        if self.journal is not None:
            # journaled even when a no-op: replay re-executes the same
            # call and reaches the same verdict deterministically
            self.journal.record_cancel({"rid": int(rid),
                                        "round": self.stats.decode_steps})
        req = self.requests.get(rid)
        if req is None or req.done:
            return False
        if self.scheduler.remove(req):
            self._retire(req, CANCELLED)
            return True
        if req.slot is not None and self.staged[req.slot] is req:
            self._unstage(req.slot)
            self._retire(req, CANCELLED)
            return True
        if req.slot is not None and self.current[req.slot] is req:
            self._kill_inflight(req, CANCELLED)
            return True
        return False

    # ------------------------------------------------------------------
    # Staging (host side of admission; the device does the arming)
    # ------------------------------------------------------------------
    def _row_eta(self, slot: int) -> int:
        """Upper bound on device rounds until this row frees up (0 for an
        idle row).  Drives staging placement: within one staging round,
        earlier-submitted requests park behind sooner-to-free rows.
        Prompt consumption is packed ``prompt_chunk`` tokens per round,
        so the prefill term is ``ceil(prompt_left / C)`` rounds over the
        prompt tokens the device has NOT yet consumed -- the synced
        ``prompt_pos`` mirror, not the full prompt length, which would
        overestimate a mid-prefill row by up to its whole prompt.  Under
        speculative decoding the decode term stays an upper bound (every
        round commits at least one token).  This is greedy per call, not
        a global ordering guarantee -- arrivals in a *later* round can
        still land on a row that frees up before an earlier request's
        row does; strict FIFO holds for staging order (``admit_seq``),
        not start order."""
        req = self.current[slot]
        if req is None:
            return 0
        if req.out:
            prompt_left = 0
        else:
            # trust the device mirror only when the row is actually
            # running THIS request (it may still be parked in staging)
            consumed = int(self._prompt_pos[slot]) \
                if int(self._rid_dev[slot]) == req.rid else 0
            prompt_left = max(0, len(req.prompt) - consumed)
        prompt_rounds = -(-prompt_left // self.prompt_chunk)
        return prompt_rounds + req.max_new - len(req.out)

    def _stage(self):
        """Park queued requests into empty staging buffers in scheduler
        order (aged priority, then earliest deadline, then submission --
        strict FIFO in the default single-class/no-deadline config).

        Rows whose current request is finished (or that never held one)
        are preferred so the device arms the request on the very next
        round; the remaining buffers are lookahead -- the request arms
        the moment its row dies, mid-superstep, with zero idle rounds.
        Busy rows are filled in order of estimated rounds-to-free
        (``_row_eta``), keeping staging placement aligned with
        scheduler order.

        Under a data-parallel mesh every slot row belongs to exactly one
        shard, so admission is also a *placement* decision: requests go
        to the least-loaded shard first (load = summed ``_row_eta`` over
        the shard's rows plus the service rounds of its parked staging),
        with rounds-to-free then row index breaking ties.  A shard whose
        rows all run long prompts stops attracting new work until the
        others catch up.  At ``data=1`` the shard load is one constant
        and this reduces exactly to the pre-mesh ``(eta, row)`` order.
        """
        empty = [i for i in range(self.max_batch)
                 if self.staged[i] is None
                 and i // self._rows_per_shard not in self.dead_shards]
        now = self.stats.decode_steps
        group = self.scheduler.take(len(empty), now_round=now)
        if not group and self.scheduler.waiting \
                and not any(self.current) and not any(self.staged):
            # every queued request sits in retry backoff but the machine
            # is idle: the round clock only advances while work runs, so
            # honouring the backoff would deadlock.  Backoff exists to
            # let a transient fault clear while OTHER work runs.
            group = self.scheduler.take(len(empty), now_round=now,
                                        ignore_backoff=True)
        if not group:
            return
        load = [0] * self.dp
        for i in range(self.max_batch):
            load[i // self._rows_per_shard] += self._row_eta(i)
        for i, parked in enumerate(self.staged):
            if parked is not None:
                load[i // self._rows_per_shard] += \
                    self._service_rounds(parked)
        m = self._smirror
        now_s = time.perf_counter()
        for req in group:
            empty.sort(key=lambda i: (load[i // self._rows_per_shard],
                                      self._row_eta(i), i))
            slot = empty.pop(0)
            load[slot // self._rows_per_shard] += self._service_rounds(req)
            req.slot = slot
            req.status = STAGED
            req.staged_s = now_s
            req.admit_seq = self.stats.admitted
            self.staged[slot] = req
            m["s_prompt"][slot, :] = 0
            m["s_prompt"][slot, :len(req.prompt)] = req.prompt
            m["s_prompt_len"][slot] = len(req.prompt)
            m["s_rid"][slot] = req.rid
            m["s_remaining"][slot] = req.max_new
            m["s_eos"][slot] = -1 if req.eos is None else req.eos
            m["s_temperature"][slot] = req.temperature
            m["s_top_k"][slot] = req.top_k
            m["s_top_p"][slot] = req.top_p
            m["s_valid"][slot] = True
            self.stats.admitted += 1
            self._dirty_slots.append(slot)

    def _unstage(self, slot: int):
        """Withdraw a parked request from its staging buffer (cancel /
        deadline sweep) before the device can arm it."""
        req = self.staged[slot]
        self.staged[slot] = None
        req.slot = None
        self._smirror["s_valid"][slot] = False
        self._dirty_slots.append(slot)

    def _upload_staging(self):
        """Push newly staged rows to the device.  The (B,) control
        vectors are re-uploaded whole (a few words); the (B, max_len)
        prompt matrix -- the only leaf whose full upload would scale
        with max_len -- is scattered row-wise for just the dirty slots.

        The ``drop_upload`` chaos injection point intercepts here: a
        dropped slot's prompt row is NOT uploaded and its ``s_valid`` is
        masked False for this call (the device must never arm a row
        whose prompt row it does not have), and the slot stays dirty so
        the next call retries -- the request arms one superstep late.
        Returns the number of prompt rows scattered.
        """
        if not self._dirty_slots:
            return 0
        rows = sorted(set(self._dirty_slots))
        dropped: List[int] = []
        if self.faults is not None:
            rows, dropped = self.faults.drop_upload(
                self.stats.decode_calls, rows)
        if rows:
            r = jnp.asarray(rows)
            self.state["s_prompt"] = self.state["s_prompt"].at[r].set(
                jnp.asarray(self._smirror["s_prompt"][np.asarray(r)]))
        s_valid = self._smirror["s_valid"]
        if dropped:
            s_valid = s_valid.copy()
            s_valid[dropped] = False
        for k in _STAGE_FIELDS:
            if k == "s_prompt":
                continue
            src = s_valid if k == "s_valid" else self._smirror[k]
            self.state[k] = jnp.asarray(src)
        self._dirty_slots = list(dropped)
        return len(rows)

    # ------------------------------------------------------------------
    # The superstep
    # ------------------------------------------------------------------
    def _superstep_fn(self, n: int):
        key = (n, self._spec_active and self.draft is not None)
        fn = self._superstep_fns.get(key)
        if fn is None:
            cfg, chunk = self.cfg, self.prompt_chunk
            draft = self.draft if key[1] else None
            if self.mesh is not None:
                fn = serve_mesh.make_superstep(
                    cfg, self.mesh_plan, self.mesh, self.state,
                    self.params, n, prompt_chunk=chunk, draft=draft)
            else:
                fn = jax.jit(lambda p, dp, s: lm.superstep(
                    p, cfg, s, n, prompt_chunk=chunk, draft=draft,
                    draft_params=dp))
            self._superstep_fns[key] = fn
        return fn

    def _promote(self, slot: int, now: float) -> Request:
        """The device armed this row's staged request: update mirrors.
        ``now`` is the drain's clock, so ``armed_s`` resolves to one
        engine call; the ``engine.arm`` event carries the request's wait
        in the queue and parked in staging."""
        prev = self.current[slot]
        assert prev is None or prev.done, \
            "device armed a row whose request the host still thinks is live"
        req = self.staged[slot]
        assert req is not None
        self.current[slot] = req
        self.staged[slot] = None
        req.status = RUNNING
        req.armed_s = now
        self.stats.mark("arm", rid=req.rid,
                        queued_us=1e6 * (req.staged_s - req.submitted_s),
                        parked_us=1e6 * (now - req.staged_s))
        return req

    def _retire(self, req: Request, status: str):
        """Move a request to a terminal status and count it."""
        req.done = True
        req.status = status
        if req.slot is not None:
            if self.current[req.slot] is req:
                self.current[req.slot] = None
            req.slot = None
        self.finished[req.rid] = req
        if status == COMPLETED:
            self.stats.completed += 1
        elif status == CANCELLED:
            self.stats.cancelled += 1
        elif status == TIMED_OUT:
            self.stats.timed_out += 1
        elif status == FAILED:
            self.stats.failed += 1
        elif status == SHED:
            if req.verdict == REJECTED_QUEUE_FULL:
                self.stats.rejected += 1
            else:
                self.stats.shed += 1

    def _finish(self, req: Request, now: float, last_round: int):
        self._retire(req, COMPLETED)
        self.stats.record_completion(len(req.out), req.first_round,
                                     last_round, req.first_token_s, now)

    def _kill_inflight(self, req: Request, status: str):
        """Retire an in-flight request between supersteps: its device row
        goes dead (re-armed from staging on the next round like any
        retirement) and the tokens drained so far are preserved."""
        slot = req.slot
        self.state = dict(self.state)
        self.state["alive"] = self.state["alive"].at[slot].set(False)
        self._retire(req, status)

    def _sweep_deadlines(self):
        """Retire every request whose deadline round has passed --
        queued, staged or in flight (the latter keeping partial
        output).  Runs at host round-trip boundaries."""
        now = self.stats.decode_steps
        overdue = [r for r in self.scheduler.waiting
                   if r.deadline is not None and now >= r.deadline]
        for req in overdue:
            self.scheduler.remove(req)
            self._retire(req, TIMED_OUT)
        for slot in range(self.max_batch):
            req = self.staged[slot]
            if req is not None and req.deadline is not None \
                    and now >= req.deadline:
                self._unstage(slot)
                self._retire(req, TIMED_OUT)
            req = self.current[slot]
            if req is not None and req.deadline is not None \
                    and now >= req.deadline:
                self._kill_inflight(req, TIMED_OUT)

    def _corrupt_slots(self, slots: List[int]):
        """Chaos injection: overwrite the recurrent state rows of
        ``slots`` with NaN (the ``corrupt_state`` point).  The in-loop
        health guard detects the poisoned rows on their next round."""
        cache = dict(self.state["cache"])
        rows = jnp.asarray(slots, jnp.int32)
        touched = False
        for name in lm._RECURRENT_CACHE_KEYS:
            leaf = cache.get(name)
            if leaf is not None and jnp.issubdtype(leaf.dtype,
                                                   jnp.floating):
                cache[name] = leaf.at[:, rows].set(jnp.nan)
                touched = True
        if touched:
            self.state = dict(self.state)
            self.state["cache"] = cache

    def _requeue(self, req: Request, round_: int, *, count_retry: bool,
                 backoff: bool) -> bool:
        """Re-enqueue a request whose slot died under it, or retire it
        if it cannot be retried.  The shared tail of quarantine (health-
        guard kill: ``count_retry=True`` -- the row poisoning might be
        the request's input, so it burns retry budget and backs off
        exponentially) and DP-shard failover (``count_retry=False`` --
        an infrastructure death is never the request's fault: no budget
        burned, re-eligible immediately).  Returns True if the request
        went back to QUEUED, False if it retired terminally."""
        if req.deadline is not None and round_ >= req.deadline:
            self._retire(req, TIMED_OUT)
            return False
        if count_retry and req.retries >= self.max_retries:
            self._retire(req, FAILED)
            return False
        verdict = self.scheduler.submit(req, now_round=round_)
        req.verdict = verdict
        if verdict != ADMITTED:
            self._retire(req, FAILED)   # no queue room for the retry
            return False
        if count_retry:
            req.retries += 1
            self.stats.retried += 1
        req.out = []        # the retry restarts the stream from scratch
        req.status = QUEUED
        req.not_before = round_ + (
            self.retry_backoff * (2 ** (req.retries - 1))
            if backoff else 0)
        self.stats.observe_queue(len(self.scheduler))
        return True

    def _crash_shard(self, shard: int, round_: int):
        """DP-shard failover (the ``shard_crash`` injection point fired):
        mark ``shard``'s slot rows permanently dead and drain its parked
        + in-flight requests back through the requeue path onto the
        surviving shards.  The dead rows stay in the dense batch --
        stepping as ``wasted_slot_steps``, so the per-shard slot-step
        identity keeps holding -- but never stage again.  A drained
        request restarts its stream from the prompt on a survivor
        (greedy output is placement-independent, so the re-served stream
        is identical to its no-crash stream); failover does not burn the
        request's retry budget."""
        self.dead_shards.add(shard)
        self.stats.shard_crashes += 1
        rows = serve_mesh.shard_rows(shard, self._rows_per_shard)
        self.state = dict(self.state)
        self.state["alive"] = self.state["alive"].at[
            jnp.asarray(list(rows))].set(False)
        for slot in rows:
            parked = self.staged[slot]
            if parked is not None:
                self._unstage(slot)
                if self._requeue(parked, round_, count_retry=False,
                                 backoff=False):
                    self.stats.failover_requeued += 1
            req = self.current[slot]
            if req is not None and not req.done:
                self.current[slot] = None
                req.slot = None
                if self._requeue(req, round_, count_retry=False,
                                 backoff=False):
                    self.stats.failover_requeued += 1

    def _quarantine(self, slot: int, round_: int, s_valid_np, dirty,
                    now: float):
        """The superstep's health guard killed this row at ``round_``:
        attribute the kill to the occupying request and re-enqueue it
        under the bounded retry budget (exponential round backoff), or
        retire it FAILED once the budget is spent.  The slot itself
        needs no host repair -- the device already marked it dead and
        the next arm re-zeroes its state through the normal re-admission
        path."""
        self.stats.quarantined += 1
        req = self.current[slot]
        if req is None or req.done:
            # the victim armed mid-superstep from staging (it emitted
            # nothing before the kill, so the drain never promoted it)
            if self.staged[slot] is not None and not s_valid_np[slot] \
                    and slot not in dirty:
                req = self._promote(slot, now)
            else:
                return
        self.current[slot] = None
        req.slot = None
        self._requeue(req, round_, count_retry=True, backoff=True)

    def _adapt_speculation(self, counters):
        """Rolling accept-rate floor: when a window of verify rounds
        accepts below ``spec_accept_floor``, drafting is disabled (the
        engine swaps to the plain superstep program) instead of paying a
        draft_len-wide verify pass for ~1 token per round.  With
        ``spec_cooldown > 0`` drafting re-probes after that many calls;
        streams are bit-identical either way -- only latency changes."""
        if self.draft is None or self.spec_accept_floor is None:
            return
        if not self._spec_active:
            self._spec_off_calls += 1
            if self.spec_cooldown and \
                    self._spec_off_calls >= self.spec_cooldown:
                self._spec_active = True
                self._spec_off_calls = 0
                self._spec_hist = []
            return
        proposed = int(counters.get("draft_proposed", 0))
        if proposed <= 0:
            return
        self._spec_hist.append(
            (proposed, int(counters.get("draft_accepted", 0))))
        if len(self._spec_hist) > self.spec_window:
            self._spec_hist.pop(0)
        if len(self._spec_hist) == self.spec_window:
            tp = sum(p for p, _ in self._spec_hist)
            ta = sum(a for _, a in self._spec_hist)
            if ta < self.spec_accept_floor * tp:
                self._spec_active = False
                self._spec_hist = []
                self.stats.spec_disabled += 1

    def step(self, n_tokens: Optional[int] = None) -> int:
        """Sweep deadlines, stage pending requests, then run ONE
        on-device superstep of ``n_tokens`` (default
        ``self.decode_block``) rounds: every slot advances one token per
        round -- its next prompt token while prefilling, a sampled token
        while decoding -- and slots that retire mid-call are re-armed
        from staging in-loop.  Drains emissions, quarantines rows the
        numerical health guard killed, and restocks staging.  Returns
        the number of requests still in flight (armed + staged +
        queued).

        Each host phase is timed into ``stats`` and recorded as an
        ``engine.*`` profiler span (``EngineStats.timed``), nested as
        ``step`` > ``sweep``, ``stage``, ``upload``, ``decode`` >
        (``dispatch``, ``fetch``), ``drain`` > ``fetch``, ``journal``."""
        k = max(1, int(n_tokens)) if n_tokens is not None \
            else self.decode_block
        with self.stats.timed("step"):
            return self._step(k)

    def _step(self, k: int) -> int:
        with self.stats.timed("sweep"):
            self._sweep_deadlines()
            if self.faults is not None:
                for s in self.faults.shard_crash(self.stats.decode_steps, k,
                                                 self.dp):
                    if s not in self.dead_shards:
                        self._crash_shard(s, self.stats.decode_steps)
        with self.stats.timed("stage"):
            self._stage()
        if not any(self.current) and not any(self.staged):
            if self.journal is not None:
                with self.stats.timed("journal"):
                    # every step() call is journaled, no-ops included:
                    # the replay must re-execute the exact call sequence
                    self.journal.record_step({
                        "round": self.stats.decode_steps, "k": k,
                        "noop": True})
                    self._maybe_snapshot()
            return len(self.scheduler)
        with self.stats.timed("upload") as span:
            span.set_metadata(rows=self._upload_staging())
        if self.faults is not None:
            with self.stats.timed("sweep"):
                slots = self.faults.corrupt_state(
                    self.stats.decode_steps, k, self.max_batch)
                if slots:
                    self._corrupt_slots(slots)

        with self.stats.timed("decode"):
            with self.stats.timed("dispatch"):
                toks, rids, self.state, counters = self._superstep_fn(k)(
                    self.params, self.draft_params, self.state)
            with self.stats.timed("fetch"):
                toks_np = np.asarray(toks)
                rids_np = np.asarray(rids)
                s_valid_np = np.asarray(self.state["s_valid"])
                nf_np = np.asarray(counters["nonfinite"])
                self._prompt_pos[:] = np.asarray(self.state["prompt_pos"])
                self._rid_dev[:] = np.asarray(self.state["rid"])
            if self.faults is not None:
                stall = self.faults.straggler(self.stats.decode_calls)
                if stall > 0:
                    time.sleep(stall)
        with self.stats.timed("drain") as span:
            base_round, emits = self._drain(k, toks_np, rids_np, s_valid_np,
                                            nf_np, counters, span)
        if self.journal is not None:
            with self.stats.timed("journal"):
                # the step record lands AFTER the superstep drains:
                # crashing mid-step replays the whole step (the journal
                # never saw it)
                self.journal.record_step({
                    "round": base_round, "k": k, "emits": emits,
                    "digest": self._journal_digest()})
                self._maybe_snapshot()
        return (sum(r is not None for r in self.current)
                + sum(r is not None for r in self.staged)
                + len(self.scheduler))

    def _drain(self, k, toks_np, rids_np, s_valid_np, nf_np, counters,
               span):
        """Host side of one superstep: fold its counters into ``stats``,
        hand each emitted token to its request, promote and retire, and
        re-sync the staging mirror.  A slot whose planes are plain
        decode (``_bulk_ok``) has its tokens appended in one go; every
        other slot is walked round by round, in the same slot order, so
        streams, events and counters do not depend on which way a slot
        went.  Returns the call's first device round and, with a journal
        attached, its ``[rid, token]`` emissions in drain order (else
        None).  The call's counts go onto the ``engine.drain`` ``span``,
        with the rows one packed round computes (``slots``: a data
        shard's, as each shard takes the packed branch on its own), its
        width (``chunk``) and the pool's rows drained in one go
        (``bulk_slots``, of ``slots`` x data shards)."""
        if toks_np.ndim == 2:       # non-speculative: one plane per round
            toks_np = toks_np[:, :, None]
            rids_np = rids_np[:, :, None]
        base_round = self.stats.decode_steps
        self.stats.decode_calls += 1
        self.stats.decode_steps += k
        self.stats.slot_steps += k * self.max_batch
        # under a mesh the counters come back as (data,) per-shard
        # vectors (single device: scalars -- atleast_1d unifies both);
        # the global stats take the cross-shard sum, the per-shard
        # ShardStats take their own component.  They come over in one
        # transfer: read one by one, each is a device-to-host round trip
        # of its own (~0.25 ms on a TPU v5e host)
        with self.stats.timed("fetch"):
            percall = {kk: np.atleast_1d(v) for kk, v in jax.device_get(
                {kk: v for kk, v in counters.items()
                 if kk != "nonfinite"}).items()}
        agg = {kk: int(v.sum()) for kk, v in percall.items()}
        self.stats.prefill_tokens += agg["prefill_steps"]
        self.stats.prefill_rounds += agg["prefill_rounds"]
        self.stats.wasted_slot_steps += agg["wasted_slot_steps"]
        self.stats.nonfinite_decode_rounds += agg["nonfinite_decode_rounds"]
        self.stats.draft_proposed += agg.get("draft_proposed", 0)
        self.stats.draft_accepted += agg.get("draft_accepted", 0)
        self.stats.packed_rounds += agg.get("packed_rounds", 0)
        self.stats.packed_tokens += agg.get("packed_tokens", 0)
        for s, sh in enumerate(self.stats.shards):
            sh.slot_steps += k * self._rows_per_shard
            sh.prefill_rounds += int(percall["prefill_rounds"][s])
            sh.wasted_slot_steps += int(percall["wasted_slot_steps"][s])
            sh.nonfinite_decode_rounds += int(
                percall["nonfinite_decode_rounds"][s])
        self._adapt_speculation(agg)

        now = time.perf_counter()
        dirty = set(self._dirty_slots)
        # each slot's planes in (round, plane) order; an entry with rid
        # >= 0 is an emitted token, and every one of them is drained
        b = self.max_batch
        toks_flat = toks_np.reshape(b, -1)
        rids_flat = rids_np.reshape(b, -1)
        emitted = rids_flat >= 0
        drained_shard = emitted.sum(1).reshape(self.dp, -1).sum(1).tolist()
        drained = sum(drained_shard)
        every = emitted.all(1).tolist()
        toks_rows = toks_flat.tolist()
        rids_rows = rids_flat.tolist()
        rid_lo = np.where(emitted, rids_flat,
                          np.iinfo(rids_flat.dtype).max).min(1).tolist()
        rid_hi = rids_flat.max(1).tolist()
        nf_any = nf_np.any(1).tolist()
        emits = [] if self.journal is not None else None  # [rid, token]
        bulk = 0
        for slot in range(b):
            req = self.current[slot]
            toks = toks_rows[slot] if every[slot] else [
                t for t, r in zip(toks_rows[slot], rids_rows[slot]) if r >= 0]
            if self._bulk_ok(req, toks, rid_lo[slot], rid_hi[slot],
                             nf_any[slot]):
                bulk += 1
                if toks:
                    req.out.extend(toks)
                    if emits is not None:
                        emits.extend([req.rid, t] for t in toks)
            else:
                shard = slot // self._rows_per_shard
                for j in range(k):
                    if nf_np[slot, j]:
                        self._quarantine(slot, base_round + j, s_valid_np,
                                         dirty, now)
                    for c in range(toks_np.shape[2]):
                        rid = int(rids_np[slot, j, c])
                        if rid < 0:
                            continue
                        req = self.current[slot]
                        if req is None or req.rid != rid:
                            req = self._promote(slot, now)  # armed mid-call
                            assert req.rid == rid, (req.rid, rid)
                        t = int(toks_np[slot, j, c])
                        if not req.out:
                            req.first_token_s = now
                            req.first_round = base_round + j
                            self.stats.record_first_token(
                                now - req.submitted_s,
                                base_round + j + 1 - req.submit_round)
                            self.stats.shards[shard].first_tokens += 1
                        req.out.append(t)
                        if emits is not None:
                            emits.append([rid, t])
                        if (req.eos is not None and t == req.eos) or \
                                len(req.out) >= req.max_new:
                            self._finish(req, now, base_round + j)
            # armed without emitting yet (still prefilling at call end);
            # a slot whose upload was dropped is still parked, not armed
            if self.staged[slot] is not None and not s_valid_np[slot] \
                    and slot not in dirty:
                self._promote(slot, now)
        self.stats.drain_bulk_slots += bulk
        self.stats.decode_tokens += drained
        # non_spec_tokens: tokens the non-speculative path contributes --
        # one per emitting slot-round.  The device counts those rounds
        # under speculation; without it every drained token is one.
        spec = "emit_rounds" in percall
        self.stats.non_spec_tokens += agg["emit_rounds"] if spec \
            else drained
        for s, sh in enumerate(self.stats.shards):
            sh.decode_tokens += drained_shard[s]
            sh.non_spec_tokens += int(percall["emit_rounds"][s]) if spec \
                else drained_shard[s]
        # re-sync the staging mirror with what the device consumed --
        # except dirty slots (dropped uploads), whose parked requests
        # the device never saw: their mirror rows stay authoritative
        self._smirror["s_valid"][:] = s_valid_np
        for slot in dirty:
            if self.staged[slot] is not None:
                self._smirror["s_valid"][slot] = True
        span.set_metadata(rounds=k, emitted=drained,
                          prefill_tokens=agg["prefill_steps"],
                          packed_rounds=agg.get("packed_rounds", 0),
                          packed_tokens=agg.get("packed_tokens", 0),
                          slots=self._rows_per_shard,
                          chunk=self.prompt_chunk, bulk_slots=bulk)
        return base_round, emits

    @staticmethod
    def _bulk_ok(req: Optional[Request], toks: List[int], rid_lo: int,
                 rid_hi: int, nonfinite: bool) -> bool:
        """Whether a slot's planes from one superstep are plain decode,
        so the drain can append its emitted tokens ``toks`` in one go:
        no round went non-finite, and either nothing was emitted (an
        idle or prefilling row) or every token came from the request
        already running in the row (``rid_lo == rid_hi == req.rid``),
        past its first token, with no stop token and short of
        ``max_new``.  Any other slot -- a promote, a first token, a
        finish, a quarantine -- takes the per-round walk."""
        if nonfinite:
            return False
        if not toks:
            return True
        return (req is not None and not req.done and bool(req.out)
                and rid_lo == rid_hi == req.rid
                and (req.eos is None or req.eos not in toks)
                and len(req.out) + len(toks) < req.max_new)

    def _journal_digest(self) -> Dict[str, int]:
        """Round-clock stats fingerprint written with every step record;
        a replayed step must reproduce it exactly (wall-clock latency
        fields are deliberately absent -- they span processes)."""
        st = self.stats
        return {"round": st.decode_steps, "completed": st.completed,
                "cancelled": st.cancelled, "timed_out": st.timed_out,
                "failed": st.failed, "quarantined": st.quarantined,
                "decode_tokens": st.decode_tokens,
                "shard_crashes": st.shard_crashes}

    def _maybe_snapshot(self):
        """Snapshot the full serving state every ``snapshot_every``
        device rounds (suppressed while replaying a journal tail --
        replay re-executes past work, it does not re-persist it)."""
        if self.recover_dir is None or self.journal.replaying:
            return
        if self.stats.decode_steps - self._last_snapshot_round \
                < self.snapshot_every:
            return
        from repro.serving import recovery
        recovery.save_snapshot(self, self.recover_dir,
                               keep=self.snapshot_keep)
        self._last_snapshot_round = self.stats.decode_steps

    @classmethod
    def restore(cls, recover_dir: str, cfg, params, *, speculative=None,
                draft_params=None) -> "ServingEngine":
        """Rebuild an engine from a crash-recovery directory on a fresh
        process: newest good snapshot + journal-tail replay (see
        ``serving.recovery.restore_engine``).  The returned engine's
        streams are bit-identical to an uninterrupted run and it keeps
        journaling + snapshotting where the dead process stopped;
        ``engine.recovery_report`` says what recovery did."""
        from repro.serving import recovery
        return recovery.restore_engine(recover_dir, cfg, params,
                                       speculative=speculative,
                                       draft_params=draft_params)

    # ------------------------------------------------------------------
    def occupancy_report(self) -> Dict[str, Any]:
        """Queue + per-slot occupancy snapshot (stall diagnosis)."""
        slots = []
        for i in range(self.max_batch):
            cur, parked = self.current[i], self.staged[i]
            slots.append({
                "slot": i,
                "current": None if cur is None else {
                    "rid": cur.rid, "status": cur.status,
                    "prompt_len": len(cur.prompt),
                    "prompt_pos": int(self._prompt_pos[i]),
                    "out_tokens": len(cur.out),
                    "deadline": cur.deadline, "retries": cur.retries},
                "staged": None if parked is None else {
                    "rid": parked.rid, "status": parked.status,
                    "not_before": parked.not_before},
            })
        return {
            "decode_steps": self.stats.decode_steps,
            "queue_depth": len(self.scheduler),
            "queued": [r.rid for r in self.scheduler.waiting],
            "in_flight": sum(r is not None for r in self.current),
            "staged": sum(r is not None for r in self.staged),
            "dead_shards": sorted(self.dead_shards),
            "slots": slots,
        }

    def run_to_completion(self, max_steps: int = 100_000
                          ) -> Dict[int, List[int]]:
        """Step until every request reaches a terminal status.  Raises
        :class:`EngineStallError` (occupancy report attached) instead of
        returning silently if ``max_steps`` is exhausted with work still
        pending.  Returns ``{rid: output tokens}`` for every terminal
        request (non-completed requests contribute their partial -- or
        empty -- output; check ``engine.finished[rid].status``)."""
        steps = 0
        while (len(self.scheduler) or any(self.current)
               or any(self.staged)):
            if steps >= max_steps:
                report = self.occupancy_report()
                raise EngineStallError(
                    f"engine did not drain within {max_steps} steps: "
                    f"{report['queue_depth']} queued, "
                    f"{report['in_flight']} in flight, "
                    f"{report['staged']} staged at round "
                    f"{report['decode_steps']} (see .report)", report)
            self.step()
            steps += 1
        return {rid: r.out for rid, r in self.finished.items()}


def replay_trace(engine: ServingEngine, trace: List[Dict[str, Any]],
                 submit, max_steps: int = 100_000, start: int = 0,
                 stop=None) -> int:
    """Drive ``engine`` over an arrival trace until every request
    reaches a terminal status.  The arrival clock is the engine's
    device-round counter: request ``i`` is submitted via
    ``submit(i, trace[i])`` once ``trace[i]["arrival"] <=
    stats.decode_steps`` -- or immediately when the engine is idle, so a
    gap in arrivals cannot stall the round clock.  Drain is judged on
    *terminal* requests (``engine.finished``), not completions, so
    shed / failed / timed-out requests under fault injection or
    overload cannot hang the replay.  Shared by the arrival-trace
    bench, the serving example and the scheduler property tests so the
    replay semantics live in one place.

    Crash-recovery hooks: ``start`` says how many leading trace entries
    were already submitted (continue a restored engine with
    ``start=len(engine.requests)`` -- the count includes shed requests,
    exactly the submit calls already journaled), and ``stop(engine)``
    is checked after every step -- returning True abandons the drive
    mid-trace (the ``--crash`` bench's kill switch).  Returns how many
    trace entries have been submitted.  Because submission is driven by
    the round clock and terminal counts only, a continued drive makes
    the same submit-round decisions an uninterrupted one would."""
    i, steps = start, 0
    while i < len(trace) or len(engine.finished) < i:
        due = i < len(trace) and \
            trace[i]["arrival"] <= engine.stats.decode_steps
        idle = len(engine.finished) == i
        while i < len(trace) and (due or idle):
            submit(i, trace[i])
            i += 1
            due = i < len(trace) and \
                trace[i]["arrival"] <= engine.stats.decode_steps
            idle = False
        engine.step()
        steps += 1
        if stop is not None and stop(engine):
            return i
        if steps >= max_steps:
            raise RuntimeError(
                f"arrival trace did not drain within {max_steps} steps "
                f"({len(engine.finished)}/{i} submitted requests "
                f"terminal)")
    return i


@functools.lru_cache(maxsize=32)
def _decode_step_fn(cfg):
    """One compiled decode step per config (configs are frozen/hashable);
    repeated generate_one calls share it instead of re-tracing."""
    return jax.jit(lambda p, t, c: lm.decode_step(p, cfg, t, c))


def generate_one(cfg, params, prompt: List[int], max_new: int = 32,
                 max_len: int = 2048) -> List[int]:
    """Single-request greedy reference path (the engine parity oracle).

    Drives the prompt token-by-token through ``lm.decode_step`` -- the
    same unified code path the engine superstep uses for prefill and
    decode -- so engine streams are bit-comparable for every cache kind.
    (The parallel ``lm.prefill`` scan matches this path to fp32 rounding;
    the padding-invariance tests in tests/test_serving.py pin that
    equivalence on the parallel side, and
    test_generate_one_matches_parallel_prefill pins it here.)
    """
    if not prompt:
        raise ValueError("empty prompt")
    # same cache-position budget as ServingEngine.submit: the request
    # consumes len(prompt) + max_new - 1 positions.  KV-cache archs would
    # otherwise scatter past max_len (silently dropped under jit -- wrong
    # attention), recurrent archs would just mis-count; both are bugs.
    if len(prompt) + max_new - 1 > max_len:
        raise ValueError(
            f"prompt ({len(prompt)}) + max_new ({max_new}) needs "
            f"{len(prompt) + max_new - 1} cache positions, exceeding "
            f"max_len ({max_len})")
    cache = lm.init_cache(cfg, 1, max_len)
    step = _decode_step_fn(cfg)
    logits = None
    for t in prompt:
        logits, cache = step(params, jnp.asarray([t], jnp.int32), cache)
    out = [int(np.asarray(logits)[0, :cfg.vocab_size].argmax())]
    for _ in range(max_new - 1):
        logits, cache = step(params, jnp.asarray([out[-1]], jnp.int32),
                             cache)
        out.append(int(np.asarray(logits)[0, :cfg.vocab_size].argmax()))
    return out
