"""Batched serving driver.

    PYTHONPATH=src python -m repro.launch.serve --arch mingru-lm --smoke \
        --ckpt-dir /tmp/repro_ckpt --prompts "To be" "Friends,"

Loads the latest checkpoint (or random init) and runs the continuous-
batching superstep engine over the given prompts: admission, prefill,
decode and sampling all happen inside one jitted device loop per
``--decode-block K`` rounds (``lm.superstep``), with finished slots
re-armed from their staging buffers in-loop.  ``--speculative ngram``
turns on speculative decoding (n-gram self-drafting, verified in one
chunk pass per round, streams bit-identical).  ``--max-queue``,
``--deadline-rounds``, ``--priority`` and ``--max-retries`` expose the
fault-tolerance layer (bounded admission, EDF deadlines, NaN-quarantine
retry -- see README "Failure model").  ``--fuse-block`` picks the decode
kernel tier (whole-block megakernel vs cell kernels) and ``--tune-file``
loads an autotuned (block_dh, C, K) plan -- see README "Autotuning".
``--snapshot-dir`` arms crash recovery (write-ahead journal + periodic
full-state snapshots) and ``--restore DIR`` resumes a crashed run
bit-identically -- see README "Crash recovery".
Prints the kernel tier + plan source, then completions (tagged with
their terminal status when not COMPLETED) + the engine stats snapshot
(prefill/decode token counters, wasted slot steps, per-request TTFT and
inter-token latency, tokens/s, host round-trips per decoded token, draft
accept rate, lifecycle/failure counters).
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import archs
from repro.data.lm_corpus import decode_bytes
from repro.distributed import serve_mesh
from repro.launch import compile_cache
from repro.models import lm
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import PHASES
from repro.training import checkpoint as ckpt_lib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mingru-lm")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--prompts", nargs="*",
                    default=["To be, or not to be", "Friends, Romans"])
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    ap.add_argument("--decode-block", type=int, default=None,
                    help="device rounds per host round-trip (K): one "
                         "superstep runs K token-select/step/sample/"
                         "re-admit rounds on device per engine.step() "
                         "(default: the --tune-file plan's K, else 1)")
    ap.add_argument("--prompt-chunk", type=int, default=None,
                    help="prompt tokens a prefilling slot consumes per "
                         "device round (C): packed prefill amortises one "
                         "weight stream over C prompt tokens (minGRU/"
                         "minLSTM archs only; default: the --tune-file "
                         "plan's C, else 1 = unpacked)")
    ap.add_argument("--fuse-block", default="auto",
                    choices=["auto", "on", "off"],
                    help="whole-block decode megakernel tier "
                         "(kernels/block_step): one pallas_call per "
                         "layer per decode round; 'off' keeps the "
                         "cell-only kernel tier, 'auto' falls back per "
                         "layer when a TP slice or non-rmsnorm block "
                         "rules the fused path out")
    ap.add_argument("--tune-file", default=None, metavar="PATH|auto|none",
                    help="autotune plan (benchmarks/autotune.py): an "
                         "explicit TUNE_<config>.json path (shape-"
                         "checked, mismatch raises), 'auto' for the "
                         "discovery order ($REPRO_TUNE_DIR, cwd, repo "
                         "root), or 'none'; fills block_dh and the K/C "
                         "defaults -- explicit flags win")
    ap.add_argument("--speculative", default=None, choices=["ngram"],
                    help="speculative decoding draft source: decoding "
                         "rows propose up to --draft-len tokens per "
                         "round, verified in one chunk pass -- streams "
                         "stay bit-identical, inter-token latency drops "
                         "below one round on accepted drafts")
    ap.add_argument("--draft-len", type=int, default=4,
                    help="max draft tokens proposed per round (S)")
    ap.add_argument("--priority", type=int, default=1,
                    help="scheduling class for all submitted prompts "
                         "(lower = more urgent; EDF-with-aging order)")
    ap.add_argument("--deadline-rounds", type=int, default=None,
                    help="per-request deadline in device rounds from "
                         "submission; overdue requests are TIMED_OUT "
                         "(partial output kept), and requests whose "
                         "deadline the capacity estimate cannot meet "
                         "are SHED at admission")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue (0 = unbounded): at "
                         "the high watermark new requests are REJECTED "
                         "until the queue drains below the low "
                         "watermark")
    ap.add_argument("--max-retries", type=int, default=1,
                    help="quarantine retry budget: how many times a "
                         "request killed by the non-finite health guard "
                         "is re-enqueued before it is FAILED")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serving mesh shape, e.g. 4x1 (data-parallel "
                         "slot shards) or 2x2 (+ tensor-parallel gate "
                         "projections).  On CPU the launcher forces DxM "
                         "virtual devices before jax initialises its "
                         "backend")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="arm crash recovery: journal every submit/"
                         "cancel/step to DIR/journal.jsonl and snapshot "
                         "the full serving state every --snapshot-every "
                         "rounds (starts a NEW journal epoch; resume a "
                         "crashed one with --restore DIR instead)")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="snapshot cadence in device rounds for "
                         "--snapshot-dir (default 8)")
    ap.add_argument("--restore", default=None, metavar="DIR",
                    help="resume a crashed serving run: rebuild the "
                         "engine from DIR's newest good snapshot + "
                         "journal-tail replay (engine shape flags are "
                         "taken from the journal header, not the CLI), "
                         "finish its in-flight requests, then serve "
                         "--prompts on top.  Keeps journaling into DIR")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.tune_file == "none":
        args.tune_file = None

    # device count is fixed at backend init: force it before ANY jax
    # device use (init_params below is the first), or fail actionably
    mesh_plan = serve_mesh.MeshPlan.parse(args.mesh)
    if mesh_plan is not None:
        serve_mesh.ensure_host_devices(mesh_plan.size)

    cfg = archs.smoke(args.arch) if args.smoke else archs.get(args.arch)
    if cfg.vocab_size != 256:
        cfg = cfg.replace(vocab_size=256)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        restored = ckpt_lib.CheckpointManager(args.ckpt_dir).restore_latest()
        if restored is not None:
            step, params, _ = restored
            print(f"loaded checkpoint step {step}")

    if args.restore:
        engine = ServingEngine.restore(args.restore, cfg, params)
        rep = engine.recovery_report
        print(f"restored from {args.restore}: snapshot "
              f"@{rep['snapshot_round']}, replayed "
              f"{rep['replayed_records']} journal records "
              f"({rep['replayed_rounds']} rounds) in "
              f"{rep['recovery_s']:.2f}s"
              + (f"; fell past corrupt snapshot(s) "
                 f"{rep['corrupt_snapshots_skipped']}"
                 if rep["corrupt_snapshots_skipped"] else ""))
    else:
        engine = ServingEngine(cfg, params, max_batch=args.max_batch,
                               max_len=args.max_len, seed=args.seed,
                               decode_block=args.decode_block,
                               prompt_chunk=args.prompt_chunk,
                               speculative=args.speculative,
                               draft_len=args.draft_len,
                               max_queue=args.max_queue,
                               max_retries=args.max_retries,
                               mesh=mesh_plan,
                               fuse_block=args.fuse_block,
                               tune=args.tune_file,
                               recover_dir=args.snapshot_dir,
                               snapshot_every=args.snapshot_every)
    rids = {}
    for p in args.prompts:
        rid = engine.submit(list(p.encode()), max_new=args.max_new,
                            temperature=args.temperature,
                            top_k=args.top_k, top_p=args.top_p,
                            priority=args.priority,
                            deadline=args.deadline_rounds)
        rids[rid] = p

    t0 = time.time()
    outs = engine.run_to_completion()
    dt = time.time() - t0
    n_tokens = sum(len(o) for o in outs.values())
    for rid, toks in sorted(outs.items()):
        req = engine.finished[rid]
        tag = "" if req.status == "COMPLETED" else f" [{req.status}]"
        # a restored engine also finishes requests journaled by the
        # crashed process, whose prompts arrived via the journal
        label = rids.get(rid, decode_bytes(req.prompt))
        print(f"--- [{label!r}]{tag} -> {decode_bytes(toks)!r}")
    print(f"{n_tokens} tokens in {dt:.2f}s "
          f"({n_tokens / max(dt, 1e-9):.1f} tok/s, batched)")
    snap = engine.stats.snapshot()
    plan = engine.tune_plan
    print(f"kernel tier: {engine.kernel_tier} "
          f"(fuse_block={args.fuse_block}, "
          f"block_dh={engine.cfg.block_dh or 'default'}"
          + (f", plan {plan.get('source', '<dict>')}" if plan else
             ", no tune plan") + ")")
    print(f"superstep K={engine.decode_block} C={engine.prompt_chunk}: "
          f"{snap['decode_calls']} host round-trips for "
          f"{snap['decode_tokens']} decoded tokens "
          f"({snap['host_roundtrips_per_decode_token']:.3f} "
          f"round-trips/token); "
          f"{snap['prefill_tokens']} prompt tokens prefilled in-loop "
          f"over {snap['prefill_rounds']} packed rounds; "
          f"wasted slot steps: {snap['wasted_slot_steps']} "
          f"({snap['wasted_slot_fraction']:.1%} of slot steps)")
    print(f"latency: ttft mean {snap['ttft_s_mean'] * 1e3:.1f}ms "
          f"(p95 {snap['ttft_s_p95'] * 1e3:.1f}ms, "
          f"{snap['ttft_rounds_mean']:.1f} device rounds), "
          f"inter-token {snap['itl_s_mean'] * 1e3:.1f}ms "
          f"({snap['itl_rounds_mean']:.2f} rounds/token)")
    calls = max(snap["decode_calls"], 1)
    print("host phases (ms per superstep call): " + ", ".join(
        f"{k} {snap[k + '_time_s'] * 1e3 / calls:.3f}" for k in PHASES)
          + f"; packed rounds {snap['packed_rounds']}, "
          f"{snap['packed_tokens']} positions filled; drained in one go "
          f"{snap['drain_bulk_slots']} of "
          f"{snap['decode_calls'] * engine.max_batch} slots")
    if args.speculative:
        print(f"speculative ({args.speculative}, S={args.draft_len}): "
              f"{snap['draft_accepted']}/{snap['draft_proposed']} drafts "
              f"accepted ({snap['accept_rate']:.1%}); "
              f"{snap['non_spec_tokens']} of {snap['decode_tokens']} "
              f"tokens from the non-speculative path")
    if mesh_plan is not None:
        per = ", ".join(
            f"shard {i}: {s['decode_tokens']} tok "
            f"({s['wasted_slot_steps']} wasted)"
            for i, s in enumerate(snap["shards"]))
        print(f"mesh {mesh_plan} ({mesh_plan.size} devices): {per}; "
              f"slot-step identity per shard + global: "
              f"{snap['shard_identities_ok']}")
    print(f"lifecycle: {snap['completed']}/{snap['submitted']} completed "
          f"({snap['completion_rate']:.0%}), "
          f"cancelled {snap['cancelled']}, timed_out {snap['timed_out']}, "
          f"failed {snap['failed']}, shed {snap['shed']}, "
          f"rejected {snap['rejected']}; "
          f"quarantined {snap['quarantined']} "
          f"(retried {snap['retried']}, "
          f"nonfinite rounds {snap['nonfinite_decode_rounds']})")
    print("engine stats: " + ", ".join(
        f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in sorted(snap.items())))


if __name__ == "__main__":
    main()
