"""Continuous-batching serving demo: the engine superstep.

Multiple requests of different lengths share one fixed-capacity device
batch.  Everything -- prompt consumption (teacher-forced prefill),
decode, sampling, EOS retirement and re-admission from per-slot staging
buffers -- happens inside ONE jitted device loop (``lm.superstep``) of
``--decode-block K`` rounds per host round-trip.  A long prompt simply
occupies one row while every other row keeps decoding: there is no
prefill phase and no barrier, and a slot that finishes mid-superstep is
re-armed from staging on the next device round (watch
``wasted_slot_steps`` stay near zero while the queue is non-empty).

    PYTHONPATH=src python examples/serve_batched.py --decode-block 4

``--trace N`` replays a synthetic N-request arrival trace instead of the
fixed prompt list: requests are submitted mid-flight (by device-round
arrival times) and per-request TTFT / inter-token latency is reported --
the continuous-admission regime the superstep engine is built for.

    PYTHONPATH=src python examples/serve_batched.py --trace 12

``--chaos`` arms the deterministic fault injector on top of either mode:
NaN state corruption, dropped staging uploads and straggler rounds are
injected at a seeded rate, the non-finite health guard quarantines and
retries poisoned requests, and the lifecycle summary shows every request
still reaching a terminal status.

    PYTHONPATH=src python examples/serve_batched.py --trace 12 --chaos

``--snapshot-dir DIR`` arms crash recovery: every submit/cancel/step is
write-ahead journaled and the full engine state is snapshotted every
``--snapshot-every`` rounds.  Kill the process mid-run, then
``--restore DIR`` rebuilds the engine from the latest snapshot, replays
the journal tail and drains the surviving requests to completion --
greedy streams are bit-identical to the uninterrupted run.

    PYTHONPATH=src python examples/serve_batched.py --trace 12 \\
        --snapshot-dir /tmp/serve_snap
    PYTHONPATH=src python examples/serve_batched.py --restore /tmp/serve_snap
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import archs
from repro.data.lm_corpus import decode_bytes
from repro.distributed import serve_mesh
from repro.launch import compile_cache
from repro.models import lm
from repro.serving.engine import ServingEngine, replay_trace
from repro.serving.faults import FaultInjector


def run_fixed(engine):
    prompts = [b"To be, or not to be", b"Now is the winter",
               b"Friends, Romans, countrymen", b"All the world's a stage",
               b"If music be the food of love", b"Once more unto the breach",
               b"O for a Muse of fire, that would ascend the brightest "
               b"heaven of invention"]        # long prompt: prefills in-loop
    for i, p in enumerate(prompts):           # 7 requests, 4 slots: queueing
        # mix of greedy and sampled requests in the same superstep batch
        engine.submit(list(p), max_new=16,
                      temperature=0.0 if i % 2 == 0 else 0.8,
                      top_k=0 if i % 2 == 0 else 40, top_p=0.95)
    t0 = time.time()
    outs = engine.run_to_completion()
    dt = time.time() - t0
    for rid in sorted(outs):
        print(f"req {rid}: {decode_bytes(outs[rid])!r}")
    return outs, dt


def run_trace(engine, n_requests, seed=0):
    """Replay a synthetic arrival trace: request i becomes visible once
    the engine has advanced past its arrival round, so admissions happen
    mid-flight (staged between supersteps, armed in-loop)."""
    rng = np.random.default_rng(seed)
    trace = [dict(arrival=int(rng.integers(0, 6 * n_requests)),
                  prompt=list(rng.integers(1, 250,
                                           size=int(rng.integers(3, 17)))),
                  max_new=int(rng.integers(8, 25)))
             for _ in range(n_requests)]
    trace.sort(key=lambda r: r["arrival"])
    t0 = time.time()
    replay_trace(engine, trace,
                 lambda i, r: engine.submit(r["prompt"],
                                            max_new=r["max_new"],
                                            temperature=0.8, top_k=40,
                                            top_p=0.95))
    dt = time.time() - t0
    for rid, req in sorted(engine.finished.items()):
        print(f"req {rid}: arrived@{req.submit_round} "
              f"ttft={req.first_round - req.submit_round + 1} rounds, "
              f"{len(req.out)} tokens")
    return {r: q.out for r, q in engine.finished.items()}, dt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--decode-block", type=int, default=None,
                    help="device rounds per host round-trip (K); default "
                         "4, or the --tune-file plan's K when one loads")
    ap.add_argument("--prompt-chunk", type=int, default=None,
                    help="prompt tokens a prefilling slot consumes per "
                         "device round (C): packed prefill streams the "
                         "weights once per C prompt tokens (default 1 = "
                         "unpacked, or the --tune-file plan's C)")
    ap.add_argument("--fuse-block", default="auto",
                    choices=["auto", "on", "off"],
                    help="whole-block decode megakernel "
                         "(kernels/block_step): one pallas_call per "
                         "layer per step; 'off' keeps the cell-only "
                         "kernel tier")
    ap.add_argument("--tune-file", default=None, metavar="PATH|auto",
                    help="autotune plan: a TUNE_<config>.json path "
                         "(shape-checked), or 'auto' for the discovery "
                         "order ($REPRO_TUNE_DIR, cwd, repo root); fills "
                         "block_dh and the K/C defaults")
    ap.add_argument("--trace", type=int, default=0, metavar="N",
                    help="replay a synthetic N-request arrival trace "
                         "instead of the fixed prompt list")
    ap.add_argument("--speculative", default=None, choices=["ngram"],
                    help="speculative decoding: n-gram self-drafts "
                         "verified in one chunk pass per round (streams "
                         "bit-identical; watch itl_rounds drop below 1)")
    ap.add_argument("--draft-len", type=int, default=4,
                    help="max draft tokens proposed per round (S)")
    ap.add_argument("--chaos", action="store_true",
                    help="inject deterministic faults (NaN corruption, "
                         "dropped uploads, stragglers) and watch the "
                         "quarantine/retry layer keep every request "
                         "terminal")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="arm crash recovery: write-ahead journal + "
                         "periodic engine snapshots under DIR (starts a "
                         "NEW journal epoch, truncating any prior one)")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="device rounds between snapshots (default 8)")
    ap.add_argument("--restore", default=None, metavar="DIR",
                    help="resume a crashed run from DIR: load the latest "
                         "good snapshot, replay the journal tail, then "
                         "drain the surviving requests (engine shape "
                         "comes from the journal header, not the CLI)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serving mesh, e.g. 2x1 (slot pool over 2 data "
                         "shards) or 2x2 (+ d_hidden over 2 model "
                         "shards); forces virtual CPU devices before jax "
                         "initialises")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.tune_file is None and args.decode_block is None:
        args.decode_block = 4           # the untuned demo default

    mesh_plan = serve_mesh.MeshPlan.parse(args.mesh)
    if mesh_plan is not None:
        serve_mesh.ensure_host_devices(mesh_plan.size)

    cfg = archs.smoke("mingru-lm")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)

    if args.restore:
        engine = ServingEngine.restore(args.restore, cfg, params)
        rep = engine.recovery_report
        print(f"restored from {args.restore}: snapshot "
              f"@{rep['snapshot_round']}, replayed "
              f"{rep['replayed_records']} journal records "
              f"({rep['replayed_rounds']} rounds) in "
              f"{rep['recovery_s']:.2f}s")
        t0 = time.time()
        outs = engine.run_to_completion()
        dt = time.time() - t0
        for rid in sorted(outs):
            print(f"req {rid}: {len(outs[rid])} tokens")
        args.chaos, mesh_plan = False, engine.mesh_plan
    else:
        faults = FaultInjector(seed=2, nan_rate=0.01, drop_rate=0.05,
                               straggler_rate=0.05, straggler_s=0.002) \
            if args.chaos else None
        engine = ServingEngine(cfg, params, max_batch=4, max_len=256,
                               decode_block=args.decode_block,
                               prompt_chunk=args.prompt_chunk,
                               speculative=args.speculative,
                               draft_len=args.draft_len,
                               faults=faults, max_retries=2,
                               mesh=mesh_plan,
                               fuse_block=args.fuse_block,
                               tune=args.tune_file,
                               recover_dir=args.snapshot_dir,
                               snapshot_every=args.snapshot_every)

    if not args.restore:
        if args.trace:
            outs, dt = run_trace(engine, args.trace)
        else:
            outs, dt = run_fixed(engine)
    n = sum(len(o) for o in outs.values())
    print(f"{len(outs)} requests, {n} tokens, {n / dt:.1f} tok/s")
    snap = engine.stats.snapshot()
    plan = engine.tune_plan
    print(f"kernel tier: {engine.kernel_tier} "
          f"(fuse_block={args.fuse_block}, "
          f"block_dh={engine.cfg.block_dh or 'default'}"
          + (f", plan {plan.get('source', '<dict>')}" if plan else "")
          + ")")
    print(f"prefill tokens (in-loop): {snap['prefill_tokens']} "
          f"over {snap['prefill_rounds']} rounds "
          f"(C={engine.prompt_chunk}), "
          f"decode rounds: {snap['decode_steps']} in "
          f"{snap['decode_calls']} host round-trips "
          f"(K={engine.decode_block}, "
          f"{snap['host_roundtrips_per_decode_token']:.2f} "
          f"round-trips/token), "
          f"wasted slot steps: {snap['wasted_slot_steps']} "
          f"({snap['wasted_slot_fraction']:.1%}), "
          f"queue peak: {snap['queue_peak']}")
    print(f"ttft mean: {snap['ttft_rounds_mean']:.1f} rounds "
          f"({snap['ttft_s_mean'] * 1e3:.1f}ms), "
          f"inter-token: {snap['itl_s_mean'] * 1e3:.1f}ms "
          f"({snap['itl_rounds_mean']:.2f} rounds/token)")
    if mesh_plan is not None:
        per = " | ".join(
            f"shard {i}: {s['decode_tokens']} tok, "
            f"{s['wasted_slot_steps']} wasted"
            for i, s in enumerate(snap["shards"]))
        print(f"mesh {mesh_plan}: {per} "
              f"(identities ok: {snap['shard_identities_ok']})")
    if args.chaos:
        print(f"chaos: injected {faults.counts()} -> "
              f"{snap['completed']}/{snap['submitted']} completed, "
              f"quarantined {snap['quarantined']}, "
              f"retried {snap['retried']}, failed {snap['failed']} "
              f"(every request terminal)")


if __name__ == "__main__":
    main()
