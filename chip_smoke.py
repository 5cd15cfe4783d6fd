#!/usr/bin/env python3
"""Run the paper's two LMs end to end on one TPU, at published width.

    python chip_smoke.py                # one chip: serve both LMs, train one
    python chip_smoke.py --four-chips   # the mesh-sharded superstep, 4 chips

One process drives the entry points a user calls -- ``ServingEngine``,
``generate_one`` and ``repro.launch.train.main`` -- at the published
widths of ``mingru-lm`` and ``minlstm-lm`` (12 layers, d_model 768,
d_hidden 1536, d_ff 3072, bf16) with random weights from ``--seed``.

One chip:
  * serve each LM: 16 byte prompts of 64-512 tokens, 64 new tokens each,
    64 slots, 8 rounds per host call, once with one prompt token per
    round (the step kernels) and once with 16 (the chunk kernels).  The
    kernel tier must be ``block-fused`` and the compiled superstep must
    hold the Pallas kernels (``tpu_custom_call``).  The greedy streams
    must be identical across the two runs and equal ``generate_one``.
    One decode round's logits, after 32 rounds over the same tokens, must
    agree with the pure-jnp path (``scan_strategy="associative"``) within
    ``LOGIT_TOL`` (see there);
  * train mingru-lm for a few steps at batch 8 x 2048 tokens: finite loss
    and no step retried by the supervisor.

Four chips (``--four-chips``, and no other phase): mingru-lm on meshes
1x1, 4x1 and 2x2.  4x1 streams must equal 1x1 bit for bit; 2x2 streams
must equal them too (argmax-equal), and one tensor-parallel decode
round's logits must agree with one device within ``LOGIT_TOL``.  Each
shard's device is printed.

Every check prints one line.  The last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every check
passed; otherwise the script exits 1.  Without a TPU it exits 2 before
any phase runs.  The persistent compilation cache is where
``JAX_COMPILATION_CACHE_DIR`` says, else ``<repo>/.jax_cache``; training
checkpoints go to ``<repo>/.chip_smoke``, emptied first.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCHS = ("mingru-lm", "minlstm-lm")
SERVE_BATCH = 64
N_REQUESTS = 16
PROMPT_LENS = (64, 512)
MAX_NEW = 64
MAX_LEN = PROMPT_LENS[1] + MAX_NEW
DECODE_BLOCK = 8
CHUNK = 16
LOGIT_ROUNDS = 32
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 2048, 3
# |logits - reference| <= LOGIT_TOL * max|reference logit|.  bf16 keeps
# 8 significant bits (relative step 2**-8 = 0.0039).  The pure-jnp path
# rounds every gate, product and sum of the cell to bf16, the kernels keep
# them in fp32 and round h once, so the two residual streams drift apart
# by a few bf16 steps over 12 layers and 32 rounds: about 0.01 of the
# logit scale (2.6 steps) on the CPU interpreter at these widths.  The
# limit leaves 5x room for the chip's own rounding; a broken kernel (a
# wrong tap, gate or tile) is off by the whole scale.
LOGIT_TOL = 0.05
OUT_DIR = os.path.join(REPO, ".chip_smoke")


class Checks:
    """Prints one line per check; remembers the failures and the
    superstep compile times."""

    def __init__(self):
        self.failed = []
        self.compile_s = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        tail = f" -- {detail}" if detail else ""
        print(f"check {name}: {'ok' if ok else 'FAILED'}{tail}", flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def make_prompts(seed: int):
    """``N_REQUESTS`` byte prompts cut from the bundled corpus."""
    import numpy as np

    from repro.data import lm_corpus
    corpus = lm_corpus.build_corpus()[0]
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    starts = rng.integers(0, len(corpus) - PROMPT_LENS[1], N_REQUESTS)
    return [corpus[s:s + n].tolist() for s, n in zip(starts, lens)]


def serve(cfg, params, prompts, *, prompt_chunk: int, mesh=None,
          tag: str, check: Checks):
    """One engine run over ``prompts``; returns the greedy streams."""
    from repro.serving.engine import ServingEngine
    eng = ServingEngine(cfg, params, max_batch=SERVE_BATCH, max_len=MAX_LEN,
                        decode_block=DECODE_BLOCK, prompt_chunk=prompt_chunk,
                        mesh=mesh)
    want_tier = "cell-fused" if mesh and eng.mesh_plan.model > 1 \
        else "block-fused"
    check(f"{tag} kernel tier", eng.kernel_tier == want_tier,
          eng.kernel_tier)
    t0 = time.perf_counter()
    superstep = eng._superstep_fn(eng.decode_block)
    text = superstep.lower(eng.params, eng.draft_params,
                           eng.state).compile().as_text()
    check.compile_s.append(time.perf_counter() - t0)
    check(f"{tag} superstep holds tpu_custom_call",
          "tpu_custom_call" in text,
          f"lower+compile {check.compile_s[-1]:.2f}s")
    rids = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    outs = eng.run_to_completion()
    wall = time.perf_counter() - t0
    done = [eng.finished[r].status == "COMPLETED" and len(outs[r]) == MAX_NEW
            for r in rids]
    snap = eng.stats.snapshot()
    check(f"{tag} requests completed", all(done),
          f"{sum(done)}/{len(rids)} with {MAX_NEW} tokens; "
          f"{snap['decode_calls']} host calls, "
          f"{snap['prefill_tokens']} prompt tokens, wall {wall:.2f}s "
          f"(first call compiles)")
    return eng, [outs[r] for r in rids]


def decode_logits(cfg, params, tokens, step_fn=None):
    """Logits of the last of ``tokens.shape[1]`` decode rounds."""
    import jax
    import numpy as np

    from repro.models import lm
    step = step_fn or jax.jit(
        lambda p, t, c: lm.decode_step(p, cfg, t, c))
    cache = lm.init_cache(cfg, tokens.shape[0], MAX_LEN)
    for t in range(tokens.shape[1]):
        logits, cache = step(params, tokens[:, t], cache)
    return np.asarray(logits, np.float32)[:, :cfg.vocab_size]


def logits_agree(name, got, want, check: Checks):
    import numpy as np
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    same = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    return check(name, err <= LOGIT_TOL * scale,
                 f"max|diff| {err:.4g}, max|ref| {scale:.4g}, "
                 f"ratio {err / scale:.4g} (limit {LOGIT_TOL}), "
                 f"argmax agreement {same:.3f}")


def round_tokens(prompts):
    """(SERVE_BATCH, LOGIT_ROUNDS) int32: prompt prefixes, rows cycled."""
    import numpy as np
    rows = [prompts[i % len(prompts)][:LOGIT_ROUNDS]
            for i in range(SERVE_BATCH)]
    return np.asarray(rows, np.int32)


def serve_phase(arch, cfg, params, prompts, check: Checks):
    from repro.serving.engine import generate_one
    streams = {}
    for chunk in (1, CHUNK):
        _, streams[chunk] = serve(cfg, params, prompts, prompt_chunk=chunk,
                                  tag=f"{arch} C={chunk}", check=check)
    check(f"{arch} greedy streams C=1 == C={CHUNK}",
          streams[1] == streams[CHUNK],
          f"{sum(a == b for a, b in zip(streams[1], streams[CHUNK]))}"
          f"/{len(prompts)} equal")
    t0 = time.perf_counter()
    ref = [generate_one(cfg, params, p, max_new=MAX_NEW, max_len=MAX_LEN)
           for p in prompts]
    check(f"{arch} C=1 streams == generate_one", ref == streams[1],
          f"{sum(a == b for a, b in zip(ref, streams[1]))}/{len(prompts)} "
          f"equal, {time.perf_counter() - t0:.2f}s")
    toks = round_tokens(prompts)
    logits_agree(f"{arch} logits vs pure-jnp path",
                 decode_logits(cfg, params, toks),
                 decode_logits(cfg.replace(scan_strategy="associative"),
                               params, toks), check)


def train_phase(check: Checks, seed: int):
    from repro.launch import train
    ckpt = os.path.join(OUT_DIR, "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    t0 = time.perf_counter()
    report = train.main(["--arch", "mingru-lm", "--steps", str(TRAIN_STEPS),
                         "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
                         "--ckpt-dir", ckpt, "--ckpt-every", "1000",
                         "--log-every", "1", "--seed", str(seed)])
    wall = time.perf_counter() - t0
    loss = float(report.final_metrics["loss"]) if report.final_metrics \
        else float("nan")
    check("mingru-lm train steps", report.steps_run == TRAIN_STEPS,
          f"{report.steps_run} steps in {wall:.2f}s (first step compiles)")
    check("mingru-lm train no recovered failures",
          report.failures_recovered == 0,
          f"failures_recovered={report.failures_recovered}")
    check("mingru-lm train loss finite", math.isfinite(loss),
          f"loss {loss:.4f}")


def shard_devices(tree):
    """{device id} holding the shards of every leaf of ``tree``."""
    import jax
    return {s.device.id for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards}


def _shard_str(shard):
    idx = ",".join("" if i.start is None else f"{i.start}:{i.stop}"
                   for i in shard.index)
    return f"[{idx}]->{shard.device}"


def mesh_phase(cfg, params, prompts, check: Checks):
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed import context as mesh_ctx
    from repro.distributed import serve_mesh
    from repro.models import lm
    streams = {}
    for spec in ("1x1", "4x1", "2x2"):
        eng, streams[spec] = serve(cfg, params, prompts, prompt_chunk=CHUNK,
                                   mesh=spec, tag=f"mesh {spec}",
                                   check=check)
        shown = {"params": (eng.params, eng.params["layers"]["blocks"]
                            ["rnn"]["wz"]["kernel"]),
                 "state": (eng.state, eng.state["cache"]["h"])}
        for name, (tree, leaf) in shown.items():
            print(f"mesh {spec} {name} leaf {leaf.shape} shards: "
                  + ", ".join(_shard_str(s) for s in leaf.addressable_shards),
                  flush=True)
            devs = shard_devices(tree)
            check(f"mesh {spec} {name} spread over {eng.mesh_plan.size} "
                  f"devices", len(devs) == eng.mesh_plan.size,
                  f"device ids {sorted(devs)}")
    check("mesh 4x1 streams == 1x1 (bit-exact)",
          streams["4x1"] == streams["1x1"])
    check("mesh 2x2 streams == 1x1 (argmax-equal)",
          streams["2x2"] == streams["1x1"],
          f"{sum(a == b for a, b in zip(streams['2x2'], streams['1x1']))}"
          f"/{len(prompts)} equal")

    plan = serve_mesh.MeshPlan(2, 2)
    mesh = plan.build()
    cache = lm.init_cache(cfg, SERVE_BATCH, MAX_LEN)
    pspecs = serve_mesh.serve_params_pspecs(params, cfg, plan, mesh)
    cspecs = serve_mesh._cache_pspecs(cache, True)

    def body(p, t, c):
        with mesh_ctx.serving_tp("model"):
            return lm.decode_step(p, cfg, t, c)

    tp_step = jax.jit(mesh_ctx.shard_map(
        body, mesh=mesh, in_specs=(pspecs, P("data"), cspecs),
        out_specs=(P("data"), cspecs), check_vma=False))
    toks = round_tokens(prompts)
    logits_agree("mesh 2x2 tensor-parallel logits vs one device",
                 decode_logits(cfg, params, toks, tp_step),
                 decode_logits(cfg, params, toks), check)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-sharded superstep on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: {device}; compile cache: {cache_dir}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke needs a TPU; JAX found none", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke needs {need} TPU chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    os.makedirs(OUT_DIR, exist_ok=True)

    from repro.configs import archs
    from repro.models import lm
    check = Checks()
    prompts = make_prompts(args.seed)
    print(f"prompts: {len(prompts)}, lengths "
          f"{sorted(len(p) for p in prompts)}", flush=True)

    def phase(name, fn, *a):
        t0 = time.perf_counter()
        try:
            fn(*a)
        except Exception:  # noqa: BLE001 -- a failed phase is reported
            traceback.print_exc()
            check(f"{name} ran", False, "raised, traceback on stderr")
        print(f"phase {name}: {time.perf_counter() - t0:.2f}s", flush=True)

    def params_for(arch):
        cfg = archs.get(arch)
        return cfg, lm.init_params(jax.random.PRNGKey(args.seed), cfg)

    t_all = time.perf_counter()
    if args.four_chips:
        phase("mesh mingru-lm",
              lambda: mesh_phase(*params_for("mingru-lm"), prompts, check))
    else:
        for arch in ARCHS:
            phase(f"serve {arch}",
                  lambda a=arch: serve_phase(a, *params_for(a), prompts,
                                             check))
        phase("train mingru-lm", train_phase, check, args.seed)
    print(f"total: {time.perf_counter() - t_all:.2f}s; superstep "
          f"lower+compile {sum(check.compile_s):.2f}s over "
          f"{len(check.compile_s)} programs; {len(check.failed)} failed "
          f"checks", flush=True)
    if check.failed:
        print("failed: " + ", ".join(check.failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
