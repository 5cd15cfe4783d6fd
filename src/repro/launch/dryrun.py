"""Multi-pod dry-run (assignment: MULTI-POD DRY-RUN step 3).

Lowers + compiles every (architecture x input-shape x mesh) cell against
the production mesh with ShapeDtypeStruct inputs (no allocation), prints
memory_analysis / cost_analysis, and records collective stats + roofline
terms to JSONL.

  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl

--all orchestrates one subprocess per cell (isolation + resumability).

The dry-run lowers on 512 virtual CPU devices, so this module pins JAX to
the CPU before JAX is imported; the orchestrating parent and its children
never hold an accelerator.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import functools
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import archs
from repro.configs.base import SHAPES, long_context_ok
from repro.distributed import context as mesh_ctx
from repro.distributed import sharding
from repro.launch import hlo_analysis, input_specs
from repro.launch.mesh import make_production_mesh
from repro.models import encdec, lm
from repro.training import optimizer as opt_lib
from repro.training import train_step as ts_lib


def _opt_cfg(cfg):
    return opt_lib.AdamWConfig(
        moment_dtype="bfloat16" if cfg.param_dtype == "bfloat16"
        else "float32")


def build_lowerable(cfg, shape, mesh, *, microbatches: int = 1):
    """Returns (fn, example_args, in_shardings, out_shardings, donate)."""
    p_specs = input_specs.params_specs(cfg)
    pure = bool(getattr(cfg, "pure_dp", 0))
    p_sh = sharding.params_shardings(p_specs, mesh, pure)
    model = encdec if cfg.family == "encdec" else lm

    if shape.kind == "train":
        ocfg = _opt_cfg(cfg)
        o_specs = jax.eval_shape(
            functools.partial(opt_lib.init, ocfg), p_specs)
        o_sh = opt_lib.AdamWState(
            step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            mu=sharding.params_shardings(o_specs.mu, mesh),
            nu=sharding.params_shardings(o_specs.nu, mesh))
        batch = input_specs.train_specs(cfg, shape)
        b_specs = sharding.batch_pspec(mesh, batch, pure)
        b_sh = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), b_specs)
        step_fn = ts_lib.make_train_step(cfg, ocfg,
                                         microbatches=microbatches)
        return (step_fn, (p_specs, o_specs, batch),
                (p_sh, o_sh, b_sh), (p_sh, o_sh, None), (0, 1))

    if shape.kind == "prefill":
        batch = input_specs.prefill_specs(cfg, shape)
        b_specs = sharding.batch_pspec(mesh, batch, pure)
        b_sh = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), b_specs)
        if cfg.family == "encdec":
            def fn(params, batch):
                return encdec.forward(params, cfg, batch["frames"],
                                      batch["tokens"])
        else:
            # frontend prefix tokens (vlm patches) extend the cached length
            max_len = shape.seq_len + (cfg.n_frontend_tokens
                                       if cfg.frontend == "patches" else 0)

            def fn(params, batch):
                return lm.prefill(params, cfg, batch["tokens"], max_len,
                                  patch_embeds=batch.get("patch_embeds"))
        return fn, (p_specs, batch), (p_sh, b_sh), None, ()

    # decode
    specs = input_specs.decode_specs(cfg, shape)
    c_pspecs = sharding.cache_pspecs(cfg, mesh, specs["cache"],
                                     shape.global_batch)
    c_sh = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), c_pspecs)
    t_sh = jax.sharding.NamedSharding(
        mesh, sharding.token_pspec(mesh, shape.global_batch))

    def fn(params, token, cache):
        return model.decode_step(params, cfg, token, cache)

    return (fn, (p_specs, specs["token"], specs["cache"]),
            (p_sh, t_sh, c_sh), (None, c_sh), (2,))


# ---------------------------------------------------------------------------
# Depth extrapolation: XLA's cost_analysis counts a while-loop body ONCE, so
# the scanned full-depth compile undercounts FLOPs/bytes/collectives by
# ~n_layers x (verified: scan vs unrolled on a 10-layer matmul stack).
# Unrolling the full model is honest but slow (374 s for starcoder2-15b).
# Instead we compile small UNROLLED depth variants, fit
#     cost = base + sum_i  n_i * per_layer_i
# per metric, and evaluate at the full depth -- exact for homogeneous
# trunks, and handled per layer type for the heterogeneous ones (dense
# prefix + MoE; encoder + decoder; hybrid groups).
# ---------------------------------------------------------------------------

def depth_variants(cfg):
    """Returns (variants, full_counts): each variant is (cfg_v, counts)."""
    if cfg.family == "encdec":
        mk = lambda e, d: cfg.replace(n_encoder_layers=e, n_layers=d)
        return ([(mk(1, 1), (1, 1)), (mk(2, 1), (2, 1)),
                 (mk(1, 2), (1, 2))],
                (cfg.n_encoder_layers, cfg.n_layers))
    if cfg.block_kind == "hybrid":
        every = cfg.hybrid_attn_every
        mk = lambda g: cfg.replace(n_layers=g * every)
        return ([(mk(1), (1,)), (mk(2), (2,))],
                (cfg.n_layers // every,))
    if cfg.moe and cfg.moe.first_dense_layers:
        mk = lambda d, m: cfg.replace(
            n_layers=d + m, moe=dataclasses.replace(
                cfg.moe, first_dense_layers=d))
        return ([(mk(1, 1), (1, 1)), (mk(2, 1), (2, 1)),
                 (mk(1, 2), (1, 2))],
                (cfg.moe.first_dense_layers,
                 cfg.n_layers - cfg.moe.first_dense_layers))
    mk = lambda n: cfg.replace(n_layers=n)
    return [(mk(1), (1,)), (mk(2), (2,))], (cfg.n_layers,)


def _cell_costs(cfg, shape, mesh, microbatches: int = 1) -> dict:
    """Compile one variant and extract the extrapolatable metrics."""
    fn, args, in_sh, out_sh, donate = build_lowerable(
        cfg, shape, mesh, microbatches=microbatches)
    kw = dict(in_shardings=in_sh)
    if out_sh is not None:
        kw["out_shardings"] = out_sh
    with mesh_ctx.use_mesh(mesh, pure_dp=bool(getattr(cfg, "pure_dp", 0))):
        compiled = jax.jit(fn, **kw).lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    coll = hlo_analysis.collective_stats(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(sum(v["bytes"] for v in coll.values())),
    }


def extrapolate_costs(cfg, shape, mesh, microbatches: int = 1) -> dict:
    """Fit base + per-layer-type costs from small unrolled variants."""
    import numpy as np
    variants, full = depth_variants(cfg)
    rows, metrics = [], []
    for cfg_v, counts in variants:
        rows.append([1.0] + list(counts))
        m = _cell_costs(cfg_v.replace(scan_layers=False), shape, mesh,
                        microbatches)
        metrics.append([m["flops"], m["bytes"], m["coll_bytes"]])
    a = np.array(rows)
    y = np.array(metrics)
    x, *_ = np.linalg.lstsq(a, y, rcond=None)
    full_row = np.array([1.0] + list(full))
    flops, byts, coll = full_row @ x
    return {"flops": max(flops, 0.0), "bytes": max(byts, 0.0),
            "coll_bytes": max(coll, 0.0),
            "fit": {"counts": [list(c) for _, c in variants],
                    "full": list(full)}}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             microbatches: int = 1, verbose: bool = True,
             cfg_override=None) -> dict:
    cfg = cfg_override or archs.get(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "microbatches": microbatches}

    if shape_name == "long_500k" and not long_context_ok(cfg):
        rec.update(ok=True, skipped=True,
                   reason="pure full-attention arch at 524k ctx "
                          "(DESIGN.md §5)")
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    fn, args, in_sh, out_sh, donate = build_lowerable(
        cfg, shape, mesh, microbatches=microbatches)
    jit_kw = dict(in_shardings=in_sh)
    if out_sh is not None:
        jit_kw["out_shardings"] = out_sh
    if donate:
        jit_kw["donate_argnums"] = donate

    with mesh_ctx.use_mesh(mesh, pure_dp=bool(getattr(cfg, "pure_dp", 0))):
        lowered = jax.jit(fn, **jit_kw).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = hlo_analysis.collective_stats(hlo)

    # honest per-device costs via small-unrolled depth extrapolation
    # (the scanned compile above proves lowering/memory; its cost_analysis
    # counts loop bodies once -- see module comment)
    costs = extrapolate_costs(cfg, shape, mesh, microbatches)
    flops_dev = costs["flops"]
    bytes_dev = costs["bytes"]
    coll_bytes = costs["coll_bytes"]
    terms = hlo_analysis.roofline_terms(flops_dev, bytes_dev, coll_bytes)

    n_total, n_active = input_specs.n_params(cfg)
    tokens = (shape.global_batch * shape.seq_len if shape.kind != "decode"
              else shape.global_batch)
    mf = hlo_analysis.model_flops(
        n_active, tokens, "train" if shape.kind == "train" else "infer")
    n_dev = mesh.size
    useful_ratio = mf / (flops_dev * n_dev) if flops_dev else 0.0

    rec.update(
        ok=True, skipped=False, cost_fit=costs["fit"],
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        n_devices=n_dev,
        mem=dict(argument_bytes=mem.argument_size_in_bytes,
                 output_bytes=mem.output_size_in_bytes,
                 temp_bytes=mem.temp_size_in_bytes,
                 alias_bytes=mem.alias_size_in_bytes),
        hbm_per_device=(mem.argument_size_in_bytes
                        + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes
                        - mem.alias_size_in_bytes),
        flops_per_dev=flops_dev, bytes_per_dev=bytes_dev,
        collectives={k: v for k, v in coll.items() if v["count"]},
        collective_bytes_per_dev=coll_bytes,
        roofline=terms,
        n_params=n_total, n_params_active=n_active,
        model_flops=mf, useful_flops_ratio=round(useful_ratio, 4),
    )
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_kind}] compile "
              f"{t_compile:.1f}s")
        print("  memory_analysis:", mem)
        print("  cost_analysis: flops/dev=%.3e bytes/dev=%.3e"
              % (flops_dev, bytes_dev))
        print("  collectives:", rec["collectives"])
        print("  roofline:", {k: (f"{v:.2e}" if isinstance(v, float) else v)
                              for k, v in terms.items()})
    return rec


def all_cells(include_extras: bool = True):
    names = list(archs.ASSIGNED)
    if include_extras:
        names += archs.PAPER_OWN + archs.EXTRAS
    for arch in names:
        for shape in SHAPES:
            for mesh in ("single", "multi"):
                yield arch, shape, mesh


def orchestrate(out_path: str, include_extras: bool, timeout: int,
                only_missing: bool = True):
    done = set()
    if only_missing and os.path.exists(out_path):
        with open(out_path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("ok"):
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass
    cells = [c for c in all_cells(include_extras) if c not in done]
    print(f"{len(cells)} cells to run ({len(done)} already done)")
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    for i, (arch, shape, mesh) in enumerate(cells):
        print(f"=== [{i + 1}/{len(cells)}] {arch} x {shape} x {mesh}",
              flush=True)
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--mesh", mesh, "--json-out", out_path]
        try:
            proc = subprocess.run(cmd, timeout=timeout,
                                  capture_output=True, text=True)
            if proc.returncode != 0:
                rec = {"arch": arch, "shape": shape, "mesh": mesh,
                       "ok": False,
                       "error": proc.stderr[-2000:] if proc.stderr else
                       "nonzero exit"}
                with open(out_path, "a") as f:
                    f.write(json.dumps(rec) + "\n")
                print("  FAILED:", proc.stderr.splitlines()[-1]
                      if proc.stderr else "?")
        except subprocess.TimeoutExpired:
            rec = {"arch": arch, "shape": shape, "mesh": mesh, "ok": False,
                   "error": f"compile timeout > {timeout}s"}
            with open(out_path, "a") as f:
                f.write(json.dumps(rec) + "\n")
            print("  TIMEOUT")


def apply_overrides(cfg, spec: str):
    """--override "ssm.chunk=64,remat=dots,moe.capacity_factor=1.0" """
    if not spec:
        return cfg
    for kv in spec.split(","):
        key, _, val = kv.partition("=")
        for cast in (int, float):
            try:
                val = cast(val)
                break
            except ValueError:
                continue
        if "." in key:
            sub, field = key.split(".", 1)
            subcfg = getattr(cfg, sub)
            cfg = cfg.replace(**{sub: dataclasses.replace(
                subcfg, **{field: val})})
        else:
            cfg = cfg.replace(**{key: val})
    return cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--override", default="",
                    help="comma-separated cfg overrides, e.g. ssm.chunk=64")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-extras", action="store_true")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--json-out", default=None,
                    help="append the single-cell record to this JSONL")
    args = ap.parse_args()

    if args.all:
        orchestrate(args.out, not args.no_extras, args.timeout)
        return

    try:
        cfg_override = None
        if args.override:
            cfg_override = apply_overrides(archs.get(args.arch),
                                           args.override)
        rec = run_cell(args.arch, args.shape, args.mesh, args.microbatches,
                       cfg_override=cfg_override)
        if args.override:
            rec["override"] = args.override
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "ok": False, "error": traceback.format_exc()[-2000:]}
        print(rec["error"], file=sys.stderr)
        if args.json_out:
            with open(args.json_out, "a") as f:
                f.write(json.dumps(rec) + "\n")
        sys.exit(1)
    if args.json_out:
        with open(args.json_out, "a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
