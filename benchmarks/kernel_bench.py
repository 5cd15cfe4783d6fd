"""Kernel micro-bench: Pallas kernels (interpret off-TPU) vs jnp strategies.

On CPU the Pallas kernels run in interpret mode (python-level emulation),
so wall-clock is NOT the TPU story -- the derived column therefore reports
the structural quantities that determine TPU performance: HBM bytes moved
per element and the arithmetic-intensity estimate from DESIGN.md §3.
Emits CSV rows plus machine-readable JSON (``--out``, default
BENCH_kernel.json) through the shared ``bench_utils.dump_json``.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from benchmarks.bench_utils import dump_json, header, row, time_call
from repro.core import blocks as blocks_lib
from repro.core import scan as scan_lib
from repro.kernels import resolve_interpret
from repro.kernels.block_step import ops as block_ops
from repro.kernels.decode_step import ops as step_ops
from repro.kernels.decode_step import ref as step_ref
from repro.kernels.fused_mingru import ops as fg_ops
from repro.kernels.scan import ops as scan_ops

# nominal v5e peaks, shared convention with roofline.py (197 TFLOP/s
# bf16) and engine_throughput.py (819 GB/s HBM); the ridge point is
# where a kernel stops being memory-bound
PEAK_FLOPS = 197e12
HBM_BYTES_PER_S = 819e9


def roofline_cols(flops: float, bytes_moved: float) -> dict:
    """Bytes-moved / FLOPs roofline columns for a kernel row: arithmetic
    intensity vs the ridge point decides which roof binds, and the
    ideal time is the binding roof's."""
    ai = flops / max(bytes_moved, 1.0)
    ridge = PEAK_FLOPS / HBM_BYTES_PER_S
    bound = "compute" if ai >= ridge else "memory"
    ideal_s = (flops / PEAK_FLOPS if bound == "compute"
               else bytes_moved / HBM_BYTES_PER_S)
    return {
        "flops_per_call": flops,
        "hbm_bytes_per_call": bytes_moved,
        "arith_intensity_flops_per_byte": ai,
        "ridge_flops_per_byte": ridge,
        "roofline_bound": bound,
        "ideal_us_v5e": ideal_s * 1e6,
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_kernel.json")
    args = ap.parse_args(argv)

    header("kernel_bench (scan strategies)")
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (4, 1024, 128)
    a = jax.nn.sigmoid(jax.random.normal(k1, shape))
    b = jax.random.normal(k2, shape)
    h0 = jnp.zeros((shape[0], shape[2]))

    runners = {
        "sequential": jax.jit(lambda a, b: scan_lib.scan_sequential(a, b)),
        "associative": jax.jit(lambda a, b: scan_lib.scan_associative(a, b)),
        "chunked": jax.jit(
            lambda a, b: scan_lib.scan_chunked(a, b, chunk=256)),
        "log_space": jax.jit(
            lambda a, b: scan_lib.scan_log_space(
                jnp.log(a), jnp.log(jnp.abs(b) + 1e-6))),
    }
    out = {}
    for name, fn in runners.items():
        us = time_call(fn, a, b, repeats=3)
        out[name] = {"us_per_call": us}
        row(f"kernel/{name}", us, "")

    # pallas rows -- compiled on TPU, interpret-mode timing on the CPU;
    # structural derived either way.
    interp = resolve_interpret(None)
    n = a.size
    # linear chunked-scan kernel: read a,b + write h
    us = time_call(
        lambda a, b, h0: scan_ops.linear_scan(a, b, h0, 256, 128, interp),
        a, b, h0, repeats=1)
    bytes_moved = 3 * n * 4
    intensity = 2 * 8 / (3 * 4)                  # kogge-stone flops/byte
    out["pallas_linear"] = {
        "us_per_call": us,
        "hbm_bytes_per_elem": bytes_moved / n,
        **roofline_cols(intensity * bytes_moved, bytes_moved),
    }
    row("kernel/pallas_linear", us,
        f"hbm_bytes_per_elem={bytes_moved / n:.0f};"
        f"arith_intensity={intensity:.2f}flops_per_byte;"
        f"{out['pallas_linear']['roofline_bound']}-bound")

    # log-space scan kernel: same traffic, ~3x the VPU flops (logaddexp)
    la, lb = jnp.log(a), jnp.log(jnp.abs(b) + 1e-6)
    lh0 = jnp.full_like(h0, -jnp.inf)
    us = time_call(
        lambda la, lb, lh0: scan_ops.log_space_scan(la, lb, lh0, 256, 128,
                                                    interp),
        la, lb, lh0, repeats=1)
    out["pallas_log"] = {
        "us_per_call": us,
        "hbm_bytes_per_elem": bytes_moved / n,
        **roofline_cols(3 * intensity * bytes_moved, bytes_moved),
    }
    row("kernel/pallas_log", us,
        f"hbm_bytes_per_elem={bytes_moved / n:.0f};"
        f"arith_intensity={3 * intensity:.2f}flops_per_byte;"
        f"{out['pallas_log']['roofline_bound']}-bound")

    # fused minGRU: read x + weights + write/re-read h (no gate round-trip).
    # Activation traffic convention matches train_throughput.py's
    # structural model: fused 2*Dh vs unfused (2P+2)*Dh = 6*Dh per token
    # (write + downstream read of every materialised activation).
    bsz, t, dh = shape
    dx = 64
    x = jax.random.normal(k3, (bsz, t, dx))
    wz = jax.random.normal(k1, (dx, dh)) * 0.2
    wh = jax.random.normal(k2, (dx, dh)) * 0.2
    us = time_call(
        lambda x, wz, wh: fg_ops.fused_mingru(x, wz, None, wh, None,
                                              interpret=interp),
        x, wz, wh, repeats=1)
    fused_bytes = (x.size + 2 * dx * dh + 2 * bsz * t * dh) * 4
    unfused_bytes = (x.size + 2 * dx * dh + 6 * bsz * t * dh) * 4
    fg_flops = 2 * 2 * bsz * t * dx * dh + 8 * bsz * t * dh
    out["pallas_fused_mingru"] = {
        "us_per_call": us,
        "hbm_bytes_per_elem": fused_bytes / (bsz * t * dh),
        "unfused_bytes_ratio": unfused_bytes / fused_bytes,
        **roofline_cols(fg_flops, fused_bytes),
    }
    row("kernel/pallas_fused_mingru", us,
        f"hbm_bytes_per_elem={fused_bytes / (bsz * t * dh):.1f};"
        f"unfused_traffic={unfused_bytes / fused_bytes:.2f}x;"
        f"{out['pallas_fused_mingru']['roofline_bound']}-bound")

    # fused decode step: the single-token batched GEMV (serving hot path).
    # Weight-bound at decode batch sizes -- structural traffic per step is
    # weights (P*Dx*Dh) + x + h in/out; the unfused step additionally
    # round-trips the P gate pre-activations (B, Dh) through HBM and
    # splits the work across P+1 XLA fusions.
    b_dec, dx_dec = 8, 128
    x1 = jax.random.normal(k3, (b_dec, dx_dec))
    h_prev = jax.random.normal(k1, (b_dec, dh))
    wz1 = jax.random.normal(k1, (dx_dec, dh)) * 0.2
    wh1 = jax.random.normal(k2, (dx_dec, dh)) * 0.2
    us = time_call(
        lambda x, h: step_ops.fused_mingru_step(x, wz1, None, wh1, None, h,
                                                interpret=interp),
        x1, h_prev, repeats=3)
    us_ref = time_call(
        jax.jit(lambda x, h: step_ref.mingru_step_ref(
            x, wz1, jnp.zeros(dh), wh1, jnp.zeros(dh), h)),
        x1, h_prev, repeats=3)
    n_proj = 2
    weight_bytes = n_proj * dx_dec * dh * 4
    act_bytes = (x1.size + 2 * b_dec * dh) * 4          # x + h in/out
    fused_step_bytes = weight_bytes + act_bytes
    unfused_step_bytes = fused_step_bytes + 2 * n_proj * b_dec * dh * 4
    step_flops = 2 * n_proj * b_dec * dx_dec * dh + 8 * b_dec * dh
    out["pallas_decode_step_mingru"] = {
        "us_per_call": us,
        "us_per_call_jnp_ref": us_ref,
        "hbm_bytes_per_step": fused_step_bytes,
        "unfused_bytes_ratio": unfused_step_bytes / fused_step_bytes,
        **roofline_cols(step_flops, fused_step_bytes),
    }
    row("kernel/pallas_decode_step_mingru", us,
        f"hbm_bytes_per_step={fused_step_bytes};"
        f"unfused_traffic={unfused_step_bytes / fused_step_bytes:.2f}x;"
        f"jnp_ref_us={us_ref:.1f};"
        f"{out['pallas_decode_step_mingru']['roofline_bound']}-bound")

    # whole-block decode step: the PR 9 megakernel -- norm + conv step +
    # cell + down + MLP for one layer in ONE pallas_call.  Structural
    # traffic per step is the layer's full weight slab + x/h/window
    # in/out; the cell-fused tier additionally round-trips every
    # intermediate activation (normed y, conv out, h, down out, MLP
    # hidden) through HBM across its 7 fusion boundaries.
    bcfg = blocks_lib.MinRNNBlockConfig(d_model=dx_dec, expansion=2.0)
    bdh = bcfg.d_hidden
    bdm = bcfg.d_mlp
    bparams = blocks_lib.init(jax.random.PRNGKey(1), bcfg)
    bstate = blocks_lib.init_state(bcfg, (b_dec,))
    xb = jax.random.normal(k3, (b_dec, dx_dec))
    us = time_call(
        lambda x, st: block_ops.fused_block_step(
            bparams, x, st, cell=bcfg.cell, mode=bcfg.mode,
            use_conv=bcfg.use_conv, use_mlp=bcfg.use_mlp),
        xb, bstate, repeats=3)
    blk_weight_bytes = ((n_proj + 1) * dx_dec * bdh
                       + 2 * dx_dec * bdm
                       + bcfg.conv_kernel * dx_dec + 2 * dx_dec) * 4
    kw = bcfg.conv_kernel - 1
    blk_act_bytes = (2 * xb.size + 2 * b_dec * bdh
                     + 2 * b_dec * kw * dx_dec) * 4
    blk_bytes = blk_weight_bytes + blk_act_bytes
    cell_tier_bytes = blk_bytes + 2 * b_dec * (3 * dx_dec + bdh + bdm) * 4
    blk_flops = (2 * (n_proj + 1) * b_dec * dx_dec * bdh
                 + 2 * 2 * b_dec * dx_dec * bdm
                 + 2 * b_dec * bcfg.conv_kernel * dx_dec
                 + 20 * b_dec * dx_dec + 8 * b_dec * bdh)
    out["pallas_block_step_mingru"] = {
        "us_per_call": us,
        "hbm_bytes_per_step": blk_bytes,
        "cell_tier_bytes_ratio": cell_tier_bytes / blk_bytes,
        **roofline_cols(blk_flops, blk_bytes),
    }
    row("kernel/pallas_block_step_mingru", us,
        f"hbm_bytes_per_step={blk_bytes};"
        f"cell_tier_traffic={cell_tier_bytes / blk_bytes:.2f}x;"
        f"{out['pallas_block_step_mingru']['roofline_bound']}-bound")

    dump_json(args.out, {"shape": list(shape), "kernels": out})
    return out


if __name__ == "__main__":
    main()
