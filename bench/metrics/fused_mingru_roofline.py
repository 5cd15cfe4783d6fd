"""Share of its roofline that the training kernels reached in the traced
window: the fused minGRU layer kernel (gate projections and scan, forward)
and the scan kernel run in reverse by its backward.  The ideal is the
work the algorithm needs for the steps completed: per layer and token,
the two gate products once (full remat runs the forward kernel a second
time, which shows as a lower share), x read and h written in bfloat16,
the gate weights once per step; and for the reverse scan, the gate
coefficient and the incoming gradient read and the carried gradient
written in float32."""

import re

import harness
import work

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = {"train": "train_tok_s"}
KERNEL = re.compile(r"^(fused_mingru_kernel|linear_scan_kernel)$")


def read(ctx, suffix):
    c = ctx.get("counters")
    secs = sum(v for k, v in ctx["trace"]["ops"].items() if KERNEL.search(k))
    if not c or not c["tokens"] or secs <= 0:
        return None
    s = ctx["shape"]
    tok, gates = c["tokens"], s.n_gates * s.d_model * s.d_hidden
    flops = s.n_layers * 2.0 * tok * gates
    nbytes = s.n_layers * (
        tok * (s.d_model + s.d_hidden) * s.dtype_bytes      # forward x, h
        + c["steps"] * gates * s.dtype_bytes                # gate weights
        + tok * s.d_hidden * 3 * 4)                         # reverse scan
    ideal, bound = work.ideal_seconds(flops, nbytes, ctx["peak"])
    harness.log(f"fused_mingru_roofline.{suffix}: {bound}-bound, ideal "
                f"{ideal:.6g}s over {secs:.6g}s of training kernels")
    return 100.0 * ideal / (secs * ctx["chips"])
