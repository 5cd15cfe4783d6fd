"""Share of its roofline that ``kernels/block_step`` reached in the traced
window, its step and chunk forms together: the least time the chip needs
for the block work the counters say was done (token-steps of every live
row, each block weight read once per round, each live row's state read
and written once per round) over the device time of the block kernels,
matched by the names the kernels carry in the trace.  Padded and masked
positions of the chunk form are not work, so they show as a lower share.
The copies of each layer's weights that the layer scan makes before the
kernel runs are not the kernel's: they show in the step's whole share
(``round_ms``, ``mfu*``) and in the breakdown, not here."""

import re

import harness
import work

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = {"chat": "tpot_p95_ms", "reason": "output_tok_s"}
KERNEL = re.compile(r"^block_(step|chunk)_kernel$")


def read(ctx, suffix):
    c = ctx.get("counters")
    secs = sum(v for k, v in ctx["trace"]["ops"].items() if KERNEL.search(k))
    if not c or secs <= 0:
        return None
    shape, chips = ctx["shape"], ctx["chips"]
    tokens = c["prefill_tokens"] + c["decode_tokens"] - c["first_tokens"]
    live = c["slot_steps"] - c["wasted_slot_steps"]
    # per chip: its share of the rows, and every weight each round (a
    # data-parallel pool holds the whole model on each chip)
    ideal, bound = work.ideal_seconds(
        shape.block_flops(tokens) / chips,
        shape.block_bytes(c["decode_steps"], live / chips), ctx["peak"])
    harness.log(f"block_step_roofline.{suffix}: {bound}-bound, ideal "
                f"{ideal:.6g}s over {secs:.6g}s of block kernels a chip")
    return 100.0 * ideal / secs
