"""Tokens decoded in the traced window (output tokens less the first token
of each request, which rides its last prompt round), times 2N, over the
device time of the superstep program times the chip's peak."""

import trace_reduce

LAYER = "superstep"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = {"chat": "tpot_p95_ms"}


def read(ctx, suffix):
    c = ctx.get("counters")
    prog = trace_reduce.main_program(ctx["trace"])
    steps = c["decode_tokens"] - c["first_tokens"] if c else 0
    if steps <= 0 or prog is None:
        return None
    flops = steps * ctx["shape"].flops_per_token()
    return 100.0 * flops / (prog[1] * ctx["chips"]
                            * ctx["peak"]["bf16_flops_per_s"])
