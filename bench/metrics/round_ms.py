"""Device time of the superstep program per device round: the summed
device time of the program that took most of the traced window, over the
rounds the engine's counters advanced in it."""

import trace_reduce

LAYER = "superstep"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = {"chat": "tpot_p95_ms", "reason": "output_tok_s"}


def read(ctx, suffix):
    c = ctx.get("counters")
    prog = trace_reduce.main_program(ctx["trace"])
    if not c or not c["decode_steps"] or prog is None:
        return None
    return 1e3 * prog[1] / c["decode_steps"]
