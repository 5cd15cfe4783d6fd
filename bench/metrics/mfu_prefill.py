"""Prompt tokens the superstep consumed in the traced window, times 2N,
over the device time of the superstep program times the chip's peak: the
whole superstep's utilisation spent on prompts.  The offered load is fixed,
so this divides by device time and not by the window."""

import trace_reduce

LAYER = "superstep"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = {"chat": "ttft_p95_ms"}


def read(ctx, suffix):
    c = ctx.get("counters")
    prog = trace_reduce.main_program(ctx["trace"])
    if not c or not c["prefill_tokens"] or prog is None:
        return None
    flops = c["prefill_tokens"] * ctx["shape"].flops_per_token()
    return 100.0 * flops / (prog[1] * ctx["chips"]
                            * ctx["peak"]["bf16_flops_per_s"])
