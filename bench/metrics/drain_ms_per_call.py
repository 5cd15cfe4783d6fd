"""Host time per engine call spent draining a superstep: the program's
``engine.drain`` spans (counter reads, the loop over every slot-round's
emission, promotion and retirement, and the staging mirror's re-sync)
summed over the traced window, over its ``engine.decode`` spans."""

import engine_spans

LAYER = "engine host loop"
UNIT = "ms"
SOURCE = "host_clock"
BETTER = "lower"
MOVES = {"chat": "tpot_p95_ms", "reason": "output_tok_s"}


def read(ctx, suffix):
    return engine_spans.per_call_ms(
        ctx, lambda r: r["time_s"].get("engine.drain", 0.0))
