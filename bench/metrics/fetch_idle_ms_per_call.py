"""Device idle time per engine call while the host reads results back:
the idle gaps in the traced window that fall inside the program's
``engine.fetch`` spans (every device-to-host read of ``step``), over its
``engine.decode`` spans.  The superstep runs while the first fetch
waits, so this is the time after it when the chip has nothing to do."""

import engine_spans

LAYER = "engine host loop"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = {"chat": "tpot_p95_ms", "reason": "output_tok_s"}


def read(ctx, suffix):
    return engine_spans.per_call_ms(
        ctx, lambda r: r["idle_s"].get("engine.fetch", 0.0))
