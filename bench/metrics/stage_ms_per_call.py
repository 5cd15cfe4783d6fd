"""Host time per engine call spent staging queued requests into the
staging buffers and uploading the staged rows to the device: the
program's ``engine.stage`` and ``engine.upload`` spans summed over the
traced window, over its ``engine.decode`` spans (one per superstep
call)."""

import engine_spans

LAYER = "engine host loop"
UNIT = "ms"
SOURCE = "host_clock"
BETTER = "lower"
MOVES = {"chat": "tpot_p95_ms", "reason": "output_tok_s"}


def read(ctx, suffix):
    return engine_spans.per_call_ms(
        ctx, lambda r: r["time_s"].get("engine.stage", 0.0)
        + r["time_s"].get("engine.upload", 0.0))
