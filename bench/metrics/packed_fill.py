"""Share of the positions the superstep computes in its packed rounds
that carry a real token: the device counters ``packed_tokens`` over
``packed_rounds`` x slots x prompt chunk, summed over the traced window
from the stats of the program's ``engine.drain`` spans.  A packed round
computes C positions for every slot; a prefilling row fills up to C of
them, a decoding row one, a dead row none."""

import engine_spans

LAYER = "superstep"
UNIT = "%"
SOURCE = "program_counter"
BETTER = "higher"
MOVES = {"chat": "ttft_p95_ms"}


def read(ctx, suffix):
    red = engine_spans.read(ctx)
    drains = [] if red is None else red["drain_stats"]
    # each call's slots and chunk are the engine's, the same every call
    computed = sum(s["packed_rounds"] * s["slots"] * s["chunk"]
                   for s in drains)
    if not computed:
        return None
    return 100.0 * sum(s["packed_tokens"] for s in drains) / computed
