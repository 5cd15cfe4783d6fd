"""Host time per engine call outside the superstep: the benchmark's span
around ``ServingEngine.step`` less that call's increase in
``stats.decode_time_s`` (the superstep call and its blocking fetch),
averaged over the calls in the traced window."""

LAYER = "engine host loop"
UNIT = "ms"
SOURCE = "host_clock"
BETTER = "lower"
MOVES = {"chat": "tpot_p95_ms", "reason": "output_tok_s"}


def read(ctx, suffix):
    calls = ctx.get("calls") or []
    if not calls:
        return None
    return 1e3 * sum((t1 - t0) - dec for t0, t1, dec in calls) / len(calls)
