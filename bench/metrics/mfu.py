"""Model FLOP/s utilisation over the traced window: useful tokens times the
operations each needs, over window x chips x peak.  Serving counts 2N per
prompt or decoded token; training counts 6N per trained token, and
recomputed forward passes do not count."""

LAYER = {"reason": "superstep", "train": "train step"}
UNIT = "%"
SOURCE = "device_trace"
BETTER = "higher"
MOVES = {"reason": "output_tok_s", "train": "train_tok_s"}


def read(ctx, suffix):
    c = ctx.get("counters")
    win = ctx["trace"]["window_s"]
    if not c or win <= 0:
        return None
    shape = ctx["shape"]
    if suffix == "train":
        flops = c["tokens"] * shape.train_flops_per_token()
    else:
        tokens = c["prefill_tokens"] + c["decode_tokens"] - c["first_tokens"]
        flops = tokens * shape.flops_per_token()
    if flops <= 0:
        return None
    return 100.0 * flops / (win * ctx["chips"]
                            * ctx["peak"]["bf16_flops_per_s"])
