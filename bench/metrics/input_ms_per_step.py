"""Host time per training step spent building the step's batch from the
corpus and uploading it (the benchmark's ``bench.batch`` span), averaged
over the steps in the traced window."""

LAYER = "training input"
UNIT = "ms"
SOURCE = "host_clock"
BETTER = "lower"
MOVES = {"train": "train_tok_s"}


def read(ctx, suffix):
    a, b = ctx["traced"]
    spans = [t1 - t0 for name, t0, t1 in ctx["spans"]
             if name == "bench.batch" and t0 >= a and t1 <= b]
    return 1e3 * sum(spans) / len(spans) if spans else None
