"""The benchmark's model modules: everything it knows about one model family.

A configuration file names its module with the meta key ``"model"``:
``"model": "minrnn"`` selects ``bench/models/minrnn.py``; ``harness.Cell``
loads it once as ``cell.model`` and hands it to the weights and the
drivers, which reach the family only through it.  So a configuration of
another architecture comes as new files: its configuration, its module,
its cells, traffic and metric readers.

A model module is a plain reference of the family, written from its
published description; it imports nothing of the program and takes
nothing that the program made.  It reads the sizes by the names of the
program's config fields.  Every key of a configuration file outside the
harness's ``META`` is checked against the program and cut to the
program's smoke preset in the rehearsals: a field of the program's config,
or a key of the module's ``source_values``; any other key stops the run.
The source's own values of the keys the file cuts sit under
``published``.  It provides:

``layout(conf)``
    ``{path tuple: (shape, kind)}`` for every leaf of the parameter tree
    the program serves, in the program's own nesting.  ``kind`` is one of
    the draws of ``weights.py``, which makes every leaf from the seed in
    one jitted call; the leaves are drawn in sorted path order.
``forward(params, tokens, conf, control=False)``
    float32 reference logits ``(B, T, vocab)`` for padded int tokens
    ``(B, T)`` (the serving check passes one sequence, ``B = 1``).  It may
    compute them in blocks, and jits what it runs.  ``control=True``
    computes at the precision below the configuration's, as the control
    that the comparison has to fail.
``loss_and_grad(params, batch, conf, rows, control=False)``
    for training cells: the mean next-token loss over labels >= 0 and its
    float32 gradient, ``rows`` rows of the batch at a time.
``counts(conf)``
    the work the algorithm needs: an object with at least
    ``flops_per_token()`` (forward) and ``train_flops_per_token()``
    (forward and backward), which the ``mfu*`` readers use, and whatever
    kernel counts the family's own metric readers need.  It is the traced
    context's ``ctx["shape"]``.
``source_values(cfg)``, optional
    the program config ``cfg``'s values under the source's own key names,
    ``{key: value}``, for a file that keeps them beside the program's
    fields, as a catalog model's must (``hidden_size`` for ``d_model``).
    The harness compares each such key of the file with it.

Shared pieces a family reuses, writing only its blocks: ``weights.py``
(``seed_key``, the draws, the jitted maker), ``reference.py`` (the
highest-precision and float8 matrix products ``_hi``, ``_mm_f8``, ``_mm``,
``_rmsnorm``, AdamW) and ``work.py`` (``peaks``, ``ideal_seconds``).
"""

import importlib


def load(conf: dict):
    """The model module that ``conf['model']`` names."""
    name = f"{__name__}.{conf['model']}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise SystemExit(f"configuration {conf['arch']!r} names the model "
                         f"module bench/models/{conf['model']}.py, which is "
                         f"not there") from None
