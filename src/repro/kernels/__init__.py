"""Pallas TPU kernels for the minGRU / minLSTM hot paths.

Each package holds ``kernel.py`` (the ``pallas_call``), ``ops.py`` (the
padded, differentiable wrappers the model calls) and ``ref.py`` (a
pure-jnp oracle where one exists).
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Whether a kernel call runs in the Pallas interpreter.

    ``None`` (every wrapper's default) decides when the kernel is called,
    from the default backend: compiled on a TPU, interpreted on the CPU
    (the test path).  Any other backend is refused -- these kernels are
    written for Mosaic, and there is no silent fallback.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"the default backend is {backend!r}")
