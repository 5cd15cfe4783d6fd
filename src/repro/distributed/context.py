"""Mesh context: lets deep layers (MoE EP, sequence-parallel scan) find the
active mesh without threading it through every apply() signature."""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
from jax.sharding import Mesh

_ACTIVE_MESH: Optional[Mesh] = None
_PURE_DP: bool = False
_SERVING_TP_AXIS: Optional[str] = None


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], pure_dp: bool = False):
    global _ACTIVE_MESH, _PURE_DP
    prev, prev_dp = _ACTIVE_MESH, _PURE_DP
    _ACTIVE_MESH, _PURE_DP = mesh, pure_dp
    try:
        yield mesh
    finally:
        _ACTIVE_MESH, _PURE_DP = prev, prev_dp


@contextlib.contextmanager
def serving_tp(axis: Optional[str]):
    """Mark the enclosed trace as running INSIDE a shard_map whose
    ``axis`` shards ``d_hidden``/``d_ff`` weight blocks (tensor-parallel
    serving).  Row-parallel projections (``blocks._row_parallel_apply``)
    consult :func:`serving_tp_axis` at trace time to decide whether their
    partial products need a ``psum`` over that axis.  ``None`` is inert
    (pure data parallelism / single device)."""
    global _SERVING_TP_AXIS
    prev = _SERVING_TP_AXIS
    _SERVING_TP_AXIS = axis
    try:
        yield axis
    finally:
        _SERVING_TP_AXIS = prev


def serving_tp_axis() -> Optional[str]:
    return _SERVING_TP_AXIS


def current_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def pure_dp() -> bool:
    return _PURE_DP


def dp_axes(mesh: Mesh):
    """Data-parallel axes: ('pod','data') on the multi-pod mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Optional[Mesh], name: str) -> int:
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: Optional[bool] = None):
    """``jax.shard_map``; ``check_vma=None`` keeps JAX's default."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
