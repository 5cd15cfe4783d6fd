"""Direct unit tests for repro.distributed.context: the version-portable
shard_map wrapper, axis introspection helpers and the serving-TP trace
context.  These run in-process under the conftest multi-device harness
(REPRO_FORCE_DEVICES, default 8 virtual CPU devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import context as mesh_ctx


def _need_devices(n: int):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (REPRO_FORCE_DEVICES)")


# ---------------------------------------------------------------------------
# shard_map wrapper
# ---------------------------------------------------------------------------

def test_shard_map_wrapper_runs_sharded():
    _need_devices(4)
    mesh = jax.make_mesh((4,), ("data",))
    x = jnp.arange(8.0)
    fn = mesh_ctx.shard_map(lambda v: v * 2.0, mesh=mesh,
                            in_specs=P("data"), out_specs=P("data"))
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(x)),
                                  np.asarray(x) * 2.0)


def test_shard_map_wrapper_check_vma_kw():
    """check_vma=False must be accepted and still produce correct output
    (it maps to check_rep on older jax)."""
    _need_devices(2)
    mesh = jax.make_mesh((2,), ("data",))
    x = jnp.arange(4.0)

    def body(v):
        return jax.lax.psum(v.sum(), "data") * jnp.ones_like(v)

    fn = mesh_ctx.shard_map(body, mesh=mesh, in_specs=P("data"),
                            out_specs=P("data"), check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(fn)(x)),
                               np.full(4, 6.0))


def test_shard_map_wrapper_new_jax_branch(monkeypatch):
    """The wrapper calls jax.shard_map and passes check_vma through."""
    _need_devices(2)
    real = jax.shard_map
    seen = {}

    def fake_shard_map(f, *, mesh, in_specs, out_specs, **kw):
        seen.update(kw)
        kw.pop("check_vma", None)
        return real(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)

    monkeypatch.setattr(jax, "shard_map", fake_shard_map, raising=False)
    mesh = jax.make_mesh((2,), ("data",))
    fn = mesh_ctx.shard_map(lambda v: v + 1.0, mesh=mesh,
                            in_specs=P("data"), out_specs=P("data"),
                            check_vma=False)
    out = jax.jit(fn)(jnp.zeros(4))
    np.testing.assert_array_equal(np.asarray(out), np.ones(4))
    assert seen == {"check_vma": False}


# ---------------------------------------------------------------------------
# axis helpers
# ---------------------------------------------------------------------------

def test_axis_size_and_dp_axes():
    _need_devices(4)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    assert mesh_ctx.axis_size(mesh, "data") == 2
    assert mesh_ctx.axis_size(mesh, "model") == 2
    assert mesh_ctx.axis_size(mesh, "pod") == 1       # absent axis -> 1
    assert mesh_ctx.axis_size(None, "data") == 1      # no mesh -> 1
    assert mesh_ctx.dp_axes(mesh) == ("data",)
    pod = jax.make_mesh((2, 2, 1), ("pod", "data", "model"))
    assert mesh_ctx.dp_axes(pod) == ("pod", "data")


def test_use_mesh_nesting_restores():
    _need_devices(2)
    mesh = jax.make_mesh((2,), ("data",))
    assert mesh_ctx.current_mesh() is None
    with mesh_ctx.use_mesh(mesh, pure_dp=True):
        assert mesh_ctx.current_mesh() is mesh
        assert mesh_ctx.pure_dp()
        with mesh_ctx.use_mesh(None):
            assert mesh_ctx.current_mesh() is None
        assert mesh_ctx.current_mesh() is mesh
    assert mesh_ctx.current_mesh() is None
    assert not mesh_ctx.pure_dp()


# ---------------------------------------------------------------------------
# serving-TP trace context
# ---------------------------------------------------------------------------

def test_serving_tp_context_restores_on_error():
    assert mesh_ctx.serving_tp_axis() is None
    with mesh_ctx.serving_tp("model"):
        assert mesh_ctx.serving_tp_axis() == "model"
        with mesh_ctx.serving_tp(None):
            assert mesh_ctx.serving_tp_axis() is None
        assert mesh_ctx.serving_tp_axis() == "model"
    assert mesh_ctx.serving_tp_axis() is None
    with pytest.raises(RuntimeError):
        with mesh_ctx.serving_tp("model"):
            raise RuntimeError("boom")
    assert mesh_ctx.serving_tp_axis() is None


def test_row_parallel_apply_psums_under_tp():
    """blocks._row_parallel_apply: identity without the context or for a
    full-width kernel; psum of block partials under the context."""
    _need_devices(2)
    from repro.core import blocks

    mesh = jax.make_mesh((2,), ("model",))
    full = jax.random.normal(jax.random.PRNGKey(0), (8, 5))
    bias = jax.random.normal(jax.random.PRNGKey(1), (5,))
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 8))
    p = {"kernel": full, "bias": bias}
    ref = x @ full + bias

    # no context: plain dense
    np.testing.assert_allclose(
        np.asarray(blocks._row_parallel_apply(p, x, None, 8)), np.asarray(ref),
        rtol=1e-6)

    # under the context, a sharded kernel psums its partials; bias is
    # added once AFTER the reduction (not once per shard)
    def body(k, xs):
        with mesh_ctx.serving_tp("model"):
            return blocks._row_parallel_apply(
                {"kernel": k, "bias": bias}, xs, None, 8)

    fn = mesh_ctx.shard_map(body, mesh=mesh,
                            in_specs=(P("model", None), P(None, "model")),
                            out_specs=P(), check_vma=False)
    out = jax.jit(fn)(full, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)

    # full-width kernel under the context: no psum needed, stays dense
    def body_full(xs):
        with mesh_ctx.serving_tp("model"):
            return blocks._row_parallel_apply(p, xs, None, 8)

    fn2 = mesh_ctx.shard_map(body_full, mesh=mesh, in_specs=P(),
                             out_specs=P(), check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(fn2)(x)),
                               np.asarray(ref), rtol=1e-6)
