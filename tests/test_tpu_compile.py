"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: JAX's TPU compiler builds each kernel for a v5e
topology that is described, not attached.  This catches what interpret
mode cannot see -- block shapes that break the TPU tiling rules, dots
Mosaic cannot lower, kernels that overflow VMEM -- at the published
widths of the paper's LMs (``mingru-lm`` / ``minlstm-lm``: d_model 768,
d_hidden 1536, d_ff 3072, bf16), serving batch 64 and training shape
8 x 2048.  Each test asserts that the compiled program holds the kernel
(``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler library.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import archs
from repro.core import blocks
from repro.kernels.block_step import ops as block_ops
from repro.kernels.decode_step import ops as step_ops
from repro.kernels.fused_mingru import ops as fg_ops
from repro.kernels.fused_minlstm import ops as fl_ops
from repro.kernels.scan import ops as scan_ops
from repro.models import lm

BF16 = jnp.bfloat16
SERVE_B = 64
TRAIN_B, TRAIN_T = 8, 2048
CHUNK = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise writes its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _lm_block_cfg(arch: str) -> blocks.MinRNNBlockConfig:
    return lm._minrnn_block_cfg(archs.get(arch))


def _compile_text(fn, one_chip, *shapes) -> str:
    """Lower + compile ``fn`` for one described v5e chip from shapes."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _s(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# training / prefill kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("form", ["linear", "log"])
def test_scan_compiles(one_chip, form):
    d = _lm_block_cfg("mingru-lm").d_hidden
    run = scan_ops.linear_scan if form == "linear" else scan_ops.log_space_scan
    fn = functools.partial(run, interpret=False)
    _compile_text(fn, one_chip, _s((TRAIN_B, TRAIN_T, d), jnp.float32),
                  _s((TRAIN_B, TRAIN_T, d), jnp.float32),
                  _s((TRAIN_B, d), jnp.float32))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("arch", ["mingru-lm", "minlstm-lm"])
def test_fused_cell_compiles(one_chip, arch, grad):
    cfg = _lm_block_cfg(arch)
    dx, dh = cfg.d_model, cfg.d_hidden
    n_gates = 2 if cfg.cell == "mingru" else 3
    op = fg_ops.fused_mingru if cfg.cell == "mingru" else fl_ops.fused_minlstm

    def fwd(x, h0, *wb):
        return op(x, *wb, h0, mode=cfg.mode, interpret=False)

    fn = fwd
    if grad:
        fn = jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                      argnums=(0, 2))
    wb = [_s((dx, dh)), _s((dh,))] * n_gates
    _compile_text(fn, one_chip, _s((TRAIN_B, TRAIN_T, dx)),
                  _s((TRAIN_B, dh)), *wb)


# ---------------------------------------------------------------------------
# serving kernels: cell step / chunk and whole-block step / chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("form", ["step", "chunk"])
@pytest.mark.parametrize("arch", ["mingru-lm", "minlstm-lm"])
def test_decode_step_compiles(one_chip, arch, form):
    cfg = _lm_block_cfg(arch)
    dx, dh = cfg.d_model, cfg.d_hidden
    n_gates = 2 if cfg.cell == "mingru" else 3
    wb = [_s((dx, dh)), _s((dh,))] * n_gates
    if form == "step":
        op = step_ops.fused_mingru_step if cfg.cell == "mingru" \
            else step_ops.fused_minlstm_step
        _compile_text(
            lambda x, h, *w: op(x, *w, h, mode=cfg.mode, interpret=False),
            one_chip, _s((SERVE_B, dx)), _s((SERVE_B, dh)), *wb)
    else:
        op = step_ops.fused_mingru_chunk if cfg.cell == "mingru" \
            else step_ops.fused_minlstm_chunk
        _compile_text(
            lambda x, h, v, *w: op(x, *w, h, v, mode=cfg.mode,
                                   interpret=False),
            one_chip, _s((SERVE_B, CHUNK, dx)), _s((SERVE_B, dh)),
            _s((SERVE_B,), jnp.int32), *wb)


@pytest.mark.parametrize("form", ["step", "chunk"])
@pytest.mark.parametrize("arch", ["mingru-lm", "minlstm-lm"])
def test_block_step_compiles(one_chip, arch, form):
    cfg = _lm_block_cfg(arch)
    params = jax.eval_shape(
        lambda: blocks.init(jax.random.PRNGKey(0), cfg, dtype=BF16))
    state = jax.eval_shape(lambda: blocks.init_state(cfg, (SERVE_B,), BF16))
    kw = dict(cell=cfg.cell, mode=cfg.mode, use_conv=cfg.use_conv,
              use_mlp=cfg.use_mlp, compute_dtype=BF16, interpret=False)
    if form == "step":
        _compile_text(
            lambda p, x, s: block_ops.fused_block_step(p, x, s, **kw),
            one_chip, params, _s((SERVE_B, cfg.d_model)), state)
    else:
        _compile_text(
            lambda p, x, s, v: block_ops.fused_block_chunk(p, x, s, v, **kw),
            one_chip, params, _s((SERVE_B, CHUNK, cfg.d_model)), state,
            _s((SERVE_B,), jnp.int32))
