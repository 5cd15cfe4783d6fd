"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_mingru import ops as fg_ops
from repro.kernels.fused_mingru import ref as fg_ref
from repro.kernels.scan import ops as scan_ops
from repro.kernels.scan import ref as scan_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# chunked linear scan kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    (1, 8, 128),          # exactly one tile
    (2, 64, 128),         # multiple time chunks
    (2, 100, 70),         # ragged T and D (padding path)
    (3, 7, 1),            # tiny
    (1, 300, 130),        # ragged both, > 1 tile each
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scan_kernel_matches_ref(shape, dtype):
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    k1, k2, k3 = jax.random.split(key, 3)
    a = jax.nn.sigmoid(jax.random.normal(k1, shape)).astype(dtype)
    b = jax.random.normal(k2, shape).astype(dtype)
    h0 = jax.random.normal(k3, shape[:1] + shape[2:]).astype(dtype)
    out = scan_ops.linear_scan(a, b, h0, 64, 128, True)
    ref = scan_ref.linear_scan_ref(a, b, h0)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), **_tol(dtype))


@pytest.mark.parametrize("block_t", [8, 32, 256])
def test_scan_kernel_block_sizes(block_t):
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    a = jax.nn.sigmoid(jax.random.normal(k1, (2, 96, 16)))
    b = jax.random.normal(k2, (2, 96, 16))
    h0 = jnp.zeros((2, 16))
    out = scan_ops.linear_scan(a, b, h0, block_t, 128, True)
    ref = scan_ref.linear_scan_ref(a, b, h0)
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


def test_scan_kernel_vjp_matches_ref_vjp():
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    a = jax.nn.sigmoid(jax.random.normal(k1, (2, 60, 20)))
    b = jax.random.normal(k2, (2, 60, 20))
    h0 = jax.random.normal(k3, (2, 20))

    def loss_k(args):
        return jnp.sum(scan_ops.linear_scan(*args, 32, 128, True) ** 2)

    def loss_r(args):
        return jnp.sum(scan_ref.linear_scan_ref(*args) ** 2)

    gk = jax.grad(loss_k)((a, b, h0))
    gr = jax.grad(loss_r)((a, b, h0))
    for x, y in zip(gk, gr):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-4)


def test_scan_kernel_long_sequence():
    """Many sequential chunks exercise the VMEM carry path."""
    key = jax.random.PRNGKey(2)
    k1, k2 = jax.random.split(key)
    a = jax.nn.sigmoid(jax.random.normal(k1, (1, 2048, 8)))
    b = jax.random.normal(k2, (1, 2048, 8))
    h0 = jnp.zeros((1, 8))
    out = scan_ops.linear_scan(a, b, h0, 128, 128, True)
    ref = scan_ref.linear_scan_ref(a, b, h0)
    np.testing.assert_allclose(out, ref, rtol=5e-5, atol=5e-5)


# ---------------------------------------------------------------------------
# fused minGRU kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    (2, 32, 16, 128),     # (B, T, Dx, Dh) aligned
    (2, 50, 24, 40),      # ragged
    (1, 8, 8, 8),         # tiny
])
@pytest.mark.parametrize("mode", ["log", "linear"])
def test_fused_mingru_matches_ref(shape, mode):
    bsz, t, dx, dh = shape
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (bsz, t, dx))
    wz = jax.random.normal(ks[1], (dx, dh)) * 0.2
    wh = jax.random.normal(ks[2], (dx, dh)) * 0.2
    bz = jax.random.normal(ks[3], (dh,)) * 0.1
    bh = jnp.zeros((dh,))
    out = fg_ops.fused_mingru(x, wz, bz, wh, bh, mode=mode, interpret=True)
    ref = fg_ref.fused_mingru_ref(x, wz, bz, wh, bh, mode=mode)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_mingru_dtypes(dtype):
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (2, 16, 32)).astype(dtype)
    wz = (jax.random.normal(ks[1], (32, 128)) * 0.2).astype(dtype)
    wh = (jax.random.normal(ks[2], (32, 128)) * 0.2).astype(dtype)
    out = fg_ops.fused_mingru(x, wz, None, wh, None, interpret=True)
    ref = fg_ref.fused_mingru_ref(
        x.astype(jnp.float32), wz.astype(jnp.float32), jnp.zeros(128),
        wh.astype(jnp.float32), jnp.zeros(128))
    np.testing.assert_allclose(out.astype(jnp.float32), ref, **_tol(dtype))


def test_fused_mingru_matches_layer():
    """Kernel output == the model-layer (min_gru.parallel) output."""
    from repro.core import min_gru
    params = min_gru.init(jax.random.PRNGKey(4), 16, 24)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 20, 16))
    layer = min_gru.parallel(params, x, mode="log")
    out = fg_ops.fused_mingru(
        x, params["wz"]["kernel"], params["wz"]["bias"],
        params["wh"]["kernel"], params["wh"]["bias"], mode="log",
        interpret=True)
    np.testing.assert_allclose(out, layer, rtol=3e-4, atol=3e-4)


def test_mingru_layer_pallas_strategy_matches_associative():
    """The model-layer kernel path: min_gru.parallel(strategy='pallas')."""
    from repro.core import min_gru
    params = min_gru.init(jax.random.PRNGKey(6), 12, 20)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 33, 12))
    ref = min_gru.parallel(params, x, mode="linear",
                           scan_strategy="associative")
    out = min_gru.parallel(params, x, mode="linear", scan_strategy="pallas")
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


def test_minlstm_layer_pallas_strategy_matches_associative():
    from repro.core import min_lstm
    params = min_lstm.init(jax.random.PRNGKey(8), 12, 20)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 33, 12))
    ref = min_lstm.parallel(params, x, mode="linear",
                            scan_strategy="associative")
    out = min_lstm.parallel(params, x, mode="linear",
                            scan_strategy="pallas")
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


def test_pallas_scan_trains():
    """Gradient flows through the kernel's custom VJP in a real layer."""
    from repro.core import min_gru
    params = min_gru.init(jax.random.PRNGKey(10), 8, 8)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 16, 8))

    def loss(p):
        h = min_gru.parallel(p, x, mode="linear", scan_strategy="pallas")
        return jnp.mean(h ** 2)

    def loss_ref(p):
        h = min_gru.parallel(p, x, mode="linear",
                             scan_strategy="associative")
        return jnp.mean(h ** 2)

    g = jax.grad(loss)(params)
    g_ref = jax.grad(loss_ref)(params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# log-space scan kernel (the default mode="log" hot path)
# ---------------------------------------------------------------------------

def _log_case(key, shape):
    k1, k2, k3 = jax.random.split(key, 3)
    a = jax.nn.sigmoid(jax.random.normal(k1, shape))
    b = jnp.exp(jax.random.normal(k2, shape) * 0.5)       # b > 0 (g())
    h0 = jnp.exp(jax.random.normal(k3, shape[:1] + shape[2:]) * 0.5)
    return jnp.log(a), jnp.log(b), jnp.log(h0)


@pytest.mark.parametrize("shape", [
    (1, 8, 128),          # exactly one tile
    (2, 64, 128),         # multiple time chunks
    (2, 100, 70),         # ragged T and D (identity (0,-inf) padding path)
    (3, 7, 1),            # tiny
    (1, 300, 130),        # ragged both, > 1 tile each
])
def test_log_scan_kernel_matches_scan_log_space(shape):
    from repro.core import scan as scan_lib
    la, lb, lh0 = _log_case(jax.random.PRNGKey(hash(shape) % 2**31), shape)
    out = scan_ops.log_space_scan(la, lb, lh0, 64, 128, True)
    ref = scan_lib.scan_log_space(la, lb, lh0)
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


def test_log_scan_kernel_zero_h0_is_neg_inf():
    """-inf log_h0 (h0 = 0) flows through the logaddexp ladder cleanly."""
    from repro.core import scan as scan_lib
    la, lb, _ = _log_case(jax.random.PRNGKey(0), (2, 50, 20))
    out = scan_ops.log_space_scan_auto(la, lb)           # fills -inf
    ref = scan_lib.scan_log_space(la, lb)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


def test_log_scan_kernel_saturated_gates_stable():
    """Saturated gates (|preact| ~ 40): long products of a_t underflow any
    linear-space carry; the log-space carry must stay finite and match the
    associative Heinsen scan.

    Tolerance note: the Heinsen reference materialises the *global* cumsum
    of log_a (~ -40*T), whose fp32 ulp alone is ~2e-3 by T=512 -- the
    kernel only ever holds per-chunk cumulants, so it is the more accurate
    of the two; the comparison bounds their divergence, not kernel error.
    """
    from repro.core import scan as scan_lib
    k = jnp.full((1, 512, 8), 40.0)
    log_a = -jax.nn.softplus(k)          # log sigma(-k) ~ -40
    log_b = -jax.nn.softplus(-k) + 0.3
    out = scan_ops.log_space_scan_auto(log_a, log_b, block_t=64)
    ref = scan_lib.scan_log_space(log_a, log_b)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


def test_log_scan_kernel_vjp_matches_scan_log_space_grad():
    from repro.core import scan as scan_lib
    la, lb, lh0 = _log_case(jax.random.PRNGKey(1), (2, 60, 20))

    def loss_k(args):
        return jnp.sum(scan_ops.log_space_scan(*args, 32, 128, True) ** 2)

    def loss_r(args):
        return jnp.sum(scan_lib.scan_log_space(*args) ** 2)

    gk = jax.grad(loss_k)((la, lb, lh0))
    gr = jax.grad(loss_r)((la, lb, lh0))
    # dlog_a couples to h_{t-1}, whose fp32 rounding differs between the
    # chunked kernel and the associative reference -- scale-relative 1e-3
    for x, y in zip(gk, gr):
        scale = np.maximum(np.abs(np.asarray(y)), 1.0)
        np.testing.assert_allclose(np.asarray(x) / scale,
                                   np.asarray(y) / scale,
                                   rtol=1e-3, atol=1e-3)


def test_mingru_layer_log_pallas_strategy_matches_associative():
    """mode='log' + strategy='pallas' routes through the log kernel."""
    from repro.core import min_gru
    params = min_gru.init(jax.random.PRNGKey(12), 12, 20)
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 33, 12))
    ref = min_gru.parallel(params, x, mode="log",
                           scan_strategy="associative")
    out = min_gru.parallel(params, x, mode="log", scan_strategy="pallas")
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# Numerical drift: why the log-space kernel exists (min_gru.gates() docs)
# ---------------------------------------------------------------------------

def test_log_vs_linear_bf16_drift_at_4096():
    """At T=4096 a bf16 linear-space scan of (1-z, z*g(v)) drifts visibly
    from the fp32 log-space reference, while the Pallas log kernel (fp32
    logaddexp ladder, log-space carry) stays tight -- the two
    parameterisations are mathematically identical (see min_gru.gates),
    so the gap is purely accumulated rounding, i.e. the kernel's
    rescaling is both needed and correct."""
    from repro.core import scan as scan_lib
    key = jax.random.PRNGKey(2)
    k1, k2 = jax.random.split(key)
    shape = (1, 4096, 128)
    a = jax.nn.sigmoid(jax.random.normal(k1, shape) * 0.5)
    b = jnp.exp(jax.random.normal(k2, shape) * 0.3)
    with _x64():     # fp64 sequential scan: the actual ground truth
        ref = np.asarray(scan_lib.scan_sequential(
            jnp.asarray(np.asarray(a), jnp.float64),
            jnp.asarray(np.asarray(b), jnp.float64)))

    lin_bf16 = np.asarray(scan_lib.scan_associative(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)).astype(jnp.float32))
    pallas_log = np.asarray(scan_ops.log_space_scan_auto(jnp.log(a),
                                                         jnp.log(b)))

    err_bf16 = float(np.max(np.abs(lin_bf16 - ref) / (np.abs(ref) + 1)))
    err_pallas = float(np.max(np.abs(pallas_log - ref) / (np.abs(ref) + 1)))
    # measured: pallas ~2e-7, bf16 linear ~1e-2 (and even the fp32 Heinsen
    # associative form sits at ~4e-4 -- the chunked kernel never
    # materialises the global cumsum, so it beats both)
    assert err_pallas < 1e-5, err_pallas
    assert err_bf16 > 1e-3, err_bf16


# ---------------------------------------------------------------------------
# Gradchecks against jax.grad of the sequential oracle (fp64)
# ---------------------------------------------------------------------------

def _x64():
    return jax.enable_x64(True)


def test_linear_scan_gradcheck_vs_sequential_fp64():
    """Kernel VJP vs jax.grad of the fp64 sequential scan: odd T/D,
    nonzero h0."""
    from repro.core import scan as scan_lib
    with _x64():
        key = jax.random.PRNGKey(3)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        shape = (2, 37, 5)
        a = jax.nn.sigmoid(jax.random.normal(k1, shape, jnp.float64))
        b = jax.random.normal(k2, shape, jnp.float64)
        h0 = jax.random.normal(k3, shape[:1] + shape[2:], jnp.float64)
        ct = jax.random.normal(k4, shape, jnp.float64)

        def loss_k(args):
            return jnp.sum(scan_ops.linear_scan(*args, 16, 128, True) * ct)

        def loss_r(args):
            return jnp.sum(scan_lib.scan_sequential(*args) * ct)

        gk = jax.grad(loss_k)((a, b, h0))
        gr = jax.grad(loss_r)((a, b, h0))
        for x, y in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cell_name", ["mingru", "minlstm"])
@pytest.mark.parametrize("mode", ["log", "linear"])
def test_fused_gradcheck_vs_sequential_fp64(cell_name, mode):
    """Fused-kernel VJPs vs jax.grad of the fp64 sequential rollout: odd
    T/D, nonzero h0, gradients into params, x AND the carried h0."""
    from repro.core import min_gru, min_lstm, nn
    cell = {"mingru": min_gru, "minlstm": min_lstm}[cell_name]
    with _x64():
        params = cell.init(jax.random.PRNGKey(5), 7, 11)
        params = jax.tree.map(lambda p: p.astype(jnp.float64), params)
        x = jax.random.normal(jax.random.PRNGKey(6), (2, 23, 7),
                              jnp.float64)
        h0 = nn.g(jax.random.normal(jax.random.PRNGKey(7), (2, 11),
                                    jnp.float64))

        def loss_fused(p, x, h0):
            h = cell.parallel(p, x, h0, mode=mode, scan_strategy="fused")
            return jnp.mean(h ** 2)

        def loss_ref(p, x, h0):
            hs = []
            h = h0
            for t in range(x.shape[-2]):
                h = cell.step(p, x[..., t, :], h, mode=mode)
                hs.append(h)
            return jnp.mean(jnp.stack(hs, axis=-2) ** 2)

        gf = jax.grad(loss_fused, argnums=(0, 1, 2))(params, x, h0)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(params, x, h0)
        for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fused minLSTM kernel
# ---------------------------------------------------------------------------

from repro.kernels.fused_minlstm import ops as fl_ops
from repro.kernels.fused_minlstm import ref as fl_ref


def _minlstm_case(key, bsz, t, dx, dh):
    ks = jax.random.split(key, 7)
    x = jax.random.normal(ks[0], (bsz, t, dx))
    ws = [jax.random.normal(k, (dx, dh)) * 0.2 for k in ks[1:4]]
    bs = [jax.random.normal(k, (dh,)) * 0.1 for k in ks[4:7]]
    return x, ws, bs


@pytest.mark.parametrize("shape", [
    (2, 32, 16, 128),     # (B, T, Dx, Dh) aligned
    (2, 50, 24, 40),      # ragged
    (1, 8, 8, 8),         # tiny
])
@pytest.mark.parametrize("mode", ["log", "linear"])
@pytest.mark.parametrize("normalize", [True, False])
def test_fused_minlstm_matches_ref(shape, mode, normalize):
    bsz, t, dx, dh = shape
    x, (wf, wi, wh), (bf, bi, bh) = _minlstm_case(
        jax.random.PRNGKey(hash(shape) % 2**31), bsz, t, dx, dh)
    out = fl_ops.fused_minlstm(x, wf, bf, wi, bi, wh, bh, mode=mode,
                               normalize=normalize, interpret=True)
    ref = fl_ref.fused_minlstm_ref(x, wf, bf, wi, bi, wh, bh, mode=mode,
                                   normalize=normalize)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


def test_fused_minlstm_matches_layer():
    from repro.core import min_lstm
    params = min_lstm.init(jax.random.PRNGKey(14), 16, 24)
    x = jax.random.normal(jax.random.PRNGKey(15), (2, 20, 16))
    layer = min_lstm.parallel(params, x, mode="log")
    out = min_lstm.parallel(params, x, mode="log", scan_strategy="fused")
    np.testing.assert_allclose(out, layer, rtol=3e-4, atol=3e-4)


def test_fused_minlstm_normalize_saturated_gates_finite():
    """f/(f+i) hits 0/0 = NaN when both sigmoids underflow (pre-activations
    below ~-104 in fp32); the stable normalized_gates form must keep the
    fused default path finite and matching the log-space associative scan
    in both forward and backward."""
    from repro.core import min_lstm
    dx, dh = 4, 8
    x = jnp.ones((1, 12, dx))
    params = {
        "wf": {"kernel": jnp.zeros((dx, dh)), "bias": jnp.full((dh,), -480.0)},
        "wi": {"kernel": jnp.zeros((dx, dh)), "bias": jnp.full((dh,), -480.0)},
        "wh": {"kernel": jax.random.normal(jax.random.PRNGKey(0),
                                           (dx, dh)) * 0.2},
    }
    ref = min_lstm.parallel(params, x, mode="log",
                            scan_strategy="associative")
    out = min_lstm.parallel(params, x, mode="log", scan_strategy="fused")
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)

    def loss(p):
        return jnp.mean(min_lstm.parallel(p, x, mode="log",
                                          scan_strategy="fused") ** 2)

    grads = jax.grad(loss)(params)
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))


# ---------------------------------------------------------------------------
# fused vs unfused parity across tilings (both cells, both modes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell_name", ["mingru", "minlstm"])
@pytest.mark.parametrize("mode", ["log", "linear"])
@pytest.mark.parametrize("block_t,block_dh", [
    (8, 128),
    (32, 128),
    (64, 256),
    (256, 128),       # default
])
def test_fused_vs_unfused_forward_parity_tilings(cell_name, mode, block_t,
                                                 block_dh):
    from repro.core import min_gru, min_lstm
    from repro.kernels.fused_mingru import ops as fg
    cell = {"mingru": min_gru, "minlstm": min_lstm}[cell_name]
    params = cell.init(jax.random.PRNGKey(block_t + block_dh), 10, 36)
    x = jax.random.normal(jax.random.PRNGKey(16), (2, 45, 10))
    ref = cell.parallel(params, x, mode=mode, scan_strategy="associative")
    if cell_name == "mingru":
        out = fg.fused_mingru(
            x, params["wz"]["kernel"], params["wz"]["bias"],
            params["wh"]["kernel"], params["wh"]["bias"], mode=mode,
            block_t=block_t, block_dh=block_dh, interpret=True)
    else:
        out = fl_ops.fused_minlstm(
            x, params["wf"]["kernel"], params["wf"]["bias"],
            params["wi"]["kernel"], params["wi"]["bias"],
            params["wh"]["kernel"], params["wh"]["bias"], mode=mode,
            block_t=block_t, block_dh=block_dh, interpret=True)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


def test_fused_carried_h0_composes_like_chunked_prefill():
    """Fused(x[:s], h0) then fused(x[s:], carry) == fused(x) -- the chunked
    prefill / carried-state contract of the engine's prefill path."""
    from repro.core import min_gru
    params = min_gru.init(jax.random.PRNGKey(17), 8, 16)
    x = jax.random.normal(jax.random.PRNGKey(18), (2, 24, 8))
    full = min_gru.parallel(params, x, mode="log", scan_strategy="fused")
    s = 11
    h_a = min_gru.parallel(params, x[:, :s], mode="log",
                           scan_strategy="fused")
    h_b = min_gru.parallel(params, x[:, s:], h_a[:, -1], mode="log",
                           scan_strategy="fused")
    np.testing.assert_allclose(jnp.concatenate([h_a, h_b], axis=1), full,
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# default dispatch: cfg.scan_strategy="auto" actually hits the kernels
# ---------------------------------------------------------------------------

def test_lm_default_dispatch_hits_fused_kernel(monkeypatch):
    """mingru_lm forward+backward run through the fused Pallas kernel by
    default (auto -> fused; interpret mode on CPU)."""
    from repro.configs import archs
    from repro.kernels.fused_mingru import ops as fg
    from repro.models import lm

    calls = {"n": 0}
    real = fg.fused_mingru

    def spy(*args, **kw):
        calls["n"] += 1
        return real(*args, **kw)

    monkeypatch.setattr(fg, "fused_mingru", spy)
    cfg = archs.smoke("mingru-lm")
    assert cfg.scan_strategy == "auto"
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    batch = {
        "tokens": jnp.zeros((1, 8), jnp.int32),
        "labels": jnp.zeros((1, 8), jnp.int32),
    }
    grads = jax.grad(lambda p: lm.loss_fn(p, cfg, batch)[0])(params)
    assert calls["n"] > 0
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


# ---------------------------------------------------------------------------
# interpret mode is decided per call, never at import
# ---------------------------------------------------------------------------

def test_resolve_interpret_follows_the_backend(monkeypatch):
    from repro import kernels
    assert kernels.resolve_interpret(True) is True
    assert kernels.resolve_interpret(False) is False
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert kernels.resolve_interpret(None) is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="TPU"):
        kernels.resolve_interpret(None)


def test_model_and_engine_import_initialise_no_backend():
    """Importing the model stack must leave the device choice open: a
    launcher can still force virtual devices, and no kernel module fixes
    interpret mode before a backend exists."""
    import os
    import subprocess
    import sys
    code = ("from jax._src import xla_bridge as xb\n"
            "import repro.models.lm, repro.serving.engine\n"
            "import repro.launch.serve, repro.launch.train\n"
            "assert not xb.backends_are_initialized()\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
