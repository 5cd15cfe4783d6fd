"""Jitted public wrappers for the chunked-scan kernels, with custom VJPs.

Two differentiable entry points share one backward structure:

  * ``linear_scan``     -- h_t = a_t h_{t-1} + b_t on linear-space inputs
    (the ``scan_strategy="pallas"``/``mode="linear"`` path);
  * ``log_space_scan``  -- same recurrence parameterised by (log a, log b)
    with the per-chunk logaddexp ladder and a log-space cross-chunk carry
    (the default ``mode="log"`` training/prefill path, numerically
    matching ``repro.core.scan.scan_log_space``).

The backward pass of h_t = a_t h_{t-1} + b_t is itself a (reversed) linear
scan:

    g_t  = dL/dh_t + a_{t+1} g_{t+1}        (reverse-scan with coeff a_{t+1})
    dL/db_t = g_t
    dL/da_t = g_t * h_{t-1}
    dL/dh0  = a_1 * g_1  ... = g_0' (the reverse carry past t=1)

and for the log parameterisation the chain rule just multiplies each grad
by the exponentiated input (d/dlog_a = a * d/da).  The reverse scan's
coefficients a_{t+1} live in (0, 1) and its values dL/dh_t are finite and
signed, so it is numerically safe in linear space: the *forward* kernel
needs log space (long products of gates underflow), the backward reuses
the linear kernel reversed.  Both directions of both entry points run the
Pallas chunked-scan kernels (compiled on a TPU, interpreted on the CPU).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.scan import kernel as _kernel


def call_with_flat_lead(fn, *specs):
    """Collapse arbitrary leading dims to one batch dim around ``fn``.

    ``specs`` are (array, n_trailing) pairs; the leading dims are taken
    from the first pair and must agree across all of them.  Used by every
    kernel wrapper (and the fused cell paths) whose Pallas grid wants a
    single (B, ...) batch axis.
    """
    x0, t0 = specs[0]
    lead = x0.shape[:-t0] if t0 else x0.shape
    if len(lead) == 1:
        return fn(*(x for x, _ in specs))
    n = math.prod(lead)
    flat = [x.reshape((n,) + x.shape[len(lead):]) for x, _ in specs]
    out = fn(*flat)
    return out.reshape(lead + out.shape[1:])


def pad_to(x, multiple, axis, value=0.0):
    """Pad ``axis`` up to a multiple with ``value``; returns (padded, size).

    Shared by every kernel wrapper (this module and the fused cell ops)
    that must round inputs up to the Pallas tile grid.
    """
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x, size
    pad = multiple - rem
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), size


def round_block_t(block_t: int, t: int) -> int:
    """Clamp the time tile for a length-t sequence: next power of two
    covering t, at least 8 (TPU sublanes), at most ``block_t``."""
    return min(block_t, max(8, 1 << (t - 1).bit_length()))


_pad_to = pad_to   # internal alias


def _run(a, b, h0, block_t, block_d, interpret):
    """Pad to tile multiples, run kernel, slice back."""
    interpret = resolve_interpret(interpret)
    t, d = a.shape[-2], a.shape[-1]
    bt = round_block_t(block_t, t)
    a_p, _ = _pad_to(a, bt, -2, 1.0)       # identity coefficient
    b_p, _ = _pad_to(b, bt, -2, 0.0)
    a_p, _ = _pad_to(a_p, block_d, -1, 1.0)
    b_p, _ = _pad_to(b_p, block_d, -1, 0.0)
    h0_p, _ = _pad_to(h0, block_d, -1, 0.0)
    out = _kernel.linear_scan_kernel(a_p, b_p, h0_p, block_t=bt,
                                     block_d=block_d, interpret=interpret)
    return out[..., :t, :d]


def reverse_scan_grads(a, dh, h, h0, block_t, block_d, interpret):
    """Shared backward core for h_t = a_t h_{t-1} + b_t.

    Runs the reverse scan g_t = dh_t + a_{t+1} g_{t+1} through the Pallas
    kernel and returns ``(g, h_prev, dh0)`` with ``dh0 = a_1 * g_1``; every
    custom VJP in this module and in the fused cell kernels derives its
    input gradients from these (dL/da = g * h_prev, dL/db = g, plus any
    chain rule for the parameterisation).  All arrays are linear-space and
    share one dtype chosen by the caller; the coefficients a live in
    (0, 1) and g is finite and signed, so linear space is safe even when
    the forward ran in log space.
    """
    # reverse scan: g_t = dh_t + a_{t+1} g_{t+1}
    a_next = jnp.concatenate(
        [a[..., 1:, :], jnp.zeros_like(a[..., :1, :])], axis=-2)
    g = _run(jnp.flip(a_next, axis=-2), jnp.flip(dh, axis=-2),
             jnp.zeros_like(h0), block_t, block_d, interpret)
    g = jnp.flip(g, axis=-2)
    h_prev = jnp.concatenate([h0[..., None, :], h[..., :-1, :]], axis=-2)
    dh0 = a[..., 0, :] * g[..., 0, :]
    return g, h_prev, dh0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def linear_scan(a: jax.Array, b: jax.Array, h0: jax.Array,
                block_t: int = 256, block_d: int = 128,
                interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable h_t = a_t h_{t-1} + b_t, Pallas-accelerated.

    a, b: (B, T, D); h0: (B, D).  Arbitrary T/D (padded to tiles).
    """
    return _run(a, b, h0, block_t, block_d, interpret)


def _fwd(a, b, h0, block_t, block_d, interpret):
    h = _run(a, b, h0, block_t, block_d, interpret)
    return h, (a, h, h0)


def _bwd(block_t, block_d, interpret, res, dh):
    a, h, h0 = res
    g, h_prev, dh0 = reverse_scan_grads(a, dh, h, h0, block_t, block_d,
                                        interpret)
    return g * h_prev, g, dh0


linear_scan.defvjp(_fwd, _bwd)


def linear_scan_auto(a: jax.Array, b: jax.Array,
                     h0: Optional[jax.Array] = None, **kw) -> jax.Array:
    """Convenience: default h0 = 0, flattens extra leading dims."""
    if h0 is None:
        h0 = jnp.zeros(a.shape[:-2] + a.shape[-1:], b.dtype)
    return call_with_flat_lead(
        lambda a_, b_, h_: linear_scan(a_, b_, h_, **kw),
        (a, 2), (b, 2), (h0, 1))


# ---------------------------------------------------------------------------
# Log-space scan (the default mode="log" training/prefill path)
# ---------------------------------------------------------------------------

def _run_log(log_a, log_b, log_h0, block_t, block_d, interpret):
    """Pad to tile multiples with the log identity (0, -inf), run, slice."""
    interpret = resolve_interpret(interpret)
    t, d = log_a.shape[-2], log_a.shape[-1]
    bt = round_block_t(block_t, t)
    la_p, _ = _pad_to(log_a, bt, -2, 0.0)         # log a = 0  <=>  a = 1
    lb_p, _ = _pad_to(log_b, bt, -2, -jnp.inf)    # log b = -inf  <=>  b = 0
    la_p, _ = _pad_to(la_p, block_d, -1, 0.0)
    lb_p, _ = _pad_to(lb_p, block_d, -1, -jnp.inf)
    lh0_p, _ = _pad_to(log_h0, block_d, -1, -jnp.inf)
    out = _kernel.log_scan_kernel(la_p, lb_p, lh0_p, block_t=bt,
                                  block_d=block_d, interpret=interpret)
    return out[..., :t, :d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def log_space_scan(log_a: jax.Array, log_b: jax.Array, log_h0: jax.Array,
                   block_t: int = 256, block_d: int = 128,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable Heinsen-style scan, Pallas-accelerated.

    h_t = exp(log_a_t) h_{t-1} + exp(log_b_t);  log_a, log_b: (B, T, D);
    log_h0: (B, D) with -inf encoding h0 = 0.  Output h is linear-space
    fp32; all in-kernel state stays in log space (see kernel.py).
    """
    return _run_log(log_a, log_b, log_h0, block_t, block_d, interpret)


def _log_fwd(log_a, log_b, log_h0, block_t, block_d, interpret):
    h = _run_log(log_a, log_b, log_h0, block_t, block_d, interpret)
    return h, (log_a, log_b, log_h0, h)


def _log_bwd(block_t, block_d, interpret, res, dh):
    log_a, log_b, log_h0, h = res
    a = jnp.exp(log_a.astype(jnp.float32))
    h0 = jnp.exp(log_h0.astype(jnp.float32))
    g, h_prev, dh0 = reverse_scan_grads(a, dh.astype(jnp.float32), h, h0,
                                        block_t, block_d, interpret)
    # chain rule through the exp parameterisation: d/dlog_x = x * d/dx
    dlog_a = (g * h_prev * a).astype(log_a.dtype)
    dlog_b = (g * jnp.exp(log_b.astype(jnp.float32))).astype(log_b.dtype)
    dlog_h0 = (dh0 * h0).astype(log_h0.dtype)
    return dlog_a, dlog_b, dlog_h0


log_space_scan.defvjp(_log_fwd, _log_bwd)


def log_space_scan_auto(log_a: jax.Array, log_b: jax.Array,
                        log_h0: Optional[jax.Array] = None, **kw
                        ) -> jax.Array:
    """Convenience: default log_h0 = -inf (h0 = 0), flattens leading dims."""
    if log_h0 is None:
        log_h0 = jnp.full(log_a.shape[:-2] + log_a.shape[-1:], -jnp.inf,
                          jnp.float32)
    return call_with_flat_lead(
        lambda a_, b_, h_: log_space_scan(a_, b_, h_, **kw),
        (log_a, 2), (log_b, 2), (log_h0, 1))
