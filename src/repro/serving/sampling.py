"""On-device vectorized token sampling for the serving engine.

One jitted call samples the whole ``(batch, vocab)`` logits matrix at once
with *per-slot* controls -- temperature, top-k, top-p and an independent RNG
key per slot -- replacing the engine v1 per-request host-side numpy loop
(one device->host transfer + one python iteration per slot per step).

Semantics (matching the common serving stacks):

  * ``temperature <= 0``  -> greedy argmax (exact, not a small-T limit).
  * ``temperature > 0``   -> categorical over ``softmax(logits / T)`` after
    the support restrictions below.
  * ``top_k > 0``    keeps the k highest logits (ties at the k-th value are
    all kept); ``top_k <= 0`` disables the filter.
  * ``top_p < 1``    keeps the smallest set of tokens whose probability mass
    reaches ``top_p`` (nucleus sampling); ``top_p >= 1`` disables it.

All controls are traced arrays, so one compiled program serves any mix of
greedy / sampled slots.  ``sample_tokens`` returns advanced keys
(`jax.random.split` per slot), making runs reproducible under a fixed
engine seed.

``lm.superstep`` calls this every device round for every slot --
including teacher-forced (prefilling) rows, whose sample is masked out
rather than skipped, so the compiled round is branch-free.  The
superstep keeps the returned key only for rows that *emit* that round:
a request's k-th output token always uses the k-th key in its slot's
chain, however many teacher-forced (and, under packed prefill,
multi-token) rounds interleave -- which is what makes seeded streams
bit-exact across ``prompt_chunk`` values.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_NEG = np.float32(-1e30)     # "removed from support" without -inf NaN risk


def validate_controls(temperature: float, top_k: int, top_p: float) -> None:
    """Reject malformed per-request sampling controls at submission time.

    The device kernels are branch-free and would silently mis-sample on
    out-of-domain controls (a negative temperature flips the softmax
    ordering, a non-positive top_p empties the nucleus), so the serving
    entry points validate here with a clear error instead.  Valid:
    ``temperature >= 0`` (0 = greedy), ``top_k >= 0`` (0 = off),
    ``0 < top_p <= 1`` (1 = off); all must be finite.
    """
    if not math.isfinite(temperature) or temperature < 0:
        raise ValueError(
            f"temperature must be finite and >= 0 (0 = greedy), "
            f"got {temperature!r}")
    if int(top_k) != top_k or top_k < 0:
        raise ValueError(
            f"top_k must be a non-negative integer (0 disables the "
            f"filter), got {top_k!r}")
    if not math.isfinite(top_p) or not 0.0 < top_p <= 1.0:
        raise ValueError(
            f"top_p must be in (0, 1] (1 disables nucleus sampling), "
            f"got {top_p!r}")


def make_keys(seed: int, batch: int) -> Array:
    """Independent per-slot PRNG keys, (batch, 2) uint32."""
    base = jax.random.PRNGKey(int(seed) % (2**31 - 1))
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(batch))


def _support_mask(logits: Array, top_k: Array, top_p: Array) -> Array:
    """Apply top-k then nucleus filtering with ONE descending sort.

    Both filters keep a *prefix* of the sorted row (top-k keeps everything
    >= the k-th value, ties included; the nucleus keeps the smallest prefix
    whose mass reaches top_p), so their intersection is a prefix too: find
    its last element and threshold the unsorted row against it.
    """
    v = logits.shape[-1]
    sorted_desc = -jnp.sort(-logits, axis=-1)

    k = jnp.clip(top_k, 1, v).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    kth = jnp.where((top_k > 0)[:, None], kth, _NEG)
    keep_k = sorted_desc >= kth                       # prefix (ties kept)

    probs = jax.nn.softmax(jnp.where(keep_k, sorted_desc, _NEG), axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    # token j (sorted) is in the nucleus iff the mass *before* it is
    # < top_p; top_p >= 1 disables explicitly (f32 cumsum saturates at 1.0,
    # which would otherwise drop tiny-probability tail tokens)
    keep_p = ((csum - probs) < top_p[:, None]) | (top_p >= 1.0)[:, None]

    count = jnp.maximum(jnp.sum(keep_k & keep_p, axis=-1), 1).astype(
        jnp.int32)
    cutoff = jnp.take_along_axis(sorted_desc, (count - 1)[:, None], axis=-1)
    return jnp.where(logits >= cutoff, logits, _NEG)


def _sample(logits: Array, keys: Array, temperature: Array,
            top_k: Array, top_p: Array):
    """One sampling round (the shared core of ``sample_tokens`` and
    ``sample_chain`` -- both MUST run the exact same ops so a chained
    position-0 sample is bit-identical to a standalone call)."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    split = jax.vmap(jax.random.split)(keys)        # (B, 2, 2)
    new_keys, use_keys = split[:, 0], split[:, 1]

    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    scaled = _support_mask(scaled, top_k, top_p)
    sampled = jax.vmap(jax.random.categorical)(use_keys, scaled
                                               ).astype(jnp.int32)
    tokens = jnp.where(temperature > 0, sampled, greedy)
    return tokens, new_keys


@jax.jit
def sample_tokens(logits: Array, keys: Array, temperature: Array,
                  top_k: Array, top_p: Array):
    """logits: (B, V); keys: (B, 2) uint32; temperature/top_p: (B,) f32;
    top_k: (B,) int32.  Returns (tokens (B,) int32, advanced keys)."""
    return _sample(logits, keys, temperature, top_k, top_p)


@jax.jit
def sample_chain(logits: Array, keys: Array, temperature: Array,
                 top_k: Array, top_p: Array):
    """Chained per-position sampling for speculative verify.

    logits: (B, W, V) -- per-position verify logits from one chunk pass.
    Position ``i`` is sampled exactly as the ``i``-th of ``W`` sequential
    ``sample_tokens`` calls would be: the key chain advances one split
    per position, so a row that commits ``e`` positions this round lands
    on the same key state as ``e`` non-speculative rounds -- which is
    what keeps seeded speculative streams bit-identical to the
    non-speculative engine (emission-aligned keys, see the module
    docstring).

    Returns ``(tokens (B, W) int32, keys_after (B, W, 2) uint32)`` where
    ``keys_after[:, i]`` is the key state after ``i + 1`` samples (the
    caller gathers the slot's new key at its last committed position;
    ``keys_after[:, 0]`` equals ``sample_tokens``'s advanced keys).
    """
    def body(k, lg):
        toks, nk = _sample(lg, k, temperature, top_k, top_p)
        return nk, (toks, nk)

    _, (toks, nks) = jax.lax.scan(body, keys, jnp.moveaxis(logits, 1, 0))
    return jnp.moveaxis(toks, 0, 1), jnp.moveaxis(nks, 0, 1)
