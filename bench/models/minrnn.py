"""Plain float32 reference of the paper's minGRU and minLSTM language models.

Written from Feng et al. 2024, "Were RNNs All We Needed?" (arXiv:2410.01201,
sections 3.1, 3.2 and App. C) and the residual block layout of
``core/blocks.py``; it imports nothing of the program.  Every matrix
product runs at ``precision="highest"`` (on a TPU a float32 product is
otherwise rounded to bfloat16).  The configuration's ``minrnn`` group
picks the cell.

Per layer, for input x (B, T, d):

    y  = RMSNorm(x) * scale                          eps 1e-6
    y  = causal depthwise conv, 4 taps, plus bias    zero left padding
    minGRU:  z = sigmoid(y Wz + bz),  h~ = g(y Wh + bh)
             h_t = (1 - z_t) h_{t-1} + z_t h~_t
    minLSTM: f = sigmoid(y Wf + bf), i = sigmoid(y Wi + bi), h~ = g(y Wh + bh)
             h_t = f/(f+i) h_{t-1} + i/(f+i) h~_t
    x  = x + h Wdown
    x  = x + gelu(RMSNorm(x) Win + bin) Wout + bout  (tanh-form gelu)

with h_{-1} = 0 and g(v) = v + 1/2 for v >= 0, sigmoid(v) otherwise.  The
logits are RMSNorm(x) times the tied embedding table.

Departures, each equal in exact arithmetic:
  * the paper's App. B scans in log space; this one scans (a, b) linearly
    with ``lax.associative_scan`` in float32, where the gates in (0, 1)
    keep it stable;
  * minLSTM's f/(f+i) is taken as written, not through Algorithm 8's
    softplus form.

``control=True`` computes every matrix product in float8 (``reference._mm``):
the reference at the next precision below the configuration's bfloat16,
used to show that the benchmark's comparison fails a lower-precision
program.

The weights' layout is the one the program's ``lm.init_params`` builds
for ``block_kind="minrnn"``; the drivers check the two trees agree leaf
for leaf before a run.  The two projections that write into the residual
stream (``down`` and ``mlp_out``) are drawn centred over their inputs
(``weights.py``'s ``residual`` draw).  The gelu and the minRNN's g() have
positive means, so with plain draws every layer adds one constant vector
to the stream; twelve of them swamp the tokens, and the logits barely
depend on the context (on seed 13 of ``minlstm-lm``, 3 distinct greedy
tokens over 320 corpus positions, against 69 with centred draws).  Greedy
requests then loop on one context, and a check of served tokens sees a
lower precision only on the seeds whose constant logits happen to hold a
near-tie.

``counts`` gives the work of a minRNN LM (``Shape``): only matrix products
count as operations (2 per multiply-add); the elementwise gates, the 4-tap
convolution, the norms and the scan are left out, so a share is an
under-statement by their small part.  Bytes are what must cross HBM: each
weight once per device round, plus the carried recurrent state read and
written once per round.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from reference import _mm, _rmsnorm

_GATES = {"mingru": ("wz", "wh"), "minlstm": ("wf", "wi", "wh")}


# ---------------------------------------------------------------------------
# The parameter tree
# ---------------------------------------------------------------------------

def layout(conf: dict) -> dict:
    """{path tuple: (shape, kind)} for every leaf; kind picks the draw."""
    mr = conf["minrnn"]
    L, d, ff, v = (conf["n_layers"], conf["d_model"], conf["d_ff"],
                   conf["vocab_size"])
    dh = int(d * mr["expansion"])
    vp = -(-v // 128) * 128
    out = {("embed", "table"): ((vp, d), "embed"),
           ("final_norm", "scale"): ((d,), "scale")}
    blk = {("norm_rnn", "scale"): ((d,), "scale"),
           ("down", "kernel"): ((dh, d), "residual"),
           ("conv", "kernel"): ((mr["conv_kernel"], d), "conv"),
           ("conv", "bias"): ((d,), "bias"),
           ("norm_mlp", "scale"): ((d,), "scale"),
           ("mlp_in", "kernel"): ((d, ff), "dense"),
           ("mlp_in", "bias"): ((ff,), "bias"),
           ("mlp_out", "kernel"): ((ff, d), "residual"),
           ("mlp_out", "bias"): ((d,), "bias")}
    for g in _GATES[mr["cell"]]:
        blk[("rnn", g, "kernel")] = ((d, dh), "dense")
        blk[("rnn", g, "bias")] = ((dh,), "bias")
    for path, (shape, kind) in blk.items():
        out[("layers", "blocks") + path] = ((L,) + shape, kind)
    return out


# ---------------------------------------------------------------------------
# The reference forward pass, loss and gradient
# ---------------------------------------------------------------------------

def _g(v):
    return jnp.where(v >= 0, v + 0.5, jax.nn.sigmoid(v))


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _linear_scan(a, b):
    """h_t = a_t h_{t-1} + b_t along axis 1, h_{-1} = 0."""
    def combine(l, r):
        return l[0] * r[0], r[0] * l[1] + r[1]
    return lax.associative_scan(combine, (a, b), axis=1)[1]


def _block(p, x, cell, control):
    y = _rmsnorm(x, p["norm_rnn"]["scale"])
    k = p["conv"]["kernel"]                          # (taps, d)
    taps = k.shape[0]
    yp = jnp.pad(y, ((0, 0), (taps - 1, 0), (0, 0)))
    t = y.shape[1]
    y = sum(yp[:, i:i + t] * k[i] for i in range(taps)) + p["conv"]["bias"]
    r = p["rnn"]

    def proj(name):
        return _mm(y, r[name]["kernel"], control) + r[name]["bias"]

    h_tilde = _g(proj("wh"))
    if cell == "mingru":
        z = jax.nn.sigmoid(proj("wz"))
        a, b = 1.0 - z, z * h_tilde
    else:
        f = jax.nn.sigmoid(proj("wf"))
        i = jax.nn.sigmoid(proj("wi"))
        a, b = f / (f + i), i / (f + i) * h_tilde
    h = _linear_scan(a, b)
    x = x + _mm(h, p["down"]["kernel"], control)
    y = _rmsnorm(x, p["norm_mlp"]["scale"])
    y = _gelu(_mm(y, p["mlp_in"]["kernel"], control) + p["mlp_in"]["bias"])
    return x + _mm(y, p["mlp_out"]["kernel"], control) + p["mlp_out"]["bias"]


def _logits(params, tokens, *, cell: str, vocab: int, control: bool = False):
    """tokens (B, T) int -> logits (B, T, vocab) float32."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    table = params["embed"]["table"]
    x = table[tokens]

    def body(x, p):
        return jax.checkpoint(functools.partial(
            _block, cell=cell, control=control))(p, x), None

    x, _ = lax.scan(body, x, params["layers"]["blocks"])
    x = _rmsnorm(x, params["final_norm"]["scale"])
    return _mm(x, table.T, control)[..., :vocab]


_jit_logits = jax.jit(_logits, static_argnames=("cell", "vocab", "control"))


def forward(params, tokens, conf: dict, control: bool = False):
    """tokens (B, T) int -> float32 reference logits (B, T, vocab)."""
    return _jit_logits(params, tokens, cell=conf["minrnn"]["cell"],
                       vocab=conf["vocab_size"], control=control)


def _nll_sum(params, batch, *, cell: str, vocab: int, control: bool = False):
    """Summed next-token negative log-likelihood over labels >= 0."""
    logits = _logits(params, batch["tokens"], cell=cell, vocab=vocab,
                     control=control)
    labels = batch["labels"]
    mask = labels >= 0
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               -1)[..., 0]
    return jnp.sum(jnp.where(mask, logz - gold, 0.0))


@functools.partial(jax.jit, static_argnames=("cell", "vocab", "control"))
def _nll_sum_and_grad(params, batch, *, cell, vocab, control=False):
    """(summed NLL, its float32 gradient) for one block of rows."""
    return jax.value_and_grad(_nll_sum)(params, batch, cell=cell,
                                        vocab=vocab, control=control)


def loss_and_grad(params, batch, conf: dict, rows: int,
                  control: bool = False):
    """Mean NLL over the batch and its gradient, ``rows`` rows at a time
    so that the float32 activations fit beside the program's memory."""
    cell, vocab = conf["minrnn"]["cell"], conf["vocab_size"]
    n = batch["tokens"].shape[0]
    total, grads = 0.0, None
    for r in range(0, n, rows):
        part = {k: v[r:r + rows] for k, v in batch.items()}
        s, g = _nll_sum_and_grad(params, part, cell=cell, vocab=vocab,
                                 control=control)
        total = total + s
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    count = jnp.sum(batch["labels"] >= 0).astype(jnp.float32)
    return total / count, jax.tree.map(lambda g: g / count, grads)


# ---------------------------------------------------------------------------
# The work: operations and bytes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Shape:
    """The sizes of a minRNN LM that the counts depend on."""
    n_layers: int
    d_model: int
    d_hidden: int
    d_ff: int
    vocab: int
    conv_kernel: int
    n_gates: int
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, conf: dict) -> "Shape":
        mr = conf["minrnn"]
        return cls(n_layers=conf["n_layers"], d_model=conf["d_model"],
                   d_hidden=int(conf["d_model"] * mr["expansion"]),
                   d_ff=conf["d_ff"], vocab=conf["vocab_size"],
                   conv_kernel=mr["conv_kernel"],
                   n_gates=len(_GATES[mr["cell"]]),
                   dtype_bytes=2 if conf["param_dtype"] == "bfloat16" else 4)

    # -- parameters -----------------------------------------------------
    @property
    def block_matmul_params(self) -> int:
        """Matrix-product weights of one residual block: the gate
        projections, the down-projection and the two MLP projections."""
        d, dh, ff = self.d_model, self.d_hidden, self.d_ff
        return self.n_gates * d * dh + dh * d + 2 * d * ff

    @property
    def block_params(self) -> int:
        """Every weight of one block, biases, norms and conv included."""
        d, dh, ff = self.d_model, self.d_hidden, self.d_ff
        return (self.block_matmul_params + self.n_gates * dh + ff + d
                + self.conv_kernel * d + d + 2 * d)

    @property
    def matmul_params(self) -> int:
        """N: matrix-product weights of the whole model, the tied
        unembedding included (the embedding lookup is a gather)."""
        return self.n_layers * self.block_matmul_params \
            + self.d_model * self.vocab

    @property
    def state_per_row(self) -> int:
        """Carried elements per sequence: h and the conv window, all
        layers."""
        return self.n_layers * (self.d_hidden
                                + (self.conv_kernel - 1) * self.d_model)

    # -- forward work ---------------------------------------------------
    def flops_per_token(self) -> float:
        """2N: one forward pass of one token."""
        return 2.0 * self.matmul_params

    def train_flops_per_token(self) -> float:
        """6N: forward and backward; recomputation does not count."""
        return 6.0 * self.matmul_params

    def block_flops(self, tokens: int) -> float:
        """Operations of the block kernels for ``tokens`` token-steps."""
        return 2.0 * tokens * self.n_layers * self.block_matmul_params

    def block_bytes(self, rounds: int, row_rounds: int) -> float:
        """HBM bytes of the block kernels over ``rounds`` device rounds in
        which ``row_rounds`` live rows were stepped: every block weight
        once per round, and each live row's state read and written once
        per round."""
        weights = self.n_layers * self.block_params * self.dtype_bytes
        state = 2 * self.state_per_row * self.dtype_bytes
        return rounds * weights + row_rounds * state


def counts(conf: dict) -> Shape:
    return Shape.from_config(conf)
