"""The work each benchmarked program needs, counted from the configuration.

These are the yardstick for every roofline share and utilisation the
benchmark reports: the operations and bytes the algorithm needs, not what
a kernel's padded shapes happen to compute.  Only matrix products count
as operations (2 per multiply-add); the elementwise gates, the 4-tap
convolution, the norms and the scan are left out, so a share is an
under-statement by their small part.  Bytes are what must cross HBM:
each weight once per device round, plus the carried recurrent state read
and written once per round.

``peaks(device_kind)`` reads ``peaks.json``; a device that is not listed
there is an error.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
_GATES = {"mingru": 2, "minlstm": 3}


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
                   n_gates=_GATES[mr["cell"]],
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


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip of ``device_kind``."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def ideal_seconds(flops: float, nbytes: float, peak: dict):
    """(least time, which bound sets it): the larger of operations over
    peak FLOP/s and bytes over peak bandwidth."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")
