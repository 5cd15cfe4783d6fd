"""The yardstick's operation and byte counts against counts by hand: the
minRNN model module's ``counts`` and ``layout``, and the peaks."""

from __future__ import annotations

import json
import math
import os

import pytest

import work
from conftest import BENCH
from models import minrnn

# (config, matrix-product weights per block, all weights per block)
# mingru: 2*768*1536 + 1536*768 + 2*768*3072 = 8,257,536 in products;
#   + gate biases 2*1536, mlp biases 3072 + 768, conv 4*768 + 768,
#   two norm scales 2*768 = 8,269,824 in all.
# minlstm: one more 768x1536 gate and its bias: 9,437,184 and 9,451,008.
HAND = {"mingru-lm": (8_257_536, 8_269_824),
        "minlstm-lm": (9_437_184, 9_451_008)}


def conf(name):
    return json.load(open(os.path.join(BENCH, "configs", name + ".json")))


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_by_hand(name):
    s = minrnn.counts(conf(name))
    mm, allp = HAND[name]
    assert s.block_matmul_params == mm
    assert s.block_params == allp
    assert s.matmul_params == 12 * mm + 768 * 256
    assert s.state_per_row == 12 * (1536 + 3 * 768)
    assert s.flops_per_token() == 2 * s.matmul_params
    assert s.train_flops_per_token() == 6 * s.matmul_params
    assert s.block_flops(10) == 2 * 10 * 12 * mm
    # one round of 64 live rows: every block weight once, state in and out
    assert s.block_bytes(1, 64) == 2 * (12 * allp + 2 * 64 * s.state_per_row)


@pytest.mark.parametrize("name", sorted(HAND))
def test_block_params_match_the_weights_made(name):
    c = conf(name)
    lay = minrnn.layout(c)
    per_block = sum(math.prod(shape[1:]) for path, (shape, _) in lay.items()
                    if path[:2] == ("layers", "blocks"))
    assert per_block == minrnn.counts(c).block_params


def test_peaks_and_unknown_device():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    t, bound = work.ideal_seconds(197e12, 1.0, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.ideal_seconds(1.0, 819e9, p)
    assert (t, bound) == (1.0, "bandwidth")
