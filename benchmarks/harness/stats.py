"""Arithmetic the yardstick owns: percentiles, rates, FLOPs per token,
MFU and the peaks table. Pure Python; no jax."""
from __future__ import annotations

import json
import math
import os
import statistics

from harness.files import HERE


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between the
    two nearest ranks, as numpy's default. A failed request is +inf;
    a percentile that touches one is +inf. None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(xs[hi]) or lo == hi:
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, with Python's `statistics.quantiles(values, n=4)`: the
    contract's measure of how widely runs of one cell disagree."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def load_peaks(device_kind):
    """The peak rates of `device_kind`. A device that is not in the
    table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in harness/peaks.json "
            f"(known: {sorted(table)}): add its published peaks with "
            "their source before measuring on it")
    return table[device_kind]


def gpt_train_flops_per_token(d_model, n_layers, seq_len, vocab_size):
    """Operations the forward and backward passes of a GPT need per
    trained token (copied from bench.py:103-108): 6 per weight
    (12 L d^2 in the blocks, V d in the tied head, S d positions) plus
    causal attention, 6 L S d. Recomputation is not counted."""
    n_params = 12 * n_layers * d_model * d_model \
        + vocab_size * d_model + seq_len * d_model
    return 6 * n_params + 6 * n_layers * seq_len * d_model


def mfu(tokens_per_s, flops_per_token, peak_flops_per_s, chips=1):
    return tokens_per_s * flops_per_token / (peak_flops_per_s * chips)
