"""Windowed DTI attention backward: the dq and dk/dv kernels of one layer
together, on a training batch.

Per head and attended pair (``winattn_fwd.pairs``): ``2 Dv`` for dP,
``2 Dqk`` each for dQ and dK, ``2 Dv`` for dV; a [SUM] query's reset adds
``2 Dv`` for the gradient of its weights and ``2 Dv`` for dV0. Scores
recomputed from q and k do not count. Bytes: q, k, v, v0, the output, its
gradient and the log-sum-exp read once; dq, dk, dv and dv0 written once.
"""
from __future__ import annotations

import numpy as np

from bench.accounts.winattn_fwd import pairs, shapes


def account(cfg: dict, batch: dict, window: int, elem: int = 2):
    h, hk, dqk, dv = shapes(cfg)
    p_all, p_sum = pairs(batch, window)
    flops = h * ((4 * dqk + 4 * dv) * p_all + 4 * dv * p_sum)
    b, s = np.asarray(batch["tokens"]).shape
    tok = b * s
    qkv = h * dqk + hk * dqk + 2 * hk * dv           # q, k, v, v0
    bytes_ = tok * elem * (2 * qkv + 2 * h * dv) + tok * h * 4
    return float(flops), float(bytes_)
