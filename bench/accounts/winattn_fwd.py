"""Windowed DTI attention forward: one call (one layer) on a training batch.

Attended pairs (query, key) follow the mask of the DTI paper: same
segment, ``0 <= pos_q - pos_k <= window``, valid key, and a [SUM] key only
for itself. Per head and pair: ``2 Dqk`` for the score (RoPE'd, or NoPE
with ALiBi on a [SUM] query) and ``2 Dv`` for the value aggregate; a [SUM]
query adds ``2 Dv`` for its reset towards the initial values. Bytes: q, k,
v and the initial values v0 read once (RoPE and the NoPE stream can be
made from the unrotated q and k), the output written once, plus the
float32 log-sum-exp a backward pass needs.
"""
from __future__ import annotations

import numpy as np


def pairs(batch: dict, window: int):
    """-> (attended pairs, pairs whose query is a [SUM]) over the batch.
    Rows are packed: segments are contiguous and positions restart at 0 in
    each, so a query's keys are a run of the row ending at itself."""
    pos = np.asarray(batch["positions"], np.int64)
    valid = np.asarray(batch["valid"], bool)
    is_sum = np.asarray(batch["is_sum"], bool)
    n = np.minimum(pos, window)                      # keys before the query
    csum = np.concatenate([np.zeros((pos.shape[0], 1), np.int64),
                           np.cumsum(is_sum, axis=1)], axis=1)
    idx = np.arange(pos.shape[1])[None]
    sums_before = csum[:, idx[0]] - np.take_along_axis(csum, idx - n, axis=1)
    cnt = np.where(valid, n + 1 - sums_before, 0)
    return int(cnt.sum()), int(np.where(is_sum, cnt, 0).sum())


def shapes(cfg: dict):
    """-> (heads, kv heads, Dqk, Dv) as the attention kernel sees them."""
    if cfg["attn_type"] == "mla":
        h = cfg["n_heads"]
        return h, h, cfg["qk_nope_dim"] + cfg["qk_rope_dim"], cfg["v_head_dim"]
    return cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"], cfg["head_dim"]


def account(cfg: dict, batch: dict, window: int, elem: int = 2):
    """-> (flops, bytes) of one forward call."""
    h, hk, dqk, dv = shapes(cfg)
    p_all, p_sum = pairs(batch, window)
    flops = h * (2 * (dqk + dv) * p_all + 2 * dv * p_sum)
    b, s = np.asarray(batch["tokens"]).shape
    tok = b * s
    bytes_ = tok * elem * (h * dqk + hk * dqk + 2 * hk * dv + h * dv) \
        + tok * h * 4
    return float(flops), float(bytes_)
