"""Decode (scoring) attention into the paged KV cache: one call (one layer)
of a scheduler step.

A unit of ``t`` tokens on a row that holds ``n`` context tokens before it
needs the context keys within the window, ``min(n, window)``, and its own
``t``. Per head and (query, key) pair ``2 (Dqk + Dv)`` operations; each
query is counted with the context keys and itself only (a lower bound: a
query's earlier tokens of its own unit are left out). Bytes: the needed
keys' K and V once per KV head, q read and the output written once.
"""
from __future__ import annotations

from bench.accounts.winattn_fwd import shapes


def account(cfg: dict, units, window: int, elem: int = 2):
    """``units``: [(tokens, context tokens before them)] of one step."""
    h, hk, dqk, dv = shapes(cfg)
    flops = bytes_ = 0.0
    for t, n in units:
        keys = min(n, window) + t
        flops += 2.0 * h * (dqk + dv) * t * (min(n, window) + 1)
        bytes_ += elem * (keys * hk * (dqk + dv) + t * h * (dqk + dv))
    return flops, bytes_
