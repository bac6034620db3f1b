"""An open loop of ranking requests (``kind: "serve"``): a user context of
``n_ctx`` interactions plus a Pareto(``tail_alpha``) excess clamped at
``n_ctx_tail``, a slate of ``k`` candidates, a share ``repeat_frac`` of
revisits (an earlier context with a fresh slate), and arrivals of a
Poisson process at ``load`` times the knee.

The knee is measured, never guessed: ``bench/sweep_knee.py`` writes it to
the file the mix names under ``knee`` (``bench/traffic/<knee>.knee.json``),
and a mix whose knee file is missing is refused. The request shapes follow
``repro.data.requests.make_request_stream`` of the program, copied here so
that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import json
import os

import numpy as np

from bench.traffic._lib import item_tokens, seed_rng, user_history

HERE = os.path.dirname(os.path.abspath(__file__))


def knee_path(mix: dict) -> str:
    return os.path.join(HERE, mix["knee"] + ".knee.json")


def rate(mix: dict) -> float:
    """Arrivals per second: ``load`` times the measured knee."""
    path = knee_path(mix)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no measured knee for this mix ({path}): run bench/sweep_knee.py "
            f"on the chip first")
    with open(path) as f:
        return float(mix["load"]) * float(json.load(f)["knee_req_per_s"])


def requests(mix: dict, vocab: int, seconds: float, seed: int, *,
             rate_per_s: float = None):
    """-> (warm-up requests, window requests). A request is a dict with
    ``due`` (seconds after the window opens), ``context`` and
    ``candidates`` (per-item token lists) and ``user``. ``rate_per_s`` is
    for the knee sweep alone, which tries rates; a cell runs at its mix's
    ``load`` times the measured knee."""
    shape = seed_rng(mix["shape_seed"])
    if rate_per_s is None:
        rate_per_s = rate(mix)
    n = max(1, int(round(rate_per_s * seconds)))
    n_ctx, tail = mix["n_ctx"], mix["n_ctx_tail"]
    n_rev = int(round(mix["repeat_frac"] * (n - 1)))
    ctx_lens = [min(n_ctx + int(n_ctx * float(shape.pareto(mix["tail_alpha"]))),
                    tail) for _ in range(n - n_rev)]
    gaps = np.diff(np.concatenate([[0.0],
                                   np.sort(shape.uniform(0, seconds, n))]))
    rng = seed_rng(seed)
    gaps = rng.permutation(gaps)
    ctx_lens = list(rng.permutation(ctx_lens))
    is_rev = np.zeros(n, bool)
    is_rev[1 + rng.choice(n - 1, size=n_rev, replace=False)] = True
    items, z = item_tokens(mix["n_items"], vocab, rng)
    hist = [user_history(items, z, mix["history"], vocab, rng)[0]
            for _ in range(mix["n_users"])]

    def draw(n_i):
        u = int(rng.integers(0, len(hist)))
        lo = int(rng.integers(0, len(hist[u]) - n_i + 1))
        return u, [list(map(int, it)) for it in hist[u][lo:lo + n_i]]

    def slate():
        return [list(map(int, items[int(i)]))
                for i in rng.integers(0, len(items), size=mix["k"])]

    warm = []
    for _ in range(mix["warm_requests"]):
        u, ctx = draw(n_ctx)
        warm.append({"due": 0.0, "user": u, "context": ctx,
                     "candidates": slate()})
    out, due = [], 0.0
    for i in range(n):
        due += float(gaps[i])
        if is_rev[i]:
            src = out[int(rng.integers(0, len(out)))]
            u, ctx = src["user"], [list(it) for it in src["context"]]
        else:
            u, ctx = draw(ctx_lens.pop())
        out.append({"due": due, "user": u, "context": ctx,
                    "candidates": slate()})
    return warm, out


def context_tokens(req: dict) -> int:
    """Logical context length of a request, [BOS] included."""
    return 1 + sum(len(t) for t in req["context"])
