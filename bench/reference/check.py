"""What decides ``correct``: the plain reference run over the timed path's
inputs, and the numbers compared with each cell's limits.

Training — the reference follows the program's first three steps on the
same batches and weights (AdamW as the configuration states it, with a
float32 master copy of the adapters stored in ``param_dtype``):

* ``loss1_gap``  the first step's relative loss gap. Later steps' losses
  (``loss_gap``, the largest of the three) are printed but not compared:
  after Adam's first full-rate step, whose per-element size does not
  depend on the gradient's, the loss moves far and amplifies every
  rounding of the stored adapters (see PERF.md);
* ``grad_gap``   the worst leaf's gap between the norms of the first
  gradient as the optimizer took it (read back from its first moment,
  ``mu_1 / (1 - beta1)``) and the reference's clipped gradient;
* ``delta_gap``  the worst leaf's gap between the norms of the parameters'
  change over the three steps (the optimizer's float32 master copy).

A gap of norms is taken against the larger of the reference's norm of that
leaf and of the median leaf. Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left out
of ``delta_gap``.

Serving — ``score_gap``: the widest gap between the log-odds of a served
click probability and the reference's, over a sample of finished requests
drawn from the seed, the request with the longest context among them.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import lm


def _leaf_norms(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in p): float(
        np.linalg.norm(np.asarray(x, np.float64).ravel()))
        for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def gap_of_norms(prog: dict, ref: dict, keep=None) -> float:
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in names)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def _lr(opt, t):
    assert opt["schedule"] == "const", "the reference follows a const schedule"
    return opt["lr"] * min(1.0, t / max(opt["warmup_steps"], 1))


def make_train_ref(cfg: dict, opt: dict, window: int, *, low: bool = False,
                   rows_per_block: int = 1, rows_kept: int = 0):
    """-> follow(params, batches) -> dict of the reference's readings.
    Rows run ``rows_per_block`` at a time (a divisor of the batch is
    taken), so the float32 scores fit. ``rows_kept`` > 0 keeps only the first rows of each batch (the
    half-batch fault put in the program's place)."""

    def nll_sum(lo, rest, blk):
        lg = lm.ctr_logits(lm.merge(lo, rest), blk, cfg, window=window,
                           train=True, low=low)
        logp = jax.nn.log_softmax(lg, -1)
        nll = -jnp.where(blk["labels"] == 1, logp[..., 0], logp[..., 1])
        w = blk["is_sum"].astype(jnp.float32)
        return jnp.sum(nll * w), jnp.sum(w)

    @jax.jit
    def loss_grad(lo, rest, batch):
        if rows_kept:
            batch = {k: v[:rows_kept] for k, v in batch.items()}
        rows = batch["tokens"].shape[0]
        per = math.gcd(rows, rows_per_block)
        blocks = {k: v.reshape(rows // per, per, *v.shape[1:])
                  for k, v in batch.items()}
        zero = jax.tree_util.tree_map(jnp.zeros_like, lo)

        def body(carry, blk):
            s, c, g = carry
            (si, ci), gi = jax.value_and_grad(nll_sum, has_aux=True)(
                lo, rest, blk)
            return (s + si, c + ci, jax.tree_util.tree_map(jnp.add, g, gi)), None

        (s, c, g), _ = jax.lax.scan(
            body, (jnp.float32(0), jnp.float32(0), zero), blocks)
        c = jnp.maximum(c, 1.0)
        return s / c, jax.tree_util.tree_map(lambda x: x / c, g)

    @partial(jax.jit, static_argnums=(4,))
    def adamw(master, mu, nu, g, t):
        b1, b2 = opt["betas"]
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
        g = jax.tree_util.tree_map(lambda x: x * clip, g)
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                    nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, m, v):
            u = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
            return p - _lr(opt, t) * (u + opt["weight_decay"] * p)
        return jax.tree_util.tree_map(upd, master, mu, nu), mu, nu, g

    store = jnp.dtype(cfg.get("param_dtype", "float32"))

    def stored(master):
        """The adapters as the configuration stores them (``param_dtype``)
        and the forward pass reads them; the optimizer keeps a float32
        master copy, as the program's does."""
        return jax.tree_util.tree_map(
            lambda x: x.astype(store).astype(jnp.float32), master)

    def follow(params, batches):
        lo, rest = lm.split_lora(params)
        master = lm.f32(lo)
        p0 = master
        mu = jax.tree_util.tree_map(jnp.zeros_like, master)
        nu = jax.tree_util.tree_map(jnp.zeros_like, master)
        losses, g1 = [], None
        for t, batch in enumerate(batches, 1):
            loss, g = loss_grad(stored(master), rest, batch)
            losses.append(float(loss))
            master, mu, nu, g = adamw(master, mu, nu, g, t)
            if t == 1:
                g1 = _leaf_norms(g)
        delta = jax.tree_util.tree_map(lambda a, b: a - b, master, p0)
        return {"losses": losses, "grad_norms": g1,
                "delta_norms": _leaf_norms(delta)}

    return follow


def program_readings(losses, mu_1, master_0, master_3, beta1) -> dict:
    """The program's side of the comparison, from host copies of its
    optimizer state (adapter leaves only)."""
    g = jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - beta1), mu_1)
    d = jax.tree_util.tree_map(lambda a, b: np.asarray(a, np.float64)
                               - np.asarray(b, np.float64), master_3, master_0)
    return {"losses": list(losses), "grad_norms": _leaf_norms(g),
            "delta_norms": _leaf_norms(d)}


def later_loss_gaps(prog: dict, ref: dict) -> list:
    """Relative loss gaps of every step followed (printed, not compared)."""
    return [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]


def train_numbers(prog: dict, ref: dict) -> dict:
    med_g = float(np.median(list(ref["grad_norms"].values())))
    moved = {n for n, v in ref["grad_norms"].items() if v >= 1e-3 * med_g}
    gaps = later_loss_gaps(prog, ref)
    return {
        "loss_gap": max(gaps),
        "loss1_gap": gaps[0],
        "grad_gap": gap_of_norms(prog["grad_norms"], ref["grad_norms"]),
        "delta_gap": gap_of_norms(prog["delta_norms"], ref["delta_norms"],
                                  keep=moved),
    }


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serve_row(req: dict, length: int, bos: int = 1, sum_id: int = 2) -> dict:
    """A request as one row: [BOS] context (segment 0), then each candidate
    and its [SUM] (segments 1..k) at positions that continue after the
    context, padded to ``length``."""
    toks = [bos] + [t for it in req["context"] for t in it]
    n = len(toks)
    pos, seg, is_sum = list(range(n)), [0] * n, [False] * n
    for j, c in enumerate(req["candidates"]):
        g = list(c) + [sum_id]
        toks += g
        pos += range(n, n + len(g))
        seg += [j + 1] * len(g)
        is_sum += [False] * len(c) + [True]
    m = len(toks)
    assert m <= length, f"request of {m} tokens > reference row {length}"
    pad = length - m
    return {"tokens": np.asarray(toks + [0] * pad, np.int32)[None],
            "positions": np.asarray(pos + [0] * pad, np.int32)[None],
            "segment_ids": np.asarray(seg + [-1] * pad, np.int32)[None],
            "is_sum": np.asarray(is_sum + [False] * pad)[None],
            "valid": np.asarray([True] * m + [False] * pad)[None]}


def make_serve_ref(cfg: dict, window: int, *, low: bool = False):
    """-> log-odds of a click at every [SUM] of one request row."""
    @jax.jit
    def log_odds(params, row):
        lg = lm.ctr_logits(params, row, cfg, window=window, train=False,
                           low=low)
        return lg[..., 0] - lg[..., 1]

    def score(params, req, length):
        row = serve_row(req, length)
        lo = np.asarray(log_odds(params, row))[0]
        return lo[np.flatnonzero(row["is_sum"][0])]

    return score


def sample_requests(finished: list, n: int, seed: int, key) -> list:
    """``n`` finished requests drawn from the seed, the one with the
    longest context among them."""
    if not finished:
        return []
    longest = max(finished, key=key)
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(abs(int(seed)) + 7)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def serve_gap(prog_scores, ref_log_odds) -> float:
    p = np.clip(np.asarray(prog_scores, np.float64), 1e-12, 1 - 1e-12)
    return float(np.max(np.abs(np.log(p) - np.log1p(-p)
                               - np.asarray(ref_log_odds, np.float64))))
