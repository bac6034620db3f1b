"""A DTI training corpus (``kind: "train"``): users with histories of
``history_min..history_max`` interactions, streaming prompts of ``n_ctx``
context interactions and up to ``k`` targets each, packed (``pack``) into
segment-isolated rows of ``train_max_len`` tokens, dealt into ``batches``
batches of the configuration's rows.

The prompt and packing rules follow ``repro.core.dti`` and
``repro.data.synthetic`` of the program, copied here so that a change to
the program cannot move the yardstick.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.traffic._lib import BOS, PAD, SUM, item_tokens, seed_rng, user_history


def geometry(mix: dict) -> dict:
    """Row length and attention window of a DTI mix (the program's
    ``train_max_len`` / ``window_tokens`` rules at 6 tokens per item)."""
    avg = 6.0
    n_ctx, k = mix["n_ctx"], mix["k"]
    n = int((n_ctx + k) * (avg + 1.5) + 8)
    return {"max_len": ((n + 63) // 64) * 64,
            "window": int(min(mix["window_cap"],
                              round(n_ctx * (avg + 0.5) + 2)))}


def _streaming_prompts(toks, labels, n_ctx: int, k: int):
    out = []
    i = n_ctx
    while i < len(toks):
        t, s, lab = [BOS], [], []
        for j in range(i - n_ctx, i):
            t.extend(toks[j])
        s = [False] * len(t)
        lab = [0] * len(t)
        for j in range(i, min(i + k, len(toks))):
            t.extend(toks[j])
            s.extend([False] * len(toks[j]))
            lab.extend([0] * len(toks[j]))
            t.append(SUM)
            s.append(True)
            lab.append(int(labels[j]))
        out.append((t, s, lab))
        i += k
    return out


def _pack(prompts, max_len: int):
    """First-fit decreasing packing into segment-isolated rows."""
    order = sorted(range(len(prompts)), key=lambda i: -len(prompts[i][0]))
    bins, free = [], []
    for i in order:
        n = len(prompts[i][0])
        for b, cap in enumerate(free):
            if n <= cap:
                bins[b].append(i)
                free[b] = cap - n
                break
        else:
            bins.append([i])
            free.append(max_len - n)
    rows = []
    for members in bins:
        row = {"tokens": np.full(max_len, PAD, np.int32),
               "positions": np.zeros(max_len, np.int32),
               "segment_ids": np.full(max_len, -1, np.int32),
               "is_sum": np.zeros(max_len, bool),
               "labels": np.zeros(max_len, np.int32),
               "valid": np.zeros(max_len, bool)}
        off = 0
        for si, i in enumerate(members):
            t, s, lab = prompts[i]
            sl = slice(off, off + len(t))
            row["tokens"][sl] = t
            row["positions"][sl] = np.arange(len(t))
            row["segment_ids"][sl] = si
            row["is_sum"][sl] = s
            row["labels"][sl] = lab
            row["valid"][sl] = True
            off += len(t)
        rows.append(row)
    return rows


def batches(mix: dict, vocab: int, rows: int, seed: int) -> List[Dict]:
    """``mix["batches"]`` batches of ``rows`` packed rows. Rows are dealt
    in snake order of their target counts, so every batch carries about
    the same number of targets."""
    geo = geometry(mix)
    need = mix["batches"] * rows
    # the users (history lengths) come from the shape seed: enough of them
    # for ``need`` rows, so every run seed trains on the same sizes (a
    # prompt's length follows from its user's: 6 tokens an interaction)
    shape = seed_rng(mix["shape_seed"])
    lengths, sizes = [], []
    while True:
        m = int(shape.integers(mix["history_min"], mix["history_max"] + 1))
        a = int(m * mix["train_frac"])
        if a <= mix["n_ctx"]:
            continue
        lengths.append(a)
        sizes += [1 + 6 * mix["n_ctx"] + 7 * min(mix["k"], a - i)
                  for i in range(mix["n_ctx"], a, mix["k"])]
        n_rows = (len(_pack([([0] * n, 0, 0) for n in sizes], geo["max_len"]))
                  if mix["pack"] else len(sizes))
        if n_rows >= need:
            break
    rng = seed_rng(seed)
    items, z = item_tokens(mix["n_items"], vocab, rng)
    prompts = []
    for a in rng.permutation(lengths):
        toks, labels = user_history(items, z, int(a), vocab, rng)
        prompts += _streaming_prompts(toks, labels, mix["n_ctx"], mix["k"])
    packed = (_pack(prompts, geo["max_len"]) if mix["pack"] else
              [r for p in prompts for r in _pack([p], geo["max_len"])])
    assert len(packed) >= need, "the users give too few rows"
    packed = [packed[i] for i in rng.permutation(len(packed))[:need]]
    packed.sort(key=lambda r: -int(r["is_sum"].sum()))
    nb = mix["batches"]
    dealt = [[] for _ in range(nb)]
    for i, r in enumerate(packed):
        lap, j = divmod(i, nb)
        dealt[j if lap % 2 == 0 else nb - 1 - j].append(r)
    return [{key: np.stack([r[key] for r in b]) for key in b[0]}
            for b in dealt]
