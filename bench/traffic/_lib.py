"""What the generators share: the token vocabulary of the synthetic
catalogue, items and user histories.

A traffic mix is a JSON file of parameters in this directory
(``<mix>.json``) that names its generator, ``<generator>.py`` here, and
the kind of driver that runs it. Sizes (history lengths, context lengths,
arrival gaps) come from the mix's fixed ``shape_seed`` and are shuffled by
the run's seed; contents (items, labels, users, candidates) come from the
run's seed, so every seed does the same amount of work, in another order.

The catalogue follows ``repro.data.synthetic`` of the program; it is
copied here so that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np

PAD, BOS, SUM, YES, NO, SEP, N_RESERVED = 0, 1, 2, 3, 4, 5, 8

_ADJ = ["dark", "silent", "lost", "golden", "broken", "electric", "crimson",
        "frozen", "hidden", "iron", "lucky", "midnight", "neon", "paper",
        "quiet", "raging", "secret", "turbo", "velvet", "wild"]
_NOUN = ["river", "empire", "garden", "signal", "harbor", "mirror", "engine",
         "forest", "galaxy", "anthem", "circus", "desert", "echo", "fortune",
         "horizon", "island", "jungle", "kingdom", "lantern", "meadow"]
_GENRE = ["action", "comedy", "drama", "horror", "romance", "scifi",
          "thriller", "western"]


def seed_rng(seed: int) -> np.random.Generator:
    # --seed may exceed 32 bits; PCG64 takes any non-negative int
    return np.random.default_rng(abs(int(seed)))


def _token(word: str, vocab: int) -> int:
    h = 0x811C9DC5
    for ch in word.lower().encode():
        h = ((h ^ ch) * 0x01000193) & 0xFFFFFFFF
    return N_RESERVED + h % (vocab - N_RESERVED)


def item_tokens(n_items: int, vocab: int, rng: np.random.Generator,
                latent_dim: int = 4):
    """-> (per-item token lists, item latents). Six tokens with the rating
    a context interaction appends: [SEP] adj noun id genre (rating)."""
    z = rng.normal(size=(n_items, latent_dim)) / np.sqrt(latent_dim)
    out = []
    for i in range(n_items):
        b = (z[i] > 0).astype(int)
        adj = _ADJ[(i * 7 + b[0] * 10) % len(_ADJ)]
        noun = _NOUN[(i * 13 + b[1 % latent_dim] * 10) % len(_NOUN)]
        genre = _GENRE[int(b @ (2 ** np.arange(len(b)))) % len(_GENRE)]
        out.append([SEP] + [_token(w, vocab) for w in f"{adj} {noun} v{i}".split()]
                   + [_token(f"genre={genre}", vocab)])
    return out, z


def user_history(items, z, m: int, vocab: int, rng: np.random.Generator,
                 label_scale: float = 3.0):
    """-> (per-interaction token lists with rating, click labels)."""
    p = rng.normal(size=(z.shape[1],)) / np.sqrt(z.shape[1])
    ids = rng.integers(0, len(items), size=m)
    aff = z[ids] @ p * label_scale
    labels = (rng.random(m) < 1.0 / (1.0 + np.exp(-aff))).astype(np.int64)
    ratings = np.clip(np.round(2.5 + 1.5 * np.tanh(aff)), 1, 5).astype(int)
    toks = [items[i] + [_token(f"rating={r}", vocab)]
            for i, r in zip(ids, ratings)]
    return toks, labels
