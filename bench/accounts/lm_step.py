"""Model operations of a whole step (model FLOPs, for ``*mfu*`` metrics).

``matmul_params``: weights multiplied per token and layer (LoRA adapters
included); a multiply-add is 2 operations. Training with LoRA on a frozen
base needs per real token: the forward (``2 P``), the gradient with respect
to the layer inputs (``2 P``) and the adapters' weight gradients
(``2 P_lora``), the DTI reset's values of the initial states (forward and
adapter gradient), and the attention forward and backward over the pairs
actually attended (``winattn_fwd`` / ``winattn_bwd``). Recomputation does
not count, and pad tokens do no work.
"""
from __future__ import annotations

import numpy as np

from bench.accounts import winattn_bwd, winattn_fwd


def _lin(d_in, d_out, r):
    return d_in * d_out, r * (d_in + d_out) if r else 0


def layer_params(cfg: dict):
    """-> (base, lora, reset-value base, reset-value lora) per layer."""
    d, r = cfg["d_model"], cfg["lora_rank"]
    h = cfg["n_heads"]
    if cfg["attn_type"] == "mla":
        dn, dr, dv = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
        qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
        mats = [(d, qr, 0), (qr, h * (dn + dr), r), (d, kvr, 0),
                (kvr, h * (dn + dv), r), (d, dr, 0), (h * dv, d, r)]
        v0 = [(d, kvr, 0), (kvr, h * (dn + dv), r)]
    else:
        hd, hk = cfg["head_dim"], cfg["n_kv_heads"]
        mats = [(d, h * hd, r), (d, hk * hd, r), (d, hk * hd, r),
                (h * hd, d, r)]
        v0 = [(d, hk * hd, r)]
    f = cfg["d_ff"]
    mats += [(d, f, r), (d, f, r), (f, d, r)]
    base = sum(_lin(*m)[0] for m in mats)
    lora = sum(_lin(*m)[1] for m in mats)
    return base, lora, sum(_lin(*m)[0] for m in v0), sum(_lin(*m)[1] for m in v0)


def train_flops(cfg: dict, batch: dict, window: int) -> float:
    """Model FLOPs of one LoRA training step on ``batch``."""
    L = cfg["n_layers"]
    base, lora, v0b, v0l = layer_params(cfg)
    tokens = int(np.asarray(batch["valid"]).sum())
    dense = tokens * L * (4 * (base + lora) + 2 * lora
                          + 2 * (v0b + v0l) + 2 * v0l)
    head = tokens * 2 * 2 * cfg["d_model"] * 2       # yes/no rows, fwd + bwd
    attn = L * (winattn_fwd.account(cfg, batch, window)[0]
                + winattn_bwd.account(cfg, batch, window)[0])
    return float(dense + head + attn)


def serve_flops(cfg: dict, units, window: int) -> float:
    """Model FLOPs of one scoring step: ``units`` [(tokens, context)]."""
    from bench.accounts import decode_attn
    L = cfg["n_layers"]
    base, lora, _, _ = layer_params(cfg)
    tokens = sum(t for t, _ in units)
    dense = tokens * (L * 2 * (base + lora) + 2 * 2 * cfg["d_model"])
    return float(dense + L * decode_attn.account(cfg, units, window)[0])
