"""The benchmark's operation and byte accounts against brute counts over
explicit masks, and the trace reduction against a recorded trace."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench.accounts import decode_attn, lm_step, winattn_bwd, winattn_fwd  # noqa: E402
from bench import tracing  # noqa: E402

GQA = {"attn_type": "gqa", "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
       "d_model": 64, "d_ff": 96, "lora_rank": 4, "n_layers": 2}
MLA = {"attn_type": "mla", "n_heads": 4, "n_kv_heads": 4, "d_model": 64,
       "d_ff": 96, "lora_rank": 4, "n_layers": 2, "q_lora_rank": 24,
       "kv_lora_rank": 16, "qk_nope_dim": 8, "qk_rope_dim": 8,
       "v_head_dim": 12}


def packed_batch(rng, rows=3, length=64):
    """Rows of contiguous segments, positions restarting per segment, a
    few [SUM] tokens, padding at the end."""
    keys = ("positions", "segment_ids", "is_sum", "valid", "tokens")
    b = {k: np.zeros((rows, length), np.int32) for k in keys}
    b["is_sum"] = np.zeros((rows, length), bool)
    b["valid"] = np.zeros((rows, length), bool)
    b["segment_ids"][:] = -1
    for r in range(rows):
        off, seg = 0, 0
        while True:
            n = int(rng.integers(5, 30))
            if off + n > length - 4:
                break
            sl = slice(off, off + n)
            b["positions"][r, sl] = np.arange(n)
            b["segment_ids"][r, sl] = seg
            b["valid"][r, sl] = True
            b["is_sum"][r, sl] = rng.random(n) < 0.2
            off, seg = off + n, seg + 1
    return b


def brute_pairs(b, window):
    p_all = p_sum = 0
    for r in range(b["positions"].shape[0]):
        pos, seg = b["positions"][r], b["segment_ids"][r]
        v, s = b["valid"][r], b["is_sum"][r]
        d = pos[:, None] - pos[None, :]
        same = seg[:, None] == seg[None, :]
        eye = np.eye(len(pos), dtype=bool)
        m = same & (d >= 0) & (d <= window) & v[None] & v[:, None]
        m &= ~s[None, :] | eye
        p_all += int(m.sum())
        p_sum += int(m[s].sum())
    return p_all, p_sum


@pytest.mark.parametrize("window", [3, 9, 40])
def test_pairs_match_an_explicit_mask(window):
    b = packed_batch(np.random.default_rng(window))
    assert winattn_fwd.pairs(b, window) == brute_pairs(b, window)


@pytest.mark.parametrize("cfg", [GQA, MLA], ids=["gqa", "mla"])
def test_attention_accounts(cfg):
    b = packed_batch(np.random.default_rng(1))
    p_all, p_sum = brute_pairs(b, 9)
    h = cfg["n_heads"]
    if cfg["attn_type"] == "mla":
        hk, dqk, dv = h, 16, 12               # Dqk != Dv
    else:
        hk, dqk, dv = 2, 16, 16               # GQA: K/V per KV head
    f, by = winattn_fwd.account(cfg, b, 9)
    assert f == h * (2 * (dqk + dv) * p_all + 2 * dv * p_sum)
    tok = b["tokens"].size
    assert by == tok * 2 * (h * dqk + hk * dqk + 2 * hk * dv + h * dv) + tok * h * 4
    fb, bb = winattn_bwd.account(cfg, b, 9)
    assert fb == h * ((4 * dqk + 4 * dv) * p_all + 4 * dv * p_sum)
    assert bb > by


def test_decode_account_against_a_brute_count():
    cfg = GQA
    units = [(10, 30), (4, 100), (7, 0)]
    window = 50
    f, by = decode_attn.account(cfg, units, window)
    want_f = want_b = 0
    for t, n in units:
        vis = min(n, window) + 1              # context in the window + itself
        want_f += t * vis * 4 * 2 * (16 + 16)
        keys = min(n, window) + t
        want_b += 2 * (keys * 2 * 32 + t * 4 * 32)
    assert (f, by) == (want_f, want_b)


@pytest.mark.parametrize("cfg", [GQA, MLA], ids=["gqa", "mla"])
def test_matmul_params_match_the_programs_layout(cfg):
    """The account's weights a token meets per layer equal the program's
    per-layer weight matrices (``w`` and adapter leaves)."""
    import jax
    from repro.models.transformer import ModelConfig, init_params
    mc = ModelConfig(n_layers=2, d_model=64, n_heads=4, d_ff=96,
                     vocab_size=64, lora_rank=4, attn_type=cfg["attn_type"],
                     n_kv_heads=cfg["n_kv_heads"],
                     head_dim=cfg.get("head_dim"),
                     q_lora_rank=cfg.get("q_lora_rank", 0),
                     kv_lora_rank=cfg.get("kv_lora_rank", 0),
                     qk_nope_dim=cfg.get("qk_nope_dim", 0),
                     qk_rope_dim=cfg.get("qk_rope_dim", 0),
                     v_head_dim=cfg.get("v_head_dim", 0))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mc))
    base = lora = 0
    for path, x in jax.tree_util.tree_flatten_with_path(shapes["stack"])[0]:
        name = getattr(path[-1], "key", "")
        n = int(np.prod(x.shape[1:]))
        if name == "w":
            base += n
        elif name in ("lora_a", "lora_b"):
            lora += n
    got = lm_step.layer_params(cfg)
    assert got[:2] == (base, lora)


def test_train_flops_count_real_tokens_only():
    b = packed_batch(np.random.default_rng(2))
    f1 = lm_step.train_flops(GQA, b, 9)
    b2 = {k: np.concatenate([v, np.zeros_like(v)], axis=1)
          for k, v in b.items()}                       # double the padding
    b2["segment_ids"][:, b["tokens"].shape[1]:] = -1
    assert lm_step.train_flops(GQA, b2, 9) == f1


# -- the trace reduction ------------------------------------------------------

# A slice of a train step recorded on a v5e: the XLA ops from a
# rematerialised forward kernel through the first dk/dv kernel, with the
# jitted modules around them, and a ``bench.window`` mark and a
# ``train.step`` span set over the slice and 1 ms past its last op.
RECORDED = os.path.join(HERE, "data", "train_step_slice.xplane.pb")


def _synthetic():
    ops = [("%while.1 = (...) while(...)", 0, 100),
           ("%fusion.3 = bf16[2] fusion(...)", 10, 30),
           ("%fusion.4 = bf16[2] fusion(...)", 40, 60),
           ("%copy.1 = bf16[2] copy(...)", 150, 170)]
    host = [("bench.window", 0, 200)]
    return tracing.Trace(ops, [("jit_step(1)", 0, 180)], host)


def test_busy_time_counts_nested_ops_once():
    tr = _synthetic()
    assert tracing.busy_intervals(tr.ops, 0, 200) == [(0, 100), (150, 170)]
    assert tracing.busy_s(tr) == pytest.approx(120e-9)
    assert tracing.window_s(tr) == pytest.approx(200e-9)


def test_op_breakdown_and_idle_gaps():
    tr = _synthetic()
    assert tracing.top_ops(tr) == [["fusion", pytest.approx(40e-9)],
                                   ["copy", pytest.approx(20e-9)]]
    spans = [("harvest", 105, 145), ("scheduler.step", 100, 180)]
    gaps = dict(tracing.idle_gaps(tr, spans))
    assert gaps["harvest"] == pytest.approx(50e-9)      # 100..150
    assert gaps["waiting"] == pytest.approx(30e-9)      # 170..200


def test_kernel_kinds_from_the_hlo_text():
    call = 'custom-call(s32[2]{0} %a), custom_call_target="tpu_custom_call"'
    fwd = "%c.1 = (bf16[16,12,1536,128]{3,2,1,0}, f32[16,12,1,1536]{3,2,1,0}) " + call
    dq = "%c.2 = (bf16[16,12,1536,128]{3,2,1,0}, bf16[16,12,1536,128]{3,2,1,0}) " + call
    dkv = "%c.3 = (f32[16,12,1536,128]{3,2,1,0}, f32[16,12,1536,128]{3,2,1,0}) " + call
    assert tracing.kernel_kind(fwd, "jit_step(9)") == "winattn_fwd"
    assert tracing.kernel_kind(dq, "jit_step(9)") == "winattn_dq"
    assert tracing.kernel_kind(dkv, "jit_step(9)") == "winattn_dkv"
    assert tracing.kernel_kind(dq, "jit_decode(3)") == "decode_attn"
    assert tracing.kernel_kind("%fusion.1 = bf16[2] fusion()", "jit_step") is None


def test_reduction_of_a_recorded_chip_trace():
    from jax.profiler import ProfileData
    tr = tracing.from_profile(ProfileData.from_file(RECORDED))
    busy, win = tracing.busy_s(tr), tracing.window_s(tr)
    assert 0.1 < busy < win
    kinds = tracing.kernel_events(tr)
    assert set(kinds) == {"winattn_fwd", "winattn_dq", "winattn_dkv"}
    ops = dict(tracing.top_ops(tr))
    assert all(v > 0 for v in ops.values())
    assert ops["winattn_fwd"] == pytest.approx(
        sum(b - a for a, b in kinds["winattn_fwd"]) / 1e9)
    step = [h for h in tr.host if h[0] == "train.step"]
    gaps = dict(tracing.idle_gaps(tr, step))
    assert gaps["train.step"] > 1e-3 and sum(gaps.values()) == pytest.approx(
        win - busy)
