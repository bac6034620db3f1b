"""Plain reference of the DTI transformer (GQA and MLA), in float32.

Written from the published descriptions (Qwen2 / MiniCPM3 layers: pre-norm
RMSNorm, RoPE half-split, SwiGLU, LoRA ``W + (alpha/r) A B``; MLA per
DeepSeek-V2) and the DTI paper's attention rules, with no import from the
program:

* causal attention within ``window`` positions, keys of the query's own
  segment only (packed rows) — or, for a serving row, the shared context
  (segment 0) plus the candidate's own segment;
* a [SUM] key is attended by itself alone;
* a [SUM] query scores the unrotated q and k with an ALiBi bias
  ``-slope * distance`` (NoPE + ALiBi);
* training only: a [SUM] query's value aggregate is reset towards the
  values of the initial hidden states, ``(1 - a(d)) V(h) + a(d) V(h0)``
  with ``a(d) = y_min + (y_max - y_min) sigmoid(d - window / 2)``;
* the click probability is the softmax of the (yes, no) rows of the tied
  embedding at each [SUM] position.

Every matrix product goes through ``mm``, at ``Precision.HIGHEST`` (a TPU
otherwise multiplies float32 in bfloat16). With ``low=True`` each product's
operands — and in the backward pass its cotangents — are first rounded to
scaled float8 (e4m3 forward, e5m2 backward): the control, one precision
step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
YES, NO = 3, 4


def _round8(x, dtype):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def lowp(x):
    return _round8(x, jnp.float8_e4m3fn)


def _lowp_fwd(x):
    return lowp(x), None


def _lowp_bwd(_, g):
    return (_round8(g, jnp.float8_e5m2),)


lowp.defvjp(_lowp_fwd, _lowp_bwd)


def mm(eq, a, b, low=False):
    if low:
        a, b = lowp(a), lowp(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def rmsnorm(p, x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def linear(p, x, low):
    y = mm("...i,io->...o", x, p["w"], low)
    if "lora_a" in p:
        y = y + p["lora_scale"] * mm("...r,ro->...o",
                                     mm("...i,ir->...r", x, p["lora_a"], low),
                                     p["lora_b"], low)
    if "b" in p:
        y = y + p["b"]
    return y


def rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None].astype(jnp.float32) * inv         # (B, S, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def alibi_slopes(n):
    def pow2(m):
        start = 2.0 ** (-(2.0 ** -(math.log2(m) - 3)))
        return [start * start ** i for i in range(m)]
    if math.log2(n).is_integer():
        s = pow2(n)
    else:
        c = 2 ** math.floor(math.log2(n))
        s = pow2(c) + pow2(2 * c)[0::2][:n - c]
    return jnp.asarray(s, jnp.float32)


def mask(rows, window, serve):
    """(B, S, S) attendability from the row's positions, segments, [SUM]
    flags and validity."""
    pos, seg = rows["positions"], rows["segment_ids"]
    d = pos[:, :, None] - pos[:, None, :]
    m = (d >= 0) & (d <= window) & rows["valid"][:, None, :]
    same = seg[:, :, None] == seg[:, None, :]
    if serve:
        same = same | (seg[:, None, :] == 0)
    m = m & same & (~rows["is_sum"][:, None, :] | (d == 0))
    return m, d.astype(jnp.float32)


def attend(q, k, v, q_np, k_np, v0, rows, m, d, *, scale, reset, low):
    """q (B,S,H,Dqk), k/v (B,S,Hk,·); returns (B,S,H,Dv)."""
    h = q.shape[2]
    rep = h // k.shape[2]
    k, v, k_np = (jnp.repeat(t, rep, axis=2) for t in (k, v, k_np))
    s_rope = mm("bqhd,bkhd->bhqk", q, k, low) * scale
    s_nope = (mm("bqhd,bkhd->bhqk", q_np, k_np, low) * scale
              - alibi_slopes(h)[None, :, None, None] * d[:, None])
    is_sum = rows["is_sum"][:, None, :, None]
    s = jnp.where(is_sum, s_nope, s_rope)
    s = jnp.where(m[:, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    p = jnp.where(jnp.any(m, -1)[:, None, :, None], p, 0.0)
    out = mm("bhqk,bkhd->bqhd", p, v, low)
    if reset is not None:
        v0 = jnp.repeat(v0, rep, axis=2)
        a = reset[0] + (reset[1] - reset[0]) * jax.nn.sigmoid(
            jnp.maximum(d, 0) - reset[2])
        out = out + mm("bhqk,bkhd->bqhd", p * a[:, None] * is_sum,
                       v0 - v, low)
    return out


def _gqa_proj(ap, x, rows, cfg, low):
    b, s, _ = x.shape
    hd = cfg["head_dim"]
    q = linear(ap["q"], x, low).reshape(b, s, cfg["n_heads"], hd)
    k = linear(ap["k"], x, low).reshape(b, s, cfg["n_kv_heads"], hd)
    v = linear(ap["v"], x, low).reshape(b, s, cfg["n_kv_heads"], hd)
    pos = rows["positions"]
    th = cfg["rope_theta"]
    return rope(q, pos, th), rope(k, pos, th), v, q, k, hd ** -0.5


def _mla_proj(ap, x, rows, cfg, low):
    b, s, _ = x.shape
    h, dn, dr, dv = (cfg["n_heads"], cfg["qk_nope_dim"], cfg["qk_rope_dim"],
                     cfg["v_head_dim"])
    qc = rmsnorm(ap["q_norm"], linear(ap["q_down"], x, low))
    q = linear(ap["q_up"], qc, low).reshape(b, s, h, dn + dr)
    ckv = rmsnorm(ap["kv_norm"], linear(ap["kv_down"], x, low))
    kv = linear(ap["kv_up"], ckv, low).reshape(b, s, h, dn + dv)
    k_pe = linear(ap["k_rope"], x, low).reshape(b, s, 1, dr)
    pos, th = rows["positions"], cfg["rope_theta"]
    q_n, q_pe, k_n, v = q[..., :dn], q[..., dn:], kv[..., :dn], kv[..., dn:]
    k_pe_b = jnp.broadcast_to(k_pe, (b, s, h, dr))
    k_rot = jnp.broadcast_to(rope(k_pe, pos, th), (b, s, h, dr))
    return (jnp.concatenate([q_n, rope(q_pe, pos, th)], -1),
            jnp.concatenate([k_n, k_rot], -1), v,
            jnp.concatenate([q_n, q_pe], -1),
            jnp.concatenate([k_n, k_pe_b], -1), (dn + dr) ** -0.5)


def layer(lp, h, h0, rows, m, d, cfg, *, window, train, low):
    lp = f32(lp)
    proj = _mla_proj if cfg["attn_type"] == "mla" else _gqa_proj
    x = rmsnorm(lp["ln_attn"], h, cfg["norm_eps"])
    q, k, v, q_np, k_np, scale = proj(lp["attn"], x, rows, cfg, low)
    reset = v0 = None
    if train:
        reset = (cfg["reset_y_min"], cfg["reset_y_max"], window / 2.0)
        v0 = proj(lp["attn"], h0, rows, cfg, low)[2]
    a = attend(q, k, v, q_np, k_np, v0, rows, m, d, scale=scale,
               reset=reset, low=low)
    b, s = h.shape[:2]
    h = h + linear(lp["attn"]["o"], a.reshape(b, s, -1), low)
    x = rmsnorm(lp["ln_ffn"], h, cfg["norm_eps"])
    f = lp["ffn"]
    g = jax.nn.silu(linear(f["gate"], x, low)) * linear(f["up"], x, low)
    return h + linear(f["down"], g, low)


def ctr_logits(params, rows, cfg, *, window, train, low):
    """(B, S, 2) yes/no logits at every position of ``rows``."""
    m, d = mask(rows, window, serve=not train)
    emb = params["embed"]
    h0 = jnp.take(emb, rows["tokens"], axis=0).astype(jnp.float32)

    @partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def body(h, lp):
        return layer(lp, h, h0, rows, m, d, cfg, window=window, train=train,
                     low=low), None

    h, _ = jax.lax.scan(body, h0, params["stack"])
    h = rmsnorm(f32(params["ln_f"]), h, cfg["norm_eps"])
    w2 = jnp.take(emb, jnp.asarray([YES, NO]), axis=0).astype(jnp.float32)
    return mm("bsd,vd->bsv", h, w2, low)


def split_lora(params):
    """-> (adapter factors, everything else) as two same-structure trees
    with ``None`` holes."""
    def is_lora(path):
        return getattr(path[-1], "key", None) in ("lora_a", "lora_b")
    lo = jax.tree_util.tree_map_with_path(
        lambda p, x: x if is_lora(p) else None, params)
    rest = jax.tree_util.tree_map_with_path(
        lambda p, x: None if is_lora(p) else x, params)
    return lo, rest


def merge(lo, rest):
    return jax.tree_util.tree_map(lambda a, b: b if a is None else a, lo, rest,
                                  is_leaf=lambda x: x is None)
