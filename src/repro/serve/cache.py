"""Decode KV caches: full-length and ring-buffer (windowed), GQA and MLA,
with refcounted context blocks for cross-request prefix sharing, in two
layouts — contiguous per-row capacity, or a global page pool addressed
through per-row page tables (``init_lm_cache(page_size=...)``; allocation
state lives host-side in ``repro.serve.pages.PagePool``, the prefix index
in ``repro.data.requests.RadixTree``; see docs/serving.md).

Layout: per-layer tensors are stacked on a leading L dim so the decode step
can ``lax.scan`` over (layer params, layer cache) — HLO stays O(1) in depth.
Slot bookkeeping (``pos``, ``cursor``, ``ref``) is shared across layers
(every layer writes the same slots).

* GQA cache: k/v per head — ``k (L, B, cap, Hk, dk)``, ``v (L, B, cap, Hk, dv)``.
* MLA cache: the **latent** per token — ``ckv (L, B, cap, r_kv)``,
  ``kpe (L, B, cap, d_rope)``. Caching the latent instead of expanded heads
  is what makes deepseek-v2 decode storable (0.58 KB/token/layer instead of
  ~82 KB); attention runs in absorbed form (see repro.serve.engine).

Ring mode (``ring=True``): capacity is a constant independent of the logical
position — the windowed causal attention the paper trains with guarantees no
query ever needs a key older than ``window``, so ``long_500k`` decode is
O(window) in both memory and FLOPs. ``ring`` is static (baked into the
jitted step), not a traced value.

Refcounted context blocks (``ref (B,)``): a row's committed prefix (the
tokens at slots ``0..cursor-1``) is a *context block* that more than one
request may score bursts against — cross-request prefix sharing, see
``repro.serve.scheduler`` and docs/serving.md. ``retain_slots`` takes a
reference on a row, ``free_slots`` drops one; a row's ``pos``/``cursor``
reset only when its last reference is dropped. The invariant the scheduler
maintains is ``ref[row] == (#active requests on the row) + (1 if the row's
context is retained for future reuse else 0)`` — so a finished request's
context survives eviction exactly as long as something (an in-flight
sharer, or the retention policy) still holds a reference.

The cache is donated through every jitted op that rewrites it, and the
scheduler always rebinds from the op's return — a single linear chain of
cache values. That chain is also what makes one-step-ahead overlap
dispatch safe: step t+1 consumes step t's output cache on device, so step
ordering is a data dependency, not a host-side sync.
"""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from repro.models.transformer import ModelConfig

#: A decode cache is a flat dict pytree. Per-layer KV tensors are stacked
#: on a leading layer dim (``k``/``v`` for GQA, ``ckv``/``kpe`` for MLA);
#: three per-row bookkeeping arrays are shared by every layer:
#:
#: * ``pos (B, cap) int32``    — the logical position held by each physical
#:   slot; ``-1`` marks an empty/unreachable slot (never attendable). This
#:   is the single source of truth for attendability — KV bytes are never
#:   cleared, they become unreachable via ``pos = -1`` and are overwritten
#:   by the next occupant.
#: * ``cursor (B,) int32``     — the next physical slot a committed write
#:   lands in (equivalently: the row's committed context length when the
#:   cache is not a ring).
#: * ``ref (B,) int32``        — reference count on the row's committed
#:   context block (see module docstring).
Cache = Dict[str, Any]


def init_lm_cache(cfg: ModelConfig, batch: int, capacity: int,
                  *, dtype=jnp.bfloat16, kv_dtype: str = None,
                  page_size: int = None, n_pages: int = None) -> Cache:
    """Allocate a decode cache.

    Contiguous layout (``page_size=None``): KV tensors carry a per-row
    capacity axis — ``(L, B, cap, ...)`` — and a row's committed context
    lives at physical slots ``0..cursor-1`` of its own row.

    Paged layout (``page_size`` set): KV tensors carry one **global** slot
    axis of ``n_pages * page_size`` physical slots shared by every row —
    ``(L, n_pages * page_size, ...)`` — and each row addresses it through
    ``page_table (B, max_pages) int32`` of pool page ids (-1 = unmapped,
    ``max_pages = capacity // page_size``). Logical slot ``j`` of a row
    lives at physical slot ``page_table[row, j // ps] * ps + j % ps`` (see
    ``physical_slots``). ``pos``/``cursor``/``ref`` keep their contiguous
    meaning — they are logical-per-row either way — so the scheduler's
    bookkeeping ops are layout-agnostic. Allocation/refcounting of the
    global pages is host-side state (``repro.serve.pages.PagePool``); the
    device only ever sees the page tables.

    Quantized layout (``kv_dtype="int8"``): KV tensors are stored as int8
    codes plus a small fp32 **scale sidecar** on the same slot axis —
    ``k_scale/v_scale (L, ..., cap, Hk)`` for GQA (one symmetric absmax
    scale per (token slot, kv head), the group RoPE rotates within),
    ``ckv_scale/kpe_scale (L, ..., cap)`` for MLA (the latent has no head
    axis — one scale per token slot per stream). Keeping the scales
    slot-resident rather than literally per-page means incremental
    chunked writes never requantize a neighbour token, and — because the
    sidecar rides the same global slot axis as the codes — a page *is*
    self-describing: adoption, steals and LRU eviction move codes and
    scales together with zero extra bookkeeping (the scale-invariance
    property tests/test_kv_quant.py pins). ``dtype`` is ignored for the
    KV tensors when ``kv_dtype`` is set; dequantization happens at read
    time (dense path) or inside the decode kernel (pallas path).
    """
    l = cfg.n_layers
    assert kv_dtype in (None, "int8"), f"unsupported kv_dtype {kv_dtype!r}"
    quant = kv_dtype == "int8"
    kv_store = jnp.int8 if quant else dtype
    if page_size is not None:
        assert capacity % page_size == 0, (
            f"paged capacity {capacity} must be a multiple of "
            f"page_size {page_size}")
        assert n_pages is not None and n_pages > 0
        kv_rows, kv_cap = 1, n_pages * page_size     # global slot axis
    else:
        kv_rows, kv_cap = batch, capacity
    if cfg.attn_type == "mla":
        tensors = {
            "ckv": jnp.zeros((l, kv_rows, kv_cap, cfg.kv_lora_rank),
                             kv_store),
            "kpe": jnp.zeros((l, kv_rows, kv_cap, cfg.qk_rope_dim),
                             kv_store),
        }
        if quant:
            tensors["ckv_scale"] = jnp.zeros((l, kv_rows, kv_cap),
                                             jnp.float32)
            tensors["kpe_scale"] = jnp.zeros((l, kv_rows, kv_cap),
                                             jnp.float32)
    else:
        hk, dk = cfg.n_kv_heads, cfg.hd
        tensors = {
            "k": jnp.zeros((l, kv_rows, kv_cap, hk, dk), kv_store),
            "v": jnp.zeros((l, kv_rows, kv_cap, hk, dk), kv_store),
        }
        if quant:
            tensors["k_scale"] = jnp.zeros((l, kv_rows, kv_cap, hk),
                                           jnp.float32)
            tensors["v_scale"] = jnp.zeros((l, kv_rows, kv_cap, hk),
                                           jnp.float32)
    if page_size is not None:
        tensors = {k: v[:, 0] for k, v in tensors.items()}   # (L, n_tot, ...)
        tensors["page_table"] = jnp.full((batch, capacity // page_size), -1,
                                         jnp.int32)
    tensors["pos"] = jnp.full((batch, capacity), -1, jnp.int32)
    tensors["cursor"] = jnp.zeros((batch,), jnp.int32)
    tensors["ref"] = jnp.zeros((batch,), jnp.int32)
    return tensors


def is_paged(cache: Cache) -> bool:
    """True when the cache uses the global page-pool layout."""
    return "page_table" in cache


#: Bookkeeping keys present in every cache layout; everything else in the
#: dict is a per-layer KV tensor (codes or scale sidecar).
BOOK_KEYS = ("pos", "cursor", "ref", "page_table")


def kv_keys(cache: Cache):
    """The per-layer KV tensor keys of ``cache`` (codes + scale sidecars),
    in a deterministic order — the order the decode step's scan carry
    threads them."""
    return tuple(k for k in ("k", "v", "k_scale", "v_scale",
                             "ckv", "kpe", "ckv_scale", "kpe_scale")
                 if k in cache)


def is_quantized(cache: Cache) -> bool:
    """True when KV is stored as int8 codes + fp32 scale sidecar."""
    return "k_scale" in cache or "ckv_scale" in cache


def kv_cache_bytes(cache: Cache) -> int:
    """Total bytes of the KV tensors (codes + scale sidecar; bookkeeping
    arrays excluded) — works on concrete caches and ``cache_shape`` specs."""
    total = 0
    for key in kv_keys(cache):
        t = cache[key]
        n = 1
        for d in t.shape:
            n *= d
        total += n * jnp.dtype(t.dtype).itemsize
    return int(total)


def kv_token_bytes(cache: Cache) -> float:
    """KV bytes per token slot, summed over layers (codes + scales): the
    per-token cost a pool budget buys, by which equal-byte int8 and bf16
    pools are sized (``tests/test_kv_quant.py``)."""
    ref = cache["ckv"] if "ckv" in cache else cache["k"]
    n_slots = ref.shape[1]          # global slot axis (paged) or B... cap
    if not is_paged(cache):
        n_slots = ref.shape[1] * ref.shape[2]
    return kv_cache_bytes(cache) / n_slots


def page_size_of(cache: Cache) -> int:
    """Static page size of a paged cache (tokens per page)."""
    cap = cache["pos"].shape[1]
    return cap // cache["page_table"].shape[1]


def physical_slots(cache: Cache):
    """Logical→physical slot map of a paged cache: (B, cap) int32 into the
    global KV slot axis, -1 where the logical slot's page is unmapped.

    This is the gather map both the dense decode path and the Pallas
    decode kernel read KV through: gathering the KV pool with (the
    clamped) map yields the same per-row ``(B, cap, ...)`` view the
    contiguous layout stores directly, and ``pos = -1`` masking makes the
    unmapped entries unattendable exactly like empty contiguous slots.
    """
    pt = cache["page_table"]
    ps = page_size_of(cache)
    batch = pt.shape[0]
    base = pt[:, :, None] * ps + jnp.arange(ps, dtype=jnp.int32)[None, None]
    flat = jnp.where(pt[:, :, None] < 0, -1, base)
    return flat.reshape(batch, -1)


def cache_shape(cfg: ModelConfig, batch: int, capacity: int,
                *, dtype=jnp.bfloat16, kv_dtype: str = None,
                page_size: int = None, n_pages: int = None) -> Dict[str, tuple]:
    """Shapes/dtypes without allocation (ahead-of-time compile specs)."""
    import jax
    return jax.eval_shape(lambda: init_lm_cache(cfg, batch, capacity,
                                                dtype=dtype,
                                                kv_dtype=kv_dtype,
                                                page_size=page_size,
                                                n_pages=n_pages))


def slot_indices(cache: Cache, s_new: int, *, ring: bool):
    """Logical slots the next ``s_new`` tokens occupy: (B, s_new) int32.

    Non-ring indices are *not* wrapped or clamped: a commit that would run
    past ``capacity`` must be rejected at admission time (the scheduler
    raises with the rid and lengths named — see ``ServeScheduler.submit``)
    rather than relying on out-of-bounds scatter writes being dropped.
    """
    cap = cache["pos"].shape[1]
    idx = cache["cursor"][:, None] + jnp.arange(s_new, dtype=jnp.int32)[None]
    return idx % cap if ring else idx


def retain_slots(cache: Cache, counts) -> Cache:
    """Take references on rows: ``counts`` is (B,) bool (one reference per
    True row) or int32 (that many references per row — several requests
    admitted onto one row in the same scheduling wave).

    Each reference is one reason the row's committed context must stay
    readable: an active request scoring bursts against it, or the
    scheduler retaining a finished request's context for future prefix
    reuse. Purely int32 bookkeeping — no KV traffic.
    """
    return dict(cache, ref=cache["ref"] + counts.astype(jnp.int32))


def free_slots(cache: Cache, counts) -> Cache:
    """Drop references on rows — ``counts`` is (B,) bool or int32, as in
    ``retain_slots`` — and reset the touched rows whose count reaches
    zero.

    With prefix sharing a row's committed context may be in use by several
    requests (and/or retained for reuse), so freeing **decrements** instead
    of unconditionally resetting: only when the last reference is dropped
    does the row's position buffer go to -1 (nothing attendable) and its
    cursor to 0. KV bytes are left in place even then — ``pos = -1``
    already makes them unreachable and the next occupant overwrites them —
    so eviction/admission stays O(B·cap) int32 work, no KV traffic.

    A ``free_slots`` on a zero-ref row (the pre-sharing idiom: "reset this
    row now") still resets it: the count saturates at zero rather than
    going negative. Used by the continuous-batching scheduler when a
    request completes, when a retained context is stolen for a new
    admission, and on (re-)admission of rows the legacy way.
    """
    counts = counts.astype(jnp.int32)
    ref = cache["ref"] - counts
    reset = (counts > 0) & (ref <= 0)
    pos = jnp.where(reset[:, None], -1, cache["pos"])
    cursor = jnp.where(reset, 0, cache["cursor"])
    return dict(cache, pos=pos, cursor=cursor, ref=jnp.maximum(ref, 0))


def trim_slots(cache: Cache, mask, keep, *, ring: bool = False) -> Cache:
    """Roll the rows selected by ``mask`` (B,) bool back to their first
    ``keep`` (B,) int32 committed tokens.

    Used when a retained context is reused by a request that shares only a
    *proper* prefix: slots at logical index >= ``keep`` become
    unreachable (``pos = -1``) and the cursor drops to ``keep``, so the
    next committed write extends the shared prefix. Only valid on rows
    with no active readers (the scheduler trims retained rows only) and on
    non-ring caches, where slot index == committed order — on a ring the
    slot holding committed token ``j`` depends on how often the row
    wrapped, so "first ``keep`` tokens" is not an index range and a trim
    would corrupt attendability. ``ring`` is the static flag the caller
    built its cache with; passing ``ring=True`` raises.
    """
    if ring:
        raise ValueError(
            "trim_slots on a ring cache: slot index != committed order, "
            "trimming would corrupt attendability (non-ring caches only)")
    cap = cache["pos"].shape[1]
    idx = jnp.arange(cap, dtype=jnp.int32)[None]
    drop = mask[:, None] & (idx >= keep[:, None])
    pos = jnp.where(drop, -1, cache["pos"])
    cursor = jnp.where(mask, jnp.minimum(cache["cursor"], keep),
                       cache["cursor"])
    return dict(cache, pos=pos, cursor=cursor)


def adopt_slots(cache: Cache, mask, length) -> Cache:
    """Install an already-populated shared prefix on the rows selected by
    ``mask`` (B,) bool: logical slots ``0..length-1`` become attendable at
    positions ``0..length-1`` and the cursor moves to ``length`` (B,)
    int32, *without writing any KV bytes*.

    Paged-cache admission uses this after mapping radix-indexed pages into
    a row's page table: the pages already hold the prefix's KV (committed
    context positions are always ``0..n-1``), so adoption is pure int32
    bookkeeping — the page-table gather makes the bytes reachable and
    ``adopt_slots`` makes them attendable. Slots at and beyond ``length``
    are reset to -1 (the row is assumed freshly reset or stolen).
    Non-ring only, like ``trim_slots``.
    """
    cap = cache["pos"].shape[1]
    idx = jnp.arange(cap, dtype=jnp.int32)[None]
    take = mask[:, None] & (idx < length[:, None])
    pos = jnp.where(take, idx, cache["pos"])
    pos = jnp.where(mask[:, None] & (idx >= length[:, None]), -1, pos)
    cursor = jnp.where(mask, length, cache["cursor"])
    return dict(cache, pos=pos, cursor=cursor)


__all__ = ["Cache", "init_lm_cache", "cache_shape", "slot_indices",
           "retain_slots", "free_slots", "trim_slots", "adopt_slots",
           "is_paged", "page_size_of", "physical_slots",
           "is_quantized", "kv_keys", "kv_cache_bytes", "kv_token_bytes",
           "BOOK_KEYS"]
