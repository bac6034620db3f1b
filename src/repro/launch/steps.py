"""Cell builders: (arch, shape, mesh) -> step fn + sharded input specs.

A *cell* is one graded (architecture x input-shape) combination. For each,
``build_cell`` returns the real step function (train step incl. optimizer
update, prefill, decode, serve or retrieval — whatever the shape's kind
dictates) plus ShapeDtypeStruct stand-ins for every input with NamedSharding
attached, so a caller can ``jit(...).lower(*args).compile()`` without
allocating anything.

The same builders back the smoke tests (pass ``smoke=True`` + the CPU mesh)
and the compile sweep of ``tests/test_sharding.py``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.configs.base import ArchSpec, ShapeSpec
from repro.core.dti import SpecialTokens
from repro.core.losses import ctr_loss
from repro.models.gnn import gin_forward, gin_graph_forward, init_gin
from repro.models.recsys import (RecsysConfig, _din_attend, bce_loss,
                                 init_recsys, mind_retrieval, recsys_logits,
                                 sasrec_encode)
from repro.models.transformer import ModelConfig, forward, init_params
from repro.serve.cache import init_lm_cache
from repro.serve.engine import make_decode_fn, make_prefill_fn
from repro.sharding.partition import (batch_spec, make_param_specs, rules_for,
                                      zero1_specs)
from repro.sparse.embedding import embedding_lookup
from repro.train.optimizer import (OptimizerConfig, adamw_update,
                                   freeze_non_lora, init_opt_state)
from repro.train.trainer import TrainState

SP = SpecialTokens()


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    step_fn: Callable
    args: Tuple[Any, ...]
    donate: Tuple[int, ...]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _attach(shapes: Any, specs: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sp),
        shapes, specs)


def _sds(mesh, shape, dtype, *axes) -> jax.ShapeDtypeStruct:
    from repro.sharding.partition import spec_for_shape
    # divisibility-aware: batch=1 cells (long_500k, retrieval queries) drop
    # the data axis instead of failing the explicit input sharding
    spec = spec_for_shape(shape, tuple(axes), mesh)
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _replicated_specs(tree: Any, mesh) -> Any:
    return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tree)


def _train_state_specs(params_shape, ocfg, rules, mesh, *, zero1=True,
                       zero1_axis="data", param_axis=None):
    param_specs = make_param_specs(params_shape, rules, mesh)
    if param_axis is not None:
        # ZeRO-3: shard every param's largest dim over `param_axis`; XLA
        # all-gathers the (bf16) weights per layer inside the scan and
        # reduce-scatters their gradients — no full grad/master tree ever
        # exists on one device.
        param_specs = zero1_specs(params_shape, param_specs, mesh,
                                  axis=param_axis)
    opt_shape = jax.eval_shape(partial(init_opt_state, ocfg), params_shape)
    repl = NamedSharding(mesh, P())

    def opt_tree_specs(tree_shape):
        sp = make_param_specs(tree_shape, rules, mesh)
        return (zero1_specs(tree_shape, sp, mesh, axis=zero1_axis)
                if zero1 else sp)

    opt_specs = type(opt_shape)(
        step=repl,
        mu=opt_tree_specs(opt_shape.mu),
        nu=opt_tree_specs(opt_shape.nu),
        master=(opt_tree_specs(opt_shape.master)
                if opt_shape.master is not None else None),
    )
    state_shape = TrainState(params=params_shape, opt=opt_shape, ef_error=None)
    state_specs = TrainState(params=param_specs, opt=opt_specs, ef_error=None)
    return state_shape, state_specs, opt_specs.mu


def _make_train_step(loss_fn, ocfg, *, grad_accum: int = 1,
                     grad_shardings=None, batch_shardings=None):
    """Train step with optional gradient-accumulation microbatching: the
    global batch is split on axis 0 into ``grad_accum`` microbatches scanned
    sequentially — per-device activation memory scales 1/grad_accum while
    the optimizer still sees the full-batch gradient.

    ``grad_shardings`` (pytree of NamedSharding mirroring params) pins the
    fp32 accumulator's layout: GSPMD does not infer scan-carry shardings, so
    without the constraint the accumulator replicates (2 x params x 4B of
    temp per device — the difference between fitting HBM and not)."""

    from repro.train.optimizer import _trainable_mask

    def constrain(tree):
        if grad_shardings is None:
            return tree
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s)
            if x.ndim else x, tree, grad_shardings)

    def train_step(state: TrainState, batch):
        mask = _trainable_mask(ocfg, state.params)
        if grad_accum <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
            # ZeRO-1: land gradients directly in the optimizer-state layout
            # — reduce-scatter in bf16 FIRST, upcast on the shard (casting
            # before the constraint materialises the full fp32 grad tree,
            # +10.9 GiB/device for minicpm-2b)
            grads = constrain(grads)
            grads = jax.tree_util.tree_map(
                lambda g, m: g.astype(jnp.float32) if m else g, grads, mask)
        else:
            # (B, ...) -> (A, B/A, ...); re-pin the batch sharding onto the
            # new axis 1 — after the reshape GSPMD would otherwise try to
            # shard axis 0 (= A, usually not divisible) and fall back to
            # fully replicated microbatches, silently dropping DP.
            def split(x, ns=None):
                y = x.reshape(grad_accum, x.shape[0] // grad_accum,
                              *x.shape[1:])
                if ns is not None:
                    y = jax.lax.with_sharding_constraint(
                        y, NamedSharding(ns.mesh, P(None, *ns.spec)))
                return y

            if batch_shardings is not None:
                mb = jax.tree_util.tree_map(
                    lambda x, s: split(x, s.sharding), batch,
                    batch_shardings)
            else:
                mb = jax.tree_util.tree_map(split, batch)

            def micro(carry, b):
                g_acc, l_acc = carry
                l, g = jax.value_and_grad(loss_fn)(state.params, b)
                # frozen leaves keep a scalar accumulator (their grads are
                # zero and DCE'd — no 236B fp32 carries for PEFT archs)
                g_acc = jax.tree_util.tree_map(
                    lambda a, gg, m: a + gg.astype(jnp.float32) if m else a,
                    g_acc, g, mask)
                return (constrain(g_acc), l_acc + l), None

            zeros = constrain(jax.tree_util.tree_map(
                lambda p, m: (jnp.zeros(p.shape, jnp.float32) if m
                              else jnp.zeros((), jnp.float32)),
                state.params, mask))
            (grads, loss), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), mb)
            inv = 1.0 / grad_accum
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            loss = loss * inv
        params, opt, stats = adamw_update(ocfg, grads, state.opt,
                                          state.params,
                                          shard_specs=grad_shardings)
        return TrainState(params, opt, state.ef_error), {
            "loss": loss, **stats}
    return train_step


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def _lm_opt_cfg(spec: ArchSpec, profile: str = "tp") -> OptimizerConfig:
    sched = "wsd" if "minicpm-2b" in spec.name else "cosine"
    # dp profile: params replicated in bf16; a separate fp32 master copy
    # forces XLA to materialise/gather full fp32 param-sized buffers around
    # the update (+12 GiB/dev, §Perf log). Without it the update fuses
    # elementwise; mu/nu stay fp32 (sharded ZeRO-1), so the second moment
    # keeps full precision and only the weight storage is bf16.
    return OptimizerConfig(lr=1e-4, schedule=sched, warmup_steps=100,
                           total_steps=10_000, trainable=spec.trainable,
                           master_fp32=(profile != "dp"))


def _lm_batch_specs(mesh, b, s, *, axis="data"):
    return {
        "tokens": _sds(mesh, (b, s), jnp.int32, axis, None),
        "positions": _sds(mesh, (b, s), jnp.int32, axis, None),
        "is_sum": _sds(mesh, (b, s), jnp.bool_, axis, None),
        "labels": _sds(mesh, (b, s), jnp.int32, axis, None),
        "valid": _sds(mesh, (b, s), jnp.bool_, axis, None),
    }


def _lm_train_cell(spec: ArchSpec, shape: ShapeSpec, mesh, cfg: ModelConfig,
                   overrides: Dict[str, Any]) -> Cell:
    p = dict(shape.params)
    b, s, win = p["global_batch"], p["seq_len"], p["window"]
    if "global_batch" in overrides:
        b = overrides["global_batch"]
    grad_accum = overrides.get("grad_accum", p.get("grad_accum", 1))
    grad_accum = max(1, min(grad_accum, b))         # smoke batches are tiny
    if b % grad_accum:
        grad_accum = 1
    ocfg = _lm_opt_cfg(spec, overrides.get("profile", spec.profile))
    lora = spec.trainable == "lora"

    def loss_fn(params, batch):
        if lora:
            params = freeze_non_lora(params)
        out = forward(params, cfg, batch["tokens"],
                      positions=batch["positions"], is_sum=batch["is_sum"],
                      valid=batch["valid"], dti_enabled=True, window=win)
        loss, _ = ctr_loss(params, cfg, out["hidden"], batch["is_sum"],
                           batch["labels"], yes_id=SP.yes, no_id=SP.no)
        return loss + out["aux_loss"]

    params_shape = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    profile = overrides.get("profile", spec.profile)
    rules = rules_for("lm", "tp" if profile == "zero3" else profile)
    state_shape, state_specs, mu_specs = _train_state_specs(
        params_shape, ocfg, [] if profile == "zero3" else rules, mesh,
        zero1_axis=(("data", "model") if profile in ("dp", "zero3")
                    else "data"),
        param_axis="model" if profile == "zero3" else None)
    # pure DP: the batch spreads over the WHOLE mesh (every device is a
    # data shard) when the microbatch still divides it; otherwise fall back
    # to the data axis + accumulation
    from repro.sharding.partition import _axis_size, _resolve_axis
    batch_axis = "data"
    if profile == "dp":
        full = _axis_size(_resolve_axis(("data", "model"), mesh), mesh)
        if b % full == 0:
            # full-mesh DP: accumulation capped so every microbatch still
            # spans the whole mesh (usually accum=1 at 1 seq/device)
            batch_axis = ("data", "model")
            grad_accum = max(1, min(grad_accum, b // full))
    batch_sds = _lm_batch_specs(mesh, b, s, axis=batch_axis)
    return Cell(spec.name, shape.name, "train",
                _make_train_step(loss_fn, ocfg, grad_accum=grad_accum,
                                 grad_shardings=mu_specs,
                                 batch_shardings=batch_sds),
                (_attach(state_shape, state_specs), batch_sds),
                donate=(0,))


def _lm_prefill_cell(spec: ArchSpec, shape: ShapeSpec, mesh,
                     cfg: ModelConfig, overrides) -> Cell:
    p = dict(shape.params)
    b, s, win = p["global_batch"], p["seq_len"], p["window"]
    prefill = make_prefill_fn(cfg, yes_id=SP.yes, no_id=SP.no, window=win)
    chunks = overrides.get("prefill_chunks",
                           p.get("prefill_chunks",
                                 spec.shapes[shape.name].params.get(
                                     "prefill_chunks", 1)))
    from repro.sharding.partition import dp_size
    bc = b // chunks
    if chunks > 1 and b % chunks == 0:
        # sequential batch chunks (lax.map) bound the live token count —
        # same lever as grad-accum microbatching, applied to inference.
        # When the chunk batch no longer divides the full dp extent
        # (multi-pod: bc=16 vs pod x data = 32) fall back to the inner
        # "data" axis and accept pod-replicated chunk compute — fitting HBM
        # beats the idle pod (noted per-cell in EXPERIMENTS.md §Dry-run).
        batch_axis = ("data",)
        if bc % dp_size(mesh) == 0:
            batch_axis = ("data",)          # alias resolves to pod+data
            full = True
        else:
            full = False

        def step(params, batch):
            def split(x):
                y = x.reshape(chunks, bc, *x.shape[1:])
                if full:
                    ns = batch_spec(mesh, "data",
                                    *([None] * (x.ndim - 1)))
                    spec = P(None, *ns.spec)
                else:
                    inner = ("data",) if "data" in mesh.axis_names else ()
                    spec = P(None, inner[0] if inner else None,
                             *([None] * (x.ndim - 1)))
                return jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, spec))
            mb = jax.tree_util.tree_map(split, batch)
            out = jax.lax.map(lambda bb: prefill(params, bb), mb)
            return out.reshape(b, *out.shape[2:])
    else:
        step = prefill
    params_shape = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    # inference: "dp" trains pure-DP but serves Megatron-TP (a 32..128
    # request batch cannot spread over 256 devices; sharded weights can)
    prof = "tp" if spec.profile in ("dp", "zero3") else spec.profile
    param_specs = make_param_specs(params_shape, rules_for("lm", prof), mesh)
    batch_sds = {k: v for k, v in _lm_batch_specs(mesh, b, s).items()
                 if k != "labels"}
    return Cell(spec.name, shape.name, "prefill", step,
                (_attach(params_shape, param_specs), batch_sds),
                donate=())


def _lm_decode_cell(spec: ArchSpec, shape: ShapeSpec, mesh, cfg: ModelConfig,
                    overrides, *, ring: bool) -> Cell:
    p = dict(shape.params)
    b, win = p["global_batch"], p["window"]
    capacity = p["ring_capacity"] if ring else p["cache_len"]
    step = make_decode_fn(cfg, window=win, ring=ring,
                          yes_id=SP.yes, no_id=SP.no)

    params_shape = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    prof = "tp" if spec.profile in ("dp", "zero3") else spec.profile
    param_specs = make_param_specs(params_shape, rules_for("lm", prof), mesh)
    cache_shape_ = jax.eval_shape(
        partial(init_lm_cache, cfg, b, capacity))
    # cache: batch over data, sequence (capacity) over model
    def cache_spec(path, leaf):
        from repro.sharding.partition import leaf_path_str, spec_for_shape
        key = leaf_path_str(path)
        if key in ("pos",):
            return NamedSharding(mesh, spec_for_shape(
                leaf.shape, ("data", "model"), mesh))
        if key in ("cursor",):
            return NamedSharding(mesh, spec_for_shape(
                leaf.shape, ("data",), mesh))
        tpl = (None, "data", "model") + (None,) * (len(leaf.shape) - 3)
        return NamedSharding(mesh, spec_for_shape(leaf.shape, tpl, mesh))
    cache_specs = jax.tree_util.tree_map_with_path(cache_spec, cache_shape_)

    tok_sds = _sds(mesh, (b, 1), jnp.int32, "data", None)
    sum_sds = _sds(mesh, (b, 1), jnp.bool_, "data", None)
    return Cell(spec.name, shape.name, "decode_ring" if ring else "decode",
                step,
                (_attach(params_shape, param_specs),
                 _attach(cache_shape_, cache_specs), tok_sds, tok_sds,
                 sum_sds),
                donate=(1,))


# ---------------------------------------------------------------------------
# RecSys cells
# ---------------------------------------------------------------------------

def _recsys_batch_specs(mesh, cfg: RecsysConfig, b, *, labels: bool):
    out: Dict[str, Any] = {}
    if cfg.kind == "xdeepfm":
        out["ids"] = _sds(mesh, (b, len(cfg.field_vocabs)), jnp.int32,
                          "data", None)
    else:
        out["hist"] = _sds(mesh, (b, cfg.seq_len), jnp.int32, "data", None)
        out["target"] = _sds(mesh, (b,), jnp.int32, "data")
    if labels:
        out["labels"] = _sds(mesh, (b,), jnp.int32, "data")
    return out


def _recsys_train_cell(spec, shape, mesh, cfg: RecsysConfig, overrides) -> Cell:
    b = overrides.get("global_batch", shape.params["batch"])
    ocfg = OptimizerConfig(lr=1e-3, schedule="cosine", total_steps=10_000)

    def loss_fn(params, batch):
        return bce_loss(recsys_logits(params, cfg, batch), batch["labels"])

    params_shape = jax.eval_shape(
        lambda: init_recsys(jax.random.PRNGKey(0), cfg))
    rules = rules_for("recsys")
    state_shape, state_specs, mu_specs = _train_state_specs(
        params_shape, ocfg, rules, mesh)
    batch_sds = _recsys_batch_specs(mesh, cfg, b, labels=True)
    return Cell(spec.name, shape.name, "train",
                _make_train_step(loss_fn, ocfg),
                (_attach(state_shape, state_specs), batch_sds),
                donate=(0,))


def _recsys_serve_cell(spec, shape, mesh, cfg: RecsysConfig, overrides) -> Cell:
    b = overrides.get("global_batch", shape.params["batch"])

    def step(params, batch):
        return jax.nn.sigmoid(
            recsys_logits(params, cfg, batch).astype(jnp.float32))

    params_shape = jax.eval_shape(
        lambda: init_recsys(jax.random.PRNGKey(0), cfg))
    param_specs = make_param_specs(params_shape, rules_for("recsys"), mesh)
    batch_sds = _recsys_batch_specs(mesh, cfg, b, labels=False)
    return Cell(spec.name, shape.name, "serve", step,
                (_attach(params_shape, param_specs), batch_sds),
                donate=())


RETRIEVAL_CHUNK = 8000


def _recsys_retrieval_cell(spec, shape, mesh, cfg: RecsysConfig,
                           overrides) -> Cell:
    c = shape.params["n_candidates"]
    chunk = overrides.get("retrieval_chunk", RETRIEVAL_CHUNK)

    if cfg.kind == "mind":
        def step(params, batch):
            return mind_retrieval(params, cfg, batch["hist"],
                                  batch["cand_ids"])
    elif cfg.kind == "sasrec":
        def step(params, batch):
            h = sasrec_encode(params, cfg, batch["hist"])[:, -1]   # (1, D)
            cand = embedding_lookup(params["items"], batch["cand_ids"])
            return (cand @ h[0]).astype(jnp.float32)
    elif cfg.kind == "din":
        def step(params, batch):
            from repro.models.layers import mlp
            h = embedding_lookup(params["items"], batch["hist"])   # (1,L,D)

            def score_chunk(ids):
                t = embedding_lookup(params["items"], ids)[None]   # (1,c,D)
                user = _din_attend(params, h, t, None)
                x = jnp.concatenate([user, t, user * t], axis=-1)
                return mlp(params["head"], x)[0, :, 0]

            return jax.lax.map(score_chunk, batch["cand_ids"]).reshape(-1)
    elif cfg.kind == "xdeepfm":
        from repro.models.recsys import xdeepfm_forward
        v0 = cfg.field_vocabs[0]

        def step(params, batch):
            def score_chunk(ids):
                full = jnp.broadcast_to(batch["base_ids"],
                                        (ids.shape[0],
                                         len(cfg.field_vocabs)))
                full = full.at[:, 0].set(ids % v0)
                return xdeepfm_forward(params, cfg, full)

            return jax.lax.map(score_chunk, batch["cand_ids"]).reshape(-1)
    else:
        raise ValueError(cfg.kind)

    params_shape = jax.eval_shape(
        lambda: init_recsys(jax.random.PRNGKey(0), cfg))
    param_specs = make_param_specs(params_shape, rules_for("recsys"), mesh)
    batch_sds: Dict[str, Any] = {}
    if cfg.kind in ("din", "xdeepfm"):
        # chunked scoring: lax.map over the leading chunk index, candidates
        # within each chunk shard over the data axis
        if c % chunk:
            chunk = next(cc for cc in range(chunk, 0, -1) if c % cc == 0)
        batch_sds["cand_ids"] = _sds(mesh, (c // chunk, chunk), jnp.int32,
                                     None, "data")
    else:
        # single-shot scoring: candidates shard over data directly
        batch_sds["cand_ids"] = _sds(mesh, (c,), jnp.int32, "data")
    if cfg.kind == "xdeepfm":
        batch_sds["base_ids"] = _sds(mesh, (1, len(cfg.field_vocabs)),
                                     jnp.int32, None, None)
    else:
        batch_sds["hist"] = _sds(mesh, (1, cfg.seq_len), jnp.int32,
                                 None, None)
    return Cell(spec.name, shape.name, "retrieval", step,
                (_attach(params_shape, param_specs), batch_sds),
                donate=())


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------

def _ce_loss(logits, labels, mask=None):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    if mask is None:
        return jnp.mean(nll)
    w = mask.astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def _gnn_cell(spec: ArchSpec, shape: ShapeSpec, mesh, overrides,
              cfg_overrides=None) -> Cell:
    from repro.configs.gin_tu import config_for_shape
    p = dict(shape.params)
    cfg = config_for_shape(p)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    ocfg = OptimizerConfig(lr=1e-3, schedule="cosine", total_steps=5_000)
    kind = shape.kind

    if kind in ("graph_full", "graph_sampled"):
        n = p.get("n_nodes")
        e = p.get("n_edges")
        if kind == "graph_sampled":
            seeds = p["batch_nodes"]
            f = p["fanouts"]
            n = seeds * (1 + f[0] + f[0] * f[1])
            e = seeds * (f[0] + f[0] * f[1])

        def loss_fn(params, batch):
            logits = gin_forward(params, cfg, batch["x"], batch["edge_src"],
                                 batch["edge_dst"],
                                 edge_valid=batch["edge_valid"])
            return _ce_loss(logits, batch["labels"], batch["label_mask"])

        batch_sds = {
            "x": _sds(mesh, (n, cfg.d_feat), jnp.float32, None, None),
            "edge_src": _sds(mesh, (e,), jnp.int32, "data"),
            "edge_dst": _sds(mesh, (e,), jnp.int32, "data"),
            "edge_valid": _sds(mesh, (e,), jnp.bool_, "data"),
            "labels": _sds(mesh, (n,), jnp.int32, None),
            "label_mask": _sds(mesh, (n,), jnp.bool_, None),
        }
    elif kind == "graph_batched":
        bsz, nn, ne = p["batch"], p["n_nodes"], p["n_edges"]
        n, e = bsz * nn, bsz * ne

        def loss_fn(params, batch):
            logits = gin_graph_forward(params, cfg, batch["x"],
                                       batch["edge_src"], batch["edge_dst"],
                                       batch["graph_ids"], bsz,
                                       edge_valid=batch["edge_valid"])
            return _ce_loss(logits, batch["labels"])

        batch_sds = {
            "x": _sds(mesh, (n, cfg.d_feat), jnp.float32, None, None),
            "edge_src": _sds(mesh, (e,), jnp.int32, "data"),
            "edge_dst": _sds(mesh, (e,), jnp.int32, "data"),
            "edge_valid": _sds(mesh, (e,), jnp.bool_, "data"),
            "graph_ids": _sds(mesh, (n,), jnp.int32, None),
            "labels": _sds(mesh, (bsz,), jnp.int32, None),
        }
    else:
        raise ValueError(kind)

    params_shape = jax.eval_shape(lambda: init_gin(jax.random.PRNGKey(0),
                                                   cfg))
    rules = rules_for("gnn")
    state_shape, state_specs, _ = _train_state_specs(params_shape, ocfg,
                                                     rules, mesh, zero1=False)
    return Cell(spec.name, shape.name, kind,
                _make_train_step(loss_fn, ocfg),
                (_attach(state_shape, state_specs), batch_sds),
                donate=(0,))


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def build_cell(arch_name: str, shape_name: str, mesh, *,
               smoke: bool = False,
               overrides: Optional[Dict[str, Any]] = None) -> Cell:
    spec = get_arch(arch_name)
    shape = spec.shape(shape_name)
    overrides = dict(overrides or {})
    cfg_overrides = overrides.pop("config", {})
    # activation pinning (repro.sharding.act): measured NET-HARMFUL for
    # tp/fsdp_tp (GSPMD re-reshards around the pins: qwen2-moe prefill
    # 13.2 -> 86.5 GiB/dev, §Perf log) and essential for the dp profile
    # (weight-grad contractions would gather global activations). Default:
    # only the dp profile pins.
    act_shard = overrides.pop(
        "act_shard",
        spec.family == "lm"
        and overrides.get("profile", spec.profile) == "dp")
    cell = _build_cell_inner(spec, shape, mesh, smoke=smoke,
                             overrides=overrides,
                             cfg_overrides=cfg_overrides)
    if act_shard and not smoke:
        from repro.sharding.act import with_activation_mesh
        profile = (overrides.get("profile", spec.profile))
        if profile in ("dp", "zero3") and cell.kind != "train":
            profile = "tp"                      # inference serves TP
        batch_axis = ("data", "model") if profile == "dp" else "data"
        tensor_axis = "model" if profile in ("tp", "fsdp_tp") else None
        cell.step_fn = with_activation_mesh(cell.step_fn, mesh, batch_axis,
                                            tensor_axis)
    return cell


def _build_cell_inner(spec, shape, mesh, *, smoke, overrides,
                      cfg_overrides) -> Cell:

    if spec.family == "lm":
        cfg = spec.smoke if smoke else spec.config
        if cfg_overrides:
            cfg = dataclasses.replace(cfg, **cfg_overrides)
        if smoke:
            shape = _shrink_lm_shape(shape, cfg)
        if shape.kind == "train":
            return _lm_train_cell(spec, shape, mesh, cfg, overrides)
        if shape.kind == "prefill":
            return _lm_prefill_cell(spec, shape, mesh, cfg, overrides)
        if shape.kind == "decode":
            return _lm_decode_cell(spec, shape, mesh, cfg, overrides,
                                   ring=False)
        if shape.kind == "decode_ring":
            return _lm_decode_cell(spec, shape, mesh, cfg, overrides,
                                   ring=True)
        raise ValueError(shape.kind)

    if spec.family == "recsys":
        cfg = spec.smoke if smoke else spec.config
        if cfg_overrides:
            cfg = dataclasses.replace(cfg, **cfg_overrides)
        if smoke:
            shape = _shrink_recsys_shape(shape)
        if shape.kind == "train":
            return _recsys_train_cell(spec, shape, mesh, cfg, overrides)
        if shape.kind == "serve":
            return _recsys_serve_cell(spec, shape, mesh, cfg, overrides)
        if shape.kind == "retrieval":
            return _recsys_retrieval_cell(spec, shape, mesh, cfg, overrides)
        raise ValueError(shape.kind)

    if spec.family == "gnn":
        if smoke:
            shape = _shrink_gnn_shape(shape)
        return _gnn_cell(spec, shape, mesh, overrides, cfg_overrides)

    raise ValueError(spec.family)


def _shrink_lm_shape(shape: ShapeSpec, cfg: ModelConfig) -> ShapeSpec:
    p = dict(shape.params)
    win = cfg.window or 32
    p["window"] = win
    p["global_batch"] = 2
    if "seq_len" in p:
        p["seq_len"] = 4 * win
    if "cache_len" in p:
        p["cache_len"] = 2 * win
    if "ring_capacity" in p:
        p["ring_capacity"] = 2 * win
    return ShapeSpec(shape.name, shape.kind, p)


def _shrink_recsys_shape(shape: ShapeSpec) -> ShapeSpec:
    p = dict(shape.params)
    if "batch" in p:
        p["batch"] = 8
    if "n_candidates" in p:
        p["n_candidates"] = 64
    return ShapeSpec(shape.name, shape.kind, p)


def _shrink_gnn_shape(shape: ShapeSpec) -> ShapeSpec:
    p = dict(shape.params)
    for k, v in [("n_nodes", 128), ("n_edges", 512), ("batch_nodes", 8),
                 ("batch", 4)]:
        if k in p:
            p[k] = v
    if "fanouts" in p:
        p["fanouts"] = (3, 2)
    if "n_nodes_raw" in p:
        p["n_nodes_raw"], p["n_edges_raw"] = 100, 400
    p["d_feat"] = min(p.get("d_feat", 16), 16)
    p["n_classes"] = min(p.get("n_classes", 4), 4)
    return ShapeSpec(shape.name, shape.kind, p)


__all__ = ["Cell", "build_cell", "RETRIEVAL_CHUNK"]
