"""A tiny cell of each kind, written into a copy of the benchmark as a
later PR would add them: a config, traffic mixes, a generator, a measured
knee, limits, a metric reader and the workloads in a ``BENCHMARK.json`` of
their own."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

CONFIG = {
    "name": "tiny-lora4", "source": "test", "reduced": [],
    "model": {"name": "tiny", "n_layers": 2, "d_model": 64, "n_heads": 4,
              "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 512,
              "attn_type": "gqa", "qkv_bias": True, "rope_theta": 10000.0,
              "norm_eps": 1e-6, "tie_embeddings": True, "window": 48,
              "attn_impl": "pallas", "dti_sum_token": True,
              "dti_sum_alibi": True, "dti_sum_isolated": True,
              "dti_reset": True, "reset_y_min": 0.0, "reset_y_max": 0.3,
              "lora_rank": 4, "remat": True, "remat_policy": "nothing",
              "param_dtype": "float32", "compute_dtype": "float32"},
    "optimizer": {"lr": 0.001, "betas": [0.9, 0.95], "eps": 1e-8,
                  "weight_decay": 0.001, "grad_clip": 1.0, "schedule": "const",
                  "warmup_steps": 1, "total_steps": 100},
    "train": {"rows_per_step": 4},
    "serve": {"n_slots": 2, "capacity": 128, "buckets": [32, 64],
              "prefill_budget": 64, "page_size": 16, "n_pages": 64},
}
TRAIN_MIX = {"kind": "train", "generator": "dti_corpus", "paradigm": "dti", "n_ctx": 6, "k": 4,
             "history_min": 10, "history_max": 30, "train_frac": 0.8,
             "n_items": 50, "pack": True, "window_cap": 1024, "batches": 2,
             "shape_seed": 3}
SERVE_MIX = {"kind": "serve", "generator": "open_loop", "knee": "tiny-serve",
             "load": 0.8, "n_ctx": 4, "n_ctx_tail": 8,
             "tail_alpha": 1.5, "k": 4, "repeat_frac": 0.25, "n_users": 10,
             "history": 20, "n_items": 50, "warm_requests": 2, "shape_seed": 5,
             "check_requests": 4, "check_row_tokens": 96, "trace_seconds": 1}
# float32 compute: the program and the reference differ by summation order
# (~1e-7); the serving cache is bfloat16 (~2e-3 in log-odds at this size)
# what the knee sweep writes: the rate is 0.8 x 12.5 = 10 req/s
KNEE = {"knee_req_per_s": 12.5}
# one user's prompts to a row, unpacked: a generator added by file
GENERATOR = '''"""Unpacked rows of the DTI corpus."""
from bench.traffic import dti_corpus


def geometry(mix):
    return dti_corpus.geometry(mix)


def batches(mix, vocab, rows, seed):
    return dti_corpus.batches(dict(mix, pack=False), vocab, rows, seed)
'''
LIMITS = {"tiny.train": {"loss1_gap": 1e-4, "grad_gap": 1e-3, "delta_gap": 1e-3},
          "tiny.serve": {"score_gap": 2e-2, "unanswered": 0.0}}
LIMITS["tiny.unpacked"] = LIMITS["tiny.train"]
METRIC = '''"""A metric a later PR adds by file: the window's steps."""


def read(ctx):
    if ctx.get("kind") == "train":
        return float(len(ctx["window_batches"]))
    return None
'''


def make_root(root: str) -> str:
    """A checkout of the benchmark's code with the tiny cells added."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(
                        "tests", ".jax_cache", ".out", "__pycache__",
                        "configs", "limits", "*.json"))
    for d in ("configs", "limits"):
        os.makedirs(os.path.join(root, "bench", d))

    def put(rel, obj):
        with open(os.path.join(root, "bench", rel), "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))
    put("configs/tiny-lora4.json", CONFIG)
    put("traffic/tiny-train.json", TRAIN_MIX)
    put("traffic/tiny-serve.json", SERVE_MIX)
    put("traffic/tiny-serve.knee.json", KNEE)
    put("traffic/tiny_unpacked.py", GENERATOR)
    put("traffic/tiny-unpacked.json", dict(TRAIN_MIX, generator="tiny_unpacked"))
    for k, v in LIMITS.items():
        put(f"limits/{k}.json", v)
    put("metrics/tiny_window_steps.py", METRIC)
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1, "configs": [],
        "workloads": [
            {"name": "tiny.train", "config": "tiny-lora4",
             "traffic": "tiny-train", "chips": 1, "why": "test"},
            {"name": "tiny.serve", "config": "tiny-lora4",
             "traffic": "tiny-serve", "chips": 1, "why": "test"},
            {"name": "tiny.unpacked", "config": "tiny-lora4",
             "traffic": "tiny-unpacked", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "train_targets_per_s", "unit": "targets/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny.train"]},
            {"name": "serve_tts_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock", "workloads": ["tiny.serve"]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "tiny_window_steps", "unit": "steps", "better": "higher",
             "source": "program_counter", "layer": "train step",
             "moves": "train_targets_per_s", "workloads": ["tiny.train"]},
            {"name": "train_pad_frac", "unit": "%", "better": "lower",
             "source": "program_counter", "layer": "packing",
             "moves": "train_targets_per_s", "workloads": ["tiny.train"]},
            {"name": "sched_host_ms.rate80", "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "scheduler",
             "moves": "serve_tts_p95_ms", "workloads": ["tiny.serve"]}],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root
