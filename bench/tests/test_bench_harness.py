"""The harness end to end on the CPU at a tiny size: cells, a generator
and a metric added by files alone, the chip check, a serving mix refused
without a measured knee, and ``correct`` failing on planted faults and on
the float8 control."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, REPO, os.path.join(REPO, "src")]

import bench_tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))


@pytest.fixture(scope="module", autouse=True)
def _restore_compile_cache():
    """A run points JAX's compile cache into its root; later tests in
    this process get the cache settings they started with."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _run(root, workload, seed, trace=0, fault=None, seconds=1.0):
    from bench import run
    return run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(trace)],
                    root=root, require_chip=False, fault=fault)


def test_cells_added_by_files_run_to_their_last_line(root, capsys):
    res = _run(root, "tiny.train", 2 ** 31 + 11, trace=1)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(last)
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and res["correct"] is True
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in line
    assert list(line)[-1] == "checks"
    # the metric registered by file is read, and so is a shared one
    assert line["metrics"]["tiny_window_steps"]["value"] >= 1
    assert 0 < line["metrics"]["train_pad_frac"]["value"] < 100
    assert line["device"]["window_s"] > 0


def test_generator_added_by_file_feeds_its_cell(root):
    res = _run(root, "tiny.unpacked", 2 ** 31 + 12)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_targets_per_s", "setup_s"}
    assert res["metrics"]["train_targets_per_s"]["value"] > 0


def test_serve_mix_without_a_measured_knee_is_refused(root):
    from bench import harness
    gen = harness.load_module(root, "traffic", "open_loop")
    mix = harness.load_json(root, "bench", "traffic", "tiny-serve.json")
    warm, reqs = gen.requests(mix, 512, 2.0, 1)
    assert len(reqs) == 20                  # 0.8 x the knee of 12.5 req/s
    with pytest.raises(FileNotFoundError, match="knee"):
        gen.requests(dict(mix, knee="never-swept"), 512, 2.0, 1)


def test_knee_sweep_measures_the_rate_a_mix_runs_at(root):
    from bench import sweep_knee
    import jax
    rows, cell, gen = sweep_knee.sweep(root, "tiny-lora4", "tiny-serve",
                                       [2.0, 4.0], 1.0, 3, jax.devices())
    assert [r["rate"] for r in rows] == [2.0, 4.0]
    assert all(r["submitted"] == r["rate"] for r in rows)
    assert sweep_knee.knee(rows) in (2.0, 4.0)
    assert gen.knee_path(cell.mix).endswith("tiny-serve.knee.json")


def test_serve_cell_reports_its_end_to_end_metrics(root, capsys):
    res = _run(root, "tiny.serve", 7)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tts_p95_ms", "setup_s"}
    assert res["metrics"]["serve_tts_p95_ms"]["value"] > 0


def test_chipless_run_exits_nonzero_and_prints_no_result(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "bench", "run.py"),
                        "--workload", "train.qwen2-1.5b.k50", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no accelerator" in p.stderr


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(root, fault):
    res = _run(root, "tiny.train", 3, fault=fault)
    assert res["correct"] is False


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged"])
def test_serve_fault_is_not_correct(root, fault):
    res = _run(root, "tiny.serve", 4, fault=fault)
    assert res["correct"] is False


@pytest.mark.parametrize("workload", ["tiny.train", "tiny.serve"])
def test_float8_control_fails_the_limits(root, workload):
    """The reference in the program's place one precision step down
    (float8), and half of each batch left out, must read past the cell's
    limits on some number; the program's own readings must not."""
    from bench import control
    r = control.readings(root, workload, 5, seconds=1.0)
    assert r["control_fp8"]["correct"] is False, r["control_fp8"]
    if workload == "tiny.train":
        assert r["program"]["correct"] is True, r["program"]
        assert r["half_batch"]["correct"] is False, r["half_batch"]
