"""chip_smoke.py's phases on the CPU at the qwen2-1.5b SMOKE size.

The phase functions run in interpret mode here (the TPU assertion lives in
``main`` only), so the checks they make on the chip — pallas vs blocked
train loss and LoRA grad-norm, scheduler vs oracle scores — are exercised
on every test run. ``main`` itself must refuse to run without a TPU.
"""
import importlib.util
import pathlib

import pytest

from repro.configs.qwen2_1_5b import SMOKE

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg(chip_smoke):
    return chip_smoke.lora_config(SMOKE)


SMALL = dict(users=12, items=60, seq=30, k=4, n_ctx=4, batch=4)


def test_train_and_serve_phases_on_cpu(chip_smoke, cfg):
    logs = []
    res = chip_smoke.train_phase(cfg, steps=3, corpus=SMALL, log=logs.append)
    assert res["layers"] == SMOKE.n_layers
    assert res["mosaic_calls"] == 0                    # interpret mode
    assert res["loss_rel_delta"] <= res["tol"]
    assert res["grad_norm_rel_delta"] <= res["tol_grad_norm"]
    assert res["lora_grad_norm"] > 0
    assert min(res["ablation_loss_rel_delta"].values()) > res["tol"]
    assert any(line.startswith("[train]") for line in logs)

    out = chip_smoke.serve_phase(cfg, res["params"], n_requests=4, k=3,
                                 n_ctx=4, log=logs.append)
    assert out["requests"] == 4
    assert out["max_abs_delta"] <= out["tol"]


def test_main_refuses_without_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
