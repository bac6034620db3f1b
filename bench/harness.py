"""What every cell's run shares: finding the cell and its files by name,
the chip check, the compile cache, the compile counter and the result
line.

A cell's code is found by name too. Its mix's ``kind`` names the driver,
``bench/drivers/<kind>.py`` (``run(cell, devices, t_start, counter)``), and
its ``generator`` names the file under ``bench/traffic/`` that makes its
inputs from the seed; each per-layer metric is read by
``bench/metrics/<metric>.py``. A later PR adds any of them as a new file.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional



def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(root: str, *rel: str) -> dict:
    with open(os.path.join(root, *rel)) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """One run of one cell, with everything found by name."""
    root: str
    spec: dict
    workload: dict
    config: dict
    mix: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    fault: Optional[str] = None      # a planted fault (tests of `correct`)

    @property
    def name(self) -> str:
        return self.workload["name"]

    def end_to_end(self) -> List[dict]:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"] if m["moves"] in mine
                and self.name in m.get("workloads", [self.name])]


def load_cell(root: str, name: str, *, seed: int, seconds: float,
              trace: bool, fault: Optional[str] = None) -> Cell:
    spec = load_json(root, "BENCHMARK.json")
    w = find_cell(spec, name)
    config = load_json(root, "bench", "configs", w["config"] + ".json")
    mix = load_json(root, "bench", "traffic", w["traffic"] + ".json")
    limits = load_json(root, "bench", "limits", name + ".json")
    out = os.path.join(root, "bench", ".out", f"{name}.{seed}")
    os.makedirs(out, exist_ok=True)
    return Cell(root, spec, w, config, mix, limits, int(seed), float(seconds),
                bool(trace), out, fault)


_MODULES: Dict[str, Any] = {}


def load_module(root: str, sub: str, name: str):
    """``bench/<sub>/<name>.py`` of the checkout at ``root``, loaded once."""
    import importlib.util
    path = os.path.join(root, "bench", sub, name + ".py")
    if path not in _MODULES:
        if not os.path.exists(path):
            raise FileNotFoundError(f"no {sub} named {name!r}: {path}")
        mod_name = "bench_{}_{}_{}".format(
            sub, "".join(c if c.isalnum() else "_" for c in name),
            len(_MODULES))
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod         # dataclasses look it up
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def driver(cell: Cell):
    """The driver of the cell's kind of traffic."""
    return load_module(cell.root, "drivers", cell.mix["kind"])


def generator(cell: Cell):
    """The generator that the cell's traffic mix names."""
    return load_module(cell.root, "traffic", cell.mix["generator"])


def model_config(cell: Cell):
    """The program's ``ModelConfig`` for the cell's configuration."""
    from repro.models.transformer import ModelConfig
    return ModelConfig(**cell.config["model"])


def ref_config(cell: Cell) -> dict:
    """The sizes the reference reads (the configuration as stated)."""
    m = dict(cell.config["model"])
    m.setdefault("head_dim", m["d_model"] // m["n_heads"])
    return m


def chip_or_exit(chips: int) -> list:
    """The devices, or exit nonzero with no result when JAX finds no
    accelerator or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        log(f"bench: JAX found no accelerator (platform "
            f"{devs[0].platform!r}); no result")
        sys.exit(3)
    if len(devs) < chips:
        log(f"bench: the cell asks for {chips} chips, JAX sees {len(devs)}")
        sys.exit(3)
    return devs


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout, so
    that two checkouts never share compiled programs (a
    ``JAX_COMPILATION_CACHE_DIR`` in the environment is overridden)."""
    import jax
    path = os.path.join(root, "bench", ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compiles (cache misses) while ``armed``."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0

        def on(event: str, *a, **k) -> None:
            if self.armed and event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(on)


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def log_memory(device, label: str) -> None:
    """The runtime's memory counters, on an earlier line."""
    stats = device.memory_stats() or {}
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved", "largest_alloc_size", "bytes_limit")
    log(f"[memory] {label}: " + ", ".join(
        f"{k} {stats[k]}" for k in keys if k in stats))


def device_info(devices, chips: int, peak: Optional[int]) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "memory_peak_bytes": peak}


def compared(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each number that the cell's limits name, beside its limit; ``ok``
    when none exceeds it."""
    rows = {k: {"value": float(numbers[k]), "limit": float(v)}
            for k, v in limits.items()}
    ok = all(r["value"] <= r["limit"] for r in rows.values())
    return {"ok": ok, "rows": rows}


def emit(result: dict, checks: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout (``checks`` under a key of its own, last)."""
    for k, r in checks["rows"].items():
        log(f"check {k} {r['value']!r} limit {r['limit']!r}")
    result = dict(result)
    result["checks"] = {k: [r["value"], r["limit"]]
                        for k, r in checks["rows"].items()}
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def run_readers(cell: Cell, ctx: dict, log_fn: Callable = log) -> dict:
    """Each per-layer metric of the cell, read by ``metrics/<name>.py``;
    a reader that finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer():
        v = load_module(cell.root, "metrics", m["name"]).read(ctx)
        if v is None:
            log_fn(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = metric(v, m["unit"])
    return out
