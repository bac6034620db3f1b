"""repro.configs — one module per assigned architecture (+ the paper's own).

``get_arch(name)`` returns the ArchSpec; ``ASSIGNED`` lists the 10 graded
architectures (40 compile cells, ``tests/test_sharding.py``), ``ALL`` adds
the paper's dti-llama.
"""
from __future__ import annotations

from importlib import import_module
from typing import Dict, List

from repro.configs.base import ArchSpec, ShapeSpec

#: arch name -> its module under ``repro.configs``, imported on first lookup
#: so that loading one architecture does not load every model family
_MODULES: Dict[str, str] = {
    "minicpm-2b": "minicpm_2b",
    "qwen2-1.5b": "qwen2_1_5b",
    "minicpm3-4b": "minicpm3_4b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "gin-tu": "gin_tu",
    "mind": "mind",
    "xdeepfm": "xdeepfm",
    "din": "din",
    "sasrec": "sasrec",
    "dti-llama": "dti_llama",
}

ASSIGNED: List[str] = [n for n in _MODULES if n != "dti-llama"]
ALL: List[str] = list(_MODULES)


def get_arch(name: str) -> ArchSpec:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    return import_module(f"repro.configs.{_MODULES[name]}").spec()


def all_cells(archs=None) -> List[tuple]:
    """Every (arch, shape) pair — the 40 graded cells by default."""
    out = []
    for a in (archs or ASSIGNED):
        spec = get_arch(a)
        for s in spec.shapes:
            out.append((a, s))
    return out


__all__ = ["ArchSpec", "ShapeSpec", "get_arch", "all_cells", "ASSIGNED",
           "ALL"]
