# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Shared kernel-package helpers.

Every kernel wrapper defaults ``interpret`` the same way: compile to Mosaic
when the default backend is a TPU, and run the Pallas interpreter on any
other backend, so the kernel *body* (not a jnp re-implementation) is what
runs — and is tested — on CPU. A backend that fails to start raises here;
it is never read as "not a TPU".
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve a tri-state ``interpret`` argument (None = auto)."""
    return (not on_tpu()) if interpret is None else bool(interpret)


__all__ = ["on_tpu", "default_interpret"]
