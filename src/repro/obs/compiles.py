"""The compile watch: one process-wide ``jax.monitoring`` listener on
``/jax/core/compile/backend_compile_duration``, which JAX records for
every XLA compile and every load from the persistent compile cache.

An owner (``Trainer``, ``ServeScheduler``: anything with ``tracer`` and
``metrics`` attributes) calls :func:`watch` once. From then on, while it
lives, each compile

- adds a ``jit.compile`` span (ending at the event, ``fun`` = the jitted
  function's name) to the owner's tracer when that tracer is enabled,
  once per tracer however many owners share it;
- counts ``jit.compiles`` and adds its seconds to ``jit.compile_s`` in
  the owner's registry.

So a recompile inside a traced window is a named span, and an idle gap
on the device is put down to it. Owners are held by weak reference; a
``NULL_TRACER`` owner pays two counter increments per compile.
"""
from __future__ import annotations

import threading
import weakref
from typing import List

EVENT = "/jax/core/compile/backend_compile_duration"

_owners: List[weakref.ref] = []
_lock = threading.Lock()
_installed = False


def watch(owner) -> None:
    """Report every later compile in this process to ``owner``."""
    global _installed
    with _lock:
        if not _installed:
            import jax
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _installed = True
        _owners.append(weakref.ref(owner))


def _on_event(event: str, duration_s: float, **kw) -> None:
    if event != EVENT:
        return
    with _lock:
        _owners[:] = [r for r in _owners if r() is not None]
        live = [o for o in (r() for r in _owners) if o is not None]
    fun = kw.get("fun_name")
    traced = set()
    for o in live:
        o.metrics.counter("jit.compiles").inc()
        o.metrics.counter("jit.compile_s").inc(duration_s)
        tr = o.tracer
        if tr is not None and tr.enabled and id(tr) not in traced:
            traced.add(id(tr))
            tr.complete("jit.compile", duration_s,
                        **({"fun": fun} if fun else {}))


__all__ = ["EVENT", "watch"]
