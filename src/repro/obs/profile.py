"""``jax.profiler`` hooks.

Two entry points:

- :func:`trace` — context manager around a whole run, writing a device
  profile to a directory (``serve_bench --jax-profile DIR``). A trace
  that was asked for and cannot start raises: a run that was meant to be
  profiled never silently comes back without its profile.
- :func:`annotate` — a ``TraceAnnotation`` so host-side span names show
  up on the device timeline; the scheduler opens one around each
  dispatch when its tracer was built with ``jax_annotate=True``.
"""
from __future__ import annotations

from contextlib import contextmanager

from jax import profiler as _jax_profiler


@contextmanager
def trace(log_dir):
    """``jax.profiler.trace`` into ``log_dir``; a no-op when none is given."""
    if not log_dir:
        yield
        return
    with _jax_profiler.trace(str(log_dir)):
        yield


def annotate(name: str):
    """``jax.profiler.TraceAnnotation(name)``."""
    return _jax_profiler.TraceAnnotation(name)
