"""``jax.profiler`` hook: :func:`trace`, a context manager around a whole
run that writes a device profile to a directory (``serve_bench
--jax-profile DIR``). A trace that was asked for and cannot start raises:
a run that was meant to be profiled never silently comes back without
its profile. Host spans reach the profile through
``SpanTracer(jax_annotate=True)``, which annotates every span.
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

