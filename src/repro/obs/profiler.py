"""Host spans on the profiler's clock, and the capture helper.

``span(name, **args)`` is the program's one span API: a
``jax.profiler.TraceAnnotation``.  With no capture running it costs
about a microsecond and records nothing; under a capture it writes a
host event onto the profiler's clock, the clock of the device trace, so
each idle gap of the device lines up with what the host was doing.
Arguments appear on the event (xprof/Perfetto show them as its
metadata); ``set_metadata(**args)`` on the returned span adds ones that
are known only when the span ends.

A span only marks where the host already is: none may wait for the
device or read a device array.  Names follow
``catapultdb.<layer>.<phase>``; docs/OBSERVABILITY.md lists them, with
the device scopes (``jax.named_scope``) inside the search programs.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax.profiler


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` carrying ``args`` (a context manager)."""
    return jax.profiler.TraceAnnotation(name, **args)


@contextmanager
def profile_trace(log_dir: str):
    """Capture everything inside the ``with`` block into a
    ``jax.profiler.trace`` at ``log_dir`` (viewable in xprof,
    TensorBoard or Perfetto): the device's operations with their
    scopes, and the program's spans."""
    with jax.profiler.trace(log_dir):
        yield
