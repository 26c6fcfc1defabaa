"""Named host spans of the serving engine.

Each span is a ``jax.profiler.TraceAnnotation``: it costs one enter and
one exit when no profiler runs, and inside ``jax.profiler.trace(...)`` it
lands on the host line of the trace, on the same clock as the device
operations, so a device gap can be put down to the engine's own work.
The engine's names (``Engine.step`` on the fast path):

  engine.admit    admission: budget law, grouping, host packing, groups
  engine.prefill  one prefill group: dispatch through the first token
  engine.pages    page grants, page-table push, live page-table slice
  engine.decode   the decode-quantum dispatch (enqueue only)
  engine.fetch    the quantum's one blocking device-to-host fetch
  engine.retire   token distribution and slot release after the fetch
"""
from __future__ import annotations

import jax


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` in any active profiler trace."""
    return jax.profiler.TraceAnnotation(name)
