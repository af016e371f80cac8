"""Named host spans on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: inside any
profiler capture (``jax.profiler.trace``, TensorBoard, Perfetto) it records
``name`` on the host's timeline, beside the device's operations, with
``meta`` as the event's stats. It never starts or stops the profiler;
whoever holds the capture does. With the profiler off a span is one
annotation object that records nothing.

The campaign path opens its spans under the ``campaign.`` prefix
(docs/architecture.md, "Spans").
"""
from __future__ import annotations

import jax


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``name`` while a capture is on."""
    return jax.profiler.TraceAnnotation(name, **meta)
