"""Host spans and the profiler window of a traced run.

Spans are ``jax.profiler.TraceAnnotation`` events: they land in the
profiler's own trace, on the host's clock, beside the device's operations,
so the reduction (``chipbench.trace``) can say what the host was doing in
each idle gap of the device. An untraced run opens no span at all.
"""
from __future__ import annotations

import contextlib
import time
from pathlib import Path
from typing import Optional

# every span the benchmark opens starts with one of these, so the reduction
# can tell them from the profiler's own host events
SPAN_PREFIXES = ("bench.", "campaign.")
WINDOW_SPAN = "bench.window"


class Tracer:
    """Starts and stops the profiler once per run, and opens spans while it
    records. ``enabled=False`` makes every call a no-op."""

    def __init__(self, enabled: bool, out_dir: Optional[Path] = None):
        self.enabled = enabled
        self.out_dir = out_dir
        self.recording = False
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None
        self._window = None

    def span(self, name: str):
        """A context manager that records ``name`` while the trace is on."""
        if not self.recording:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open(self, name: str):
        """Open a span that a later ``close`` ends (for spans that do not
        nest in one block of code); None while the trace is off."""
        if not self.recording:
            return None
        import jax

        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        return ann

    @staticmethod
    def close(ann) -> None:
        if ann is not None:
            ann.__exit__(None, None, None)

    def start(self) -> None:
        """Start the profiler and open the window span."""
        if not self.enabled or self.recording:
            return
        import jax

        self.out_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # spans only: no per-call Python events
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self.recording = True
        self.started_at = time.perf_counter()
        self._window = self.open(WINDOW_SPAN)

    def stop(self) -> None:
        """Close the window span and write the trace."""
        if not self.recording:
            return
        import jax

        self.close(self._window)
        self._window = None
        self.stopped_at = time.perf_counter()
        self.recording = False
        jax.profiler.stop_trace()

    @property
    def seconds(self) -> Optional[float]:
        if self.started_at is None or self.stopped_at is None:
            return None
        return self.stopped_at - self.started_at
