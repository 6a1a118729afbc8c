"""Host spans of the batch path, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation`` named ``depam.<name>``;
its keyword arguments become the event's stats in the trace.  With no
trace running a span costs about a microsecond, so spans are always on.
Every span of step-scoped work carries ``step``, the plan step it
belongs to, which joins one step's spans across the driver, loader and
writer threads; ``start``, the job's, carries how the compiled step
computes the percentiles.

  ``start``          bind, compile, open the sink, seed the carry (job;
                     pct_select: 1 when the step selects the
                     percentiles' order statistics, 0 when it sorts or
                     has no percentiles)
  ``fetch_wait``     driver waits for the step's payload (step, records)
  ``dispatch``       masks, window ids, host->device copies, both
                     programs, the async device->host copies
                     (step, h2d_bytes)
  ``drain``          one in-flight step into the sink (step)
  ``d2h_wait``       driver blocks on the step's results (step, d2h_bytes)
  ``compact``        host compaction of the event slabs (step, events)
  ``flush_windows``  finalize and write closed windows (step)
  ``sink_put``       one call of the engine into its sink (step)
  ``sink.<op>``      one sink call on the async writer thread (step)
  ``store.commit``   one store commit (step, bytes of the carry sidecar)
  ``store.fsync``    one ``os.fsync`` of a commit
  ``read``           one read task of the loader (records, bytes)

Span arguments are values the host already holds: no argument may wait
for the device.
"""
from __future__ import annotations

import jax

PREFIX = "depam."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The span ``depam.<name>``, with ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
