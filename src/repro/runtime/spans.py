"""Named host spans in the profiler's trace.

``span(name, **meta)`` is a `jax.profiler.TraceAnnotation` named
``repro.<name>``.  It records only while a profiler session is active
(``jax.profiler.trace`` or ``start_trace``), so the profiler is the only
switch: there is no flag, and outside a session a span costs one small
object.  Its events land on the host plane of the session's ``.xplane.pb``,
on the same clock as the device planes; ``meta`` (``batch=``, ``step=``)
ties the spans of one batch or step together.
"""

from __future__ import annotations

import jax.profiler


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation("repro." + name, **meta)
