"""Host spans of the socket datapath, on the profiler's clock.

:func:`span` opens a ``jax.profiler.TraceAnnotation``: it records only
while a profiler session is open (``jax.profiler.start_trace``), and then
lands on the trace's host plane, on the clock the device plane shares, so
a span lines up with the kernels it launched. With no session open an
enter and exit costs about a microsecond. The datapath opens spans per
scheduling round, never per message; docs/API.md lists their names.

Call sites go through the module (``trace.span(name)``), so a test can
record the spans by patching this one function.
"""
from __future__ import annotations

import functools


@functools.cache
def _annotation():
    # JAX is imported on the first span, not when repro.core is imported
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


def span(name: str):
    """A context manager that records ``name`` as one host span."""
    return _annotation()(name)
