"""The bridge from the bus to the JAX profiler.

`install` makes every :func:`repro.obs.span` also a
``jax.profiler.TraceAnnotation``, so that a profiler capture (an
operator's, or a benchmark's traced run) holds the program's spans, with
their attrs as event metadata, on the device trace's clock.  With no
capture running an annotation records nothing.  This is the only module
of :mod:`repro.obs` that imports jax; `repro.obs.trace` stays stdlib-only.
"""
from __future__ import annotations

import jax

from repro.obs.trace import TRACER

__all__ = ["install"]


def install() -> None:
    """Annotate every span of the process-global tracer in the profiler's
    trace (idempotent)."""
    TRACER.annotator = jax.profiler.TraceAnnotation
