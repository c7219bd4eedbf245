"""Observability: the structured event tracer.

The instrumented stack (search, measure, dispatch, serving) imports from
this package only — ``from ..obs import emit, span`` — so the whole
layer can be reasoned about (and disabled) in one place.  Tracing is off
unless ``REPRO_TRACE`` is set or a tracer is installed (see
:mod:`repro.obs.trace`); while it is on, every span is also a
``repro.<ev>`` annotation in a running JAX profiler trace.
"""

from .metrics import spearman  # noqa: F401
from .trace import (  # noqa: F401
    ConsoleSink,
    JsonlSink,
    NullSink,
    RingBufferSink,
    Sink,
    Tracer,
    configure_tracing,
    disable_tracing,
    emit,
    init_from_env,
    span,
    trace_enabled,
    tracer,
)

__all__ = [
    "spearman",
    "ConsoleSink",
    "JsonlSink",
    "NullSink",
    "RingBufferSink",
    "Sink",
    "Tracer",
    "configure_tracing",
    "disable_tracing",
    "emit",
    "init_from_env",
    "span",
    "trace_enabled",
    "tracer",
]
