"""Runtime observability (DESIGN.md §10): step scopes, loop spans and the
compile counter, the phased step's fenced spans, metrics stream, heartbeat
stall detection, and the measured-vs-predicted calibration loop.

Import surface is deliberately thin — ``spans``/``metrics``/``heartbeat``
are stdlib(+lazy jax) only, safe to import from any layer including
``core.schedule``. The heavyweight pieces (``obs.phased`` builds jitted
segments; ``obs.calibrate`` is a CLI) are imported as submodules by their
consumers, never here, to keep import cycles impossible.
"""
from . import heartbeat, metrics, spans
from .spans import (SpanRecorder, TraceConfig, compile_counter, scope, span,
                    step_span)

__all__ = [
    "spans", "metrics", "heartbeat",
    "SpanRecorder", "TraceConfig", "compile_counter", "scope", "span",
    "step_span",
]
