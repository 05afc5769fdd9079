"""Eval flight recorder (ISSUE 9): always-on, bounded-ring per-eval
span tracing with tail exemplars and Chrome/Perfetto export. See
tracer.py for the design; `tracer` is the process-wide recorder the
server configures and the kernels/gateways report into."""

from .tracer import (AMBIENT_STAGES, STAGE_PARENTS, EvalTrace, Tracer,
                     begin, current, current_all, emit, finish, report,
                     span, to_chrome, tracer, uncovered_s, use, use_many)

__all__ = [
    "AMBIENT_STAGES", "STAGE_PARENTS", "EvalTrace", "Tracer", "begin",
    "current", "current_all", "emit", "finish", "report", "span",
    "to_chrome", "tracer", "uncovered_s", "use", "use_many",
]
