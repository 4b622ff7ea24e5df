"""Train step: device milliseconds a step under the part ``hc.coef``: a
hyper-connection's coefficients (``models/hyper.py``: the norm over a
token's ``n C`` elements, the projection, the sigmoids, the Sinkhorn
steps), all float32.  All phases together, each op's self time on device
0; the part is the OUTERMOST component of the op's name that is on the
program's list (``scopes.part``, the list from the run's
``model:step.scopes`` span).  ``None`` without that span, without names
in the profiler's file, or where the program's list has no such part."""

from benchmarks.reduce import program_spans, scopes


def read(trace, spans, run):
    parts = scopes.step_parts(program_spans.timeline())
    if not parts or "hc.coef" not in parts:
        return None
    return scopes.part_ms(trace, run, "hc.coef")
