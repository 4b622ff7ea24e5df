"""Compile: seconds of ``step.lower(...).compile()`` in the worker —
Python tracing and lowering of every layer, then the backend compile or
the persistent cache's answer."""


def read(trace, spans, run):
    return run["final"]["compile_s"]
