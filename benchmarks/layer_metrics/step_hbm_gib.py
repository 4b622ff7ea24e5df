"""Train step, memory: what the compiled step holds on one device by
the compiler's own ``memory_analysis()``: arguments + temporaries +
outputs - aliased.  Not the allocator's ``peak_bytes_in_use``, which
leaves the program's temporaries out."""


def read(trace, spans, run):
    m = run["final"]["hbm"]
    total = m["argument"] + m["temp"] + m["output"] - m["alias"]
    return total / 2 ** 30
