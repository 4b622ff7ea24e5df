"""Kernels: device milliseconds per step in the Mosaic (Pallas) custom
calls — the flash-attention kernels, forward, recomputed forward,
dK/dV and dQ — summed on device 0 of the trace."""


def read(trace, spans, run):
    if not trace:
        return None
    dev = trace["devices"][0]
    if not dev["steps"] or not dev["kernel_calls"]:
        return None
    return dev["kernel_ns"] / dev["steps"] / 1e6
