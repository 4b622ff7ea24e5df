"""Trainer and gang: seconds from the parent's ``fit()`` to the worker's
first line of the train loop (placement group, actor spawn, backend
set-up, shipping the loop), both stamps on the one host's clock."""


def read(trace, spans, run):
    return run["gang_up_s"]
