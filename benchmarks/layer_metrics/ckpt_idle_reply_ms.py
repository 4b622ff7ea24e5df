"""Device, in a cell that saves: milliseconds per save of the traced
cycle in which device 0 ran nothing WHILE the gang worker was inside
``worker:reply`` (serialising or storing the reply that carries the
checkpoint).  The test of the guess about ``ckpt_redispatch_ms``: if the
reply holds the loop's next dispatch back, the idle time sits here."""

from benchmarks.reduce import program_spans as ps


def read(trace, spans, run):
    if not trace:
        return None
    replies = ps.reply_intervals(trace, run, ps.timeline())
    if not replies:
        return None
    saves = max(1, (run["final"].get("trace") or {}).get("saves") or 1)
    return ps.idle_under(trace["devices"][0]["idle_gaps"],
                         replies) / 1e6 / saves
