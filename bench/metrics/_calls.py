"""Shared by the readers of one operation's spans in the device trace."""
from bench import work


def device_ms(run, op):
    """Mean device-busy ms inside the harness's ``op`` spans."""
    if run.trace is None:
        return None
    t = run.trace.span_times(op)
    return 1e3 * sum(d for _, d in t) / len(t) if t else None


def roofline(run, op):
    """Least chip time of the ``op`` calls' work over the device-busy time
    inside their spans, in %; silent without a trace or device time."""
    if run.trace is None:
        return None
    calls = [c for c in run.calls if c.ok and c.op == op
             and c.bytes is not None]
    busy = sum(d for _, d in run.trace.span_times(op))
    if not calls or busy <= 0.0:
        return None
    least = sum(work.least_seconds(run.device_kind, c.bytes) for c in calls)
    return 100.0 * least / busy
