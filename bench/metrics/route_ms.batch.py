"""Mean host time of ``engine.query_state(batch)`` per call, in ms: host
routing, query weights, the batch's content key and its CSR build (the
engine's host layer), timed by the harness around the call."""


def read(run):
    t = [c.t_route - c.t0 for c in run.calls if c.ok]
    return 1e3 * sum(t) / len(t) if t else None
