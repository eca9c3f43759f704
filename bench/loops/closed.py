"""Closed loop: one batch in flight.  Each step routes a batch
(``engine.query_state``) and runs the op on it, then takes the next.

Traffic keys: ``batch_rows``, ``pool_batches`` (held-out batches drawn
from the seed, sent in order; a window longer than the pool starts it
again, which no cache of the engine (8 routed batches) can remember) and
``warmup_batches`` (further batches, sent in set-up only).

End-to-end: ``rows_per_s``, the rows answered in the window over its
seconds; the window ends with the call that crosses ``seconds``.
"""
import sys
import time
import traceback

from bench import data
from bench.harness import Call, Window, annotate


def prepare(op, engine, cfg: dict, traffic: dict, seed: int):
    """The window's batches; every shape they use warmed up on others."""
    nb, rows = traffic["pool_batches"], traffic["batch_rows"]
    warm = traffic["warmup_batches"]
    pool = data.query_rows(cfg, seed, (nb + warm) * rows).reshape(
        nb + warm, rows, -1)
    for X in pool[nb:]:
        engine.query_state(X)
        op(X)
    return pool[:nb]


def run(op, engine, pool, seconds: float, traced: bool) -> Window:
    span = annotate(traced)
    calls, answers = [], []
    start = time.perf_counter()
    b = 0
    while time.perf_counter() - start < seconds:
        X = pool[b % len(pool)]
        t0 = time.perf_counter()
        try:
            with span("route"):
                engine.query_state(X)
            t_route = time.perf_counter()
            with span(op.name):
                answers.append(op(X))
            ok = True
        except Exception:               # noqa: BLE001 — counted, reported
            print(f"call {b} failed:", file=sys.stderr)
            traceback.print_exc()
            t_route, ok = time.perf_counter(), False
        calls.append(Call(op.name, X, t0, t_route, time.perf_counter(),
                          ok))
        b += 1
    end = time.perf_counter()
    return Window(calls, answers, end - start)


def end_to_end(w: Window) -> dict:
    rows = sum(len(c.X) for c in w.calls if c.ok)
    return {"rows_per_s": rows / w.seconds}
