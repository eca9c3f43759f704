#!/usr/bin/env python3
"""Inside a traced run of a cell: the program's own spans and device scopes.

``bench/trace.py`` keeps the harness's ``bench:`` spans and the device's
operations.  The program puts spans of its own on the same clock
(``repro.obs.trace.span``: ``repro:engine.apply``, ``repro:engine.upload``
with the ``bytes`` it stages, ...) and names the segment product's stages
on the device (``jax.named_scope``: ``bucket``, ``gather``).  This module
keeps those too and reads them:

    python3 bench/program_trace.py --workload <name> --seed <n> --seconds <s> [--events out.json]

makes one run of the cell as ``bench/run.py --trace 1`` does (the
harness's own code, with this module's ``extract`` and ``ProgramReduction``
in place of ``bench/trace.py``'s) and prints its result line with two more
keys: ``program``, the numbers of ``ProgramReduction.numbers``, and
``rows_per_s_traced``.  ``--events`` writes the events it kept (a fixture
for the tests).  The benchmark's runs do not run this.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from collections import defaultdict
from pathlib import Path
from typing import Optional

import numpy as np

if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parents[1])   # the checkout

from bench import harness, trace  # noqa: E402

__all__ = ["extract", "ProgramReduction", "PROGRAM", "SCOPE_STAT", "ROUTE"]

PROGRAM = "repro:"        # prefix of the program's spans (repro.obs.trace)
SCOPE_STAT = "tf_op"      # stat of an op's event metadata: its op_name
ROUTE = ("engine.batch_key", "engine.apply", "engine.weights",
         "engine.leaf_map")
_extract = trace.extract  # kept: ``traced_run`` swaps ``trace.extract``


def _stats(e) -> dict:
    """An event's own stats, without the profiler's internal ``_`` ones."""
    return {k: v for k, v in e.stats if not k.startswith("_")}


def _varint(buf, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of each field of one serialized protobuf
    message: an int for a varint, a memoryview for the others."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def op_scopes(path: str) -> dict:
    """{device plane: {event name: op_name}} of an ``.xplane.pb``.

    The ``op_name`` of an operation is the ``SCOPE_STAT`` stat of its event
    *metadata*, which ``jax.profiler.ProfileData`` does not expose, so the
    few fields needed are read from the serialized ``XSpace``
    (``tsl/profiler/protobuf/xplane.proto``): planes (1) with their name
    (2), event metadata (4: id 1 → name 2, stats 5) and stat metadata (5:
    id 1 → name 2); a stat (metadata_id 1) holds a string in ``str_value``
    (5) or as a ``ref_value`` (7) to a stat metadata's name.  An event name
    that two operations with different ``op_name`` share maps to "".
    """
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        if not name.startswith("/device:"):
            continue

        def entry(v):                    # a map<int64, message> entry
            d = dict(_fields(v))
            return d.get(1, 0), d.get(2, b"")

        stat_names = {}
        for k, v in fields:
            if k == 5:
                i, meta = entry(v)
                stat_names[i] = bytes(dict(_fields(meta)).get(2, b"")).decode()
        scopes = {}
        for k, v in fields:
            if k != 4:
                continue
            ev_name, scope = "", ""
            for mk, mv in _fields(entry(v)[1]):
                if mk == 2:
                    ev_name = bytes(mv).decode()
                elif mk == 5:
                    st = dict(_fields(mv))
                    if stat_names.get(st.get(1)) == SCOPE_STAT:
                        scope = bytes(st[5]).decode() if 5 in st else \
                            stat_names.get(st.get(7), "")
            if scopes.get(ev_name, scope) != scope:
                scope = ""
            scopes[ev_name] = scope
        out[name] = scopes
    return out


def extract(trace_dir: str) -> dict:
    """``trace.extract``'s events of the newest trace in ``trace_dir``, and
    two more keys:

    - ``program``: the ``repro:`` host spans, ``[name, start, end, stats]``;
    - ``device_scope``: beside each ``device`` event, the ``op_name`` of
      its HLO instruction, the ``jax.named_scope`` path
      (``jit(_swlc_product)/bucket/while/body/...``; "" where it has none).
    """
    import jax
    events = _extract(trace_dir)
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    scopes = op_scopes(path)
    program, scope = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name == trace.OPS_LINE:
                names = scopes.get(plane.name, {})
                scope += [names.get(e.name, "") for e in line.events]
            elif not on_device:
                program += [[e.name, float(e.start_ns),
                             float(e.start_ns + e.duration_ns), _stats(e)]
                            for e in line.events
                            if e.name.startswith(PROGRAM)]
    return dict(events, program=program, device_scope=scope)


class ProgramReduction(trace.Reduction):
    """``trace.Reduction`` that also reads the program's spans and the
    device's named scopes.  Without them (``trace.extract``'s events, an
    older program) it reduces exactly as ``trace.Reduction``."""

    def __init__(self, events: dict, window: str = trace.SPAN + "window"):
        super().__init__(events, window)
        self.program = [p for p in events.get("program", [])
                        if self.t0 <= p[1] <= self.t1]
        scopes = events.get("device_scope") or [""] * len(events["device"])
        self.scoped = defaultdict(list)   # plane -> (scope path, a, b)
        for (plane, _, a, b), sc in zip(events["device"], scopes):
            a, b = max(a, self.t0), min(b, self.t1)
            if b > a:
                self.scoped[plane].append((sc.split("/"), a, b))

    def _harness(self, name: str) -> list:
        return [(a, b) for s, a, b in self.spans if s == trace.SPAN + name]

    def program_times(self, name: str) -> list:
        """(wall s, stats) of every ``repro:<name>`` span in the window."""
        return [((b - a) * 1e-9, st) for s, a, b, st in self.program
                if s == PROGRAM + name]

    def program_per_span(self, name: Optional[str], span: str) -> list:
        """For each ``bench:<span>`` span, the (wall s, stats) of the
        ``repro:<name>`` spans (every program span for None) that start
        inside it."""
        return [[((b - a) * 1e-9, st) for s, a, b, st in self.program
                 if name in (None, s[len(PROGRAM):]) and sa <= a <= sb]
                for sa, sb in self._harness(span)]

    def has_scope(self, scope: str) -> bool:
        """Whether any operation in the window ran under ``scope``."""
        return any(scope in p for ops in self.scoped.values()
                   for p, _, _ in ops)

    def scope_busy_s(self, scope: str, a: Optional[float] = None,
                     b: Optional[float] = None) -> float:
        """Seconds in [a, b] (the window by default) in which an operation
        under the named scope ``scope`` ran, averaged over the chips."""
        a = self.t0 if a is None else a
        b = self.t1 if b is None else b
        busy = 0.0
        for ops in self.scoped.values():
            iv = np.asarray([(x, y) for p, x, y in ops if scope in p])
            busy += trace._overlap(trace._union(iv), a, b)
        return busy * 1e-9 / self.n_planes

    def scope_times(self, scope: str, span: str) -> list:
        """Device-busy s under ``scope`` inside each ``bench:<span>``."""
        return [self.scope_busy_s(scope, a, b)
                for a, b in self._harness(span)]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest idle pieces of the device: each idle gap cut at the
        start and end of every program span inside it, each piece named by
        the innermost span, program (``engine.upload``) or harness
        (``predict``), that covers its middle (``other`` where none does)."""
        gaps = []
        for m in self.merged.values():
            edges = np.concatenate([[self.t0], m.ravel(), [self.t1]])
            for a, b in edges.reshape(-1, 2):
                if b > a:
                    gaps.append((a, b))
        cuts = np.unique([t for _, a, b, _ in self.program for t in (a, b)])
        pieces = []
        for a, b in gaps:
            inner = cuts[np.searchsorted(cuts, a, "right"):
                         np.searchsorted(cuts, b, "left")]
            edges = np.concatenate([[a], inner, [b]])
            pieces += zip(edges[:-1], edges[1:])
        named = [(s[len(trace.SPAN):], sa, sb) for s, sa, sb in self.spans]
        named += [(s[len(PROGRAM):], sa, sb)
                  for s, sa, sb, _ in self.program]
        # innermost: the shortest covering span, ties to the first name
        named.sort(key=lambda s: (s[2] - s[1], s[0]))
        mid = np.asarray([0.5 * (a + b) for a, b in pieces])
        which = np.full(len(pieces), -1)
        for i, (_, sa, sb) in enumerate(named):
            which[(which < 0) & (sa <= mid) & (mid <= sb)] = i
        out = [[named[i][0] if i >= 0 else "other", (b - a) * 1e-9]
               for i, (a, b) in zip(which, pieces)]
        return sorted(out, key=lambda kv: -kv[1])[:n]

    def numbers(self, route: str = "route", op: str = "predict") -> dict:
        """What the program's spans and scopes say about the harness's
        ``route`` and ``op`` steps; a number is None where the trace has
        nothing to read for it."""
        def mean(xs):
            return sum(xs) / len(xs) if xs else None

        def per_call(name, value):
            per = self.program_per_span(name, op)
            return mean([sum(value(w, st) for w, st in p) for p in per]) \
                if any(per) else None

        def scope_s(scope):
            return mean(self.scope_times(scope, op)) \
                if self.has_scope(scope) else None

        def scaled(x, k=1e3):            # seconds to ms by default
            return None if x is None else k * x

        def wall_s(name):
            return mean([w for w, _ in self.program_times(name)])

        by_name = defaultdict(float)
        for name, s in self.idle_gaps(None):
            by_name[name] += s
        idle = sum(by_name.values())
        in_program = sum(by_name[s[len(PROGRAM):]]
                         for s in {p[0] for p in self.program})
        return {
            "apply_ms.batch": scaled(wall_s("engine.apply")),
            "leaf_map_ms.batch": scaled(wall_s("engine.leaf_map")),
            f"upload_ms.{op}": scaled(per_call("engine.upload",
                                               lambda w, st: w)),
            # what the engine stages (engine.upload) or hands to a kernel
            # that stages its own inputs (engine.dispatch of block_prox)
            f"h2d_mb.{op}": scaled(per_call(
                None, lambda w, st: st.get("bytes", 0)), 1e-6),
            "bucket_device_ms": scaled(scope_s("bucket")),
            "gather_device_ms": scaled(scope_s("gather")),
            f"{route}_split_ms": {
                name: scaled(mean([sum(w for w, _ in p) for p in
                                   self.program_per_span(name, route)]))
                for name in ROUTE},
            "idle_s_by_span": dict(sorted(by_name.items(),
                                          key=lambda kv: -kv[1])),
            "idle_share_in_program_spans":
                in_program / idle if idle > 0 and self.program else None,
        }


def traced_run(workload: str, seed: int, seconds: float,
               **kw) -> tuple:
    """(result line, kept events, reduction) of one traced run of
    ``workload``: ``harness.run`` with this module's ``extract`` and
    ``ProgramReduction`` in place of ``bench/trace.py``'s.  ``kw`` goes to
    ``harness.run`` (tests stand in for the chip with ``devices``)."""
    kept = {}

    def extract_(trace_dir):
        kept["events"] = extract(trace_dir)
        return kept["events"]

    def reduce_(events):
        kept["red"] = ProgramReduction(events)
        return kept["red"]

    saved = trace.extract, trace.Reduction
    trace.extract, trace.Reduction = extract_, reduce_
    try:
        res = harness.run(workload, seed, seconds, True, 0.0, **kw)
    finally:
        trace.extract, trace.Reduction = saved
    return res, kept["events"], kept["red"]


def main(argv: Optional[list] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--events", help="write the kept events to this file")
    args = ap.parse_args(argv)
    harness.configure()
    _, _, traffic = harness.load_cell(args.workload, harness.load_spec())
    try:
        res, events, red = traced_run(args.workload, args.seed,
                                      args.seconds)
    except harness.NoDevice as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    if args.events:
        with open(args.events, "w") as fh:
            json.dump(events, fh)
    res["program"] = red.numbers()
    # the closed loop's rows_per_s, over the traced window
    answered = res["attempted"] - res["failed"]
    res["rows_per_s_traced"] = answered * traffic["batch_rows"] / \
        red.window_s
    harness.report(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
