"""From a JAX profiler trace to the benchmark's device numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes and
keeps two kinds of events, with start and end in nanoseconds on the
profiler's clock:

- device operations: the events of each TPU plane's "XLA Ops" line, named
  by their HLO instruction (``%fusion.38``); an operation that calls
  others (a ``while`` loop) spans the events of its body;
- the harness's own spans: host events whose name starts with ``bench:``
  (``jax.profiler.TraceAnnotation``), so they share the device's clock.

``Reduction`` turns them into the busy union, the idle share, device time
inside each span, and the ``breakdown`` of the result line.
"""
from __future__ import annotations

import glob
import json
import os
from collections import defaultdict

import numpy as np

__all__ = ["extract", "load_events", "Reduction", "SPAN"]

SPAN = "bench:"           # prefix of the harness's TraceAnnotation names
OPS_LINE = "XLA Ops"


def extract(trace_dir: str) -> dict:
    """Device-op and harness-span events of the newest trace in a
    ``jax.profiler.trace`` directory."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    device, host = [], []
    for plane in pd.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name == OPS_LINE:
                device += [[plane.name, e.name.split(" = ")[0],
                            float(e.start_ns),
                            float(e.start_ns + e.duration_ns)]
                           for e in line.events]
            elif not on_device:
                host += [[e.name, float(e.start_ns),
                          float(e.start_ns + e.duration_ns)]
                         for e in line.events if e.name.startswith(SPAN)]
    return {"device": device, "host": host}


def load_events(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted (m, 2) intervals covering the rows of ``iv``."""
    if not len(iv):
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out)


def _overlap(merged: np.ndarray, a: float, b: float) -> float:
    if not len(merged) or b <= a:
        return 0.0
    lo = np.clip(merged[:, 0], a, b)
    hi = np.clip(merged[:, 1], a, b)
    return float((hi - lo).sum())


class Reduction:
    """Busy and idle time of the device inside the ``bench:window`` span,
    averaged over the device planes (chips) that ran operations."""

    def __init__(self, events: dict, window: str = SPAN + "window"):
        spans = [s for s in events["host"] if s[0] == window]
        if not spans:
            raise ValueError(f"trace has no {window!r} span")
        self.t0, self.t1 = spans[0][1], spans[0][2]
        self.spans = [s for s in events["host"] if s[0] != window]
        by_plane = defaultdict(list)
        for plane, name, a, b in events["device"]:
            a, b = max(a, self.t0), min(b, self.t1)
            if b > a:
                by_plane[plane].append((name, a, b))
        self.by_plane = by_plane
        self.merged = {p: _union(np.asarray([(a, b) for _, a, b in ops]))
                       for p, ops in by_plane.items()}
        self.n_planes = max(len(self.merged), 1)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(float((m[:, 1] - m[:, 0]).sum())
                   for m in self.merged.values()) * 1e-9 / self.n_planes

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def span_times(self, name: str) -> list:
        """(wall s, device-busy s) of every ``bench:<name>`` span."""
        out = []
        for s, a, b in self.spans:
            if s == SPAN + name:
                busy = sum(_overlap(m, a, b) for m in self.merged.values())
                out.append(((b - a) * 1e-9, busy * 1e-9 / self.n_planes))
        return out

    def top_ops(self, n: int = 10) -> list:
        """The operations with the most self time (their span less that of
        the operations nested in it), summed by name, in seconds."""
        tot = defaultdict(float)
        for plane_ops in self.by_plane.values():
            stack = []          # [name, end, self time] of open operations
            for name, a, b in sorted(plane_ops, key=lambda o: (o[1], -o[2])):
                while stack and stack[-1][1] <= a:
                    done = stack.pop()
                    tot[done[0]] += done[2]
                if stack:
                    stack[-1][2] -= min(b, stack[-1][1]) - a
                stack.append([name, b, b - a])
            for done in stack:
                tot[done[0]] += done[2]
        return sorted(([k, v * 1e-9] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest idle gaps of the device, each named by the innermost
        harness span that covers its middle (``other`` where none does)."""
        gaps = []
        for m in self.merged.values():
            edges = np.concatenate([[self.t0], m.ravel(), [self.t1]])
            for a, b in edges.reshape(-1, 2):
                if b > a:
                    gaps.append((a, b))
        out = []
        for a, b in gaps:
            mid = 0.5 * (a + b)
            cover = [(sb - sa, s) for s, sa, sb in self.spans
                     if sa <= mid <= sb]
            name = min(cover)[1][len(SPAN):] if cover else "other"
            out.append([name, (b - a) * 1e-9])
        return sorted(out, key=lambda kv: -kv[1])[:n]
