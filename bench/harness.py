"""One run of one benchmark cell: set-up, measured window, check, result.

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json`` names its configuration (``bench/configs/<name>.json``)
and its traffic mix (``bench/traffic/<traffic>.json``); the configuration
names its data generator (``bench/generators/<generator>.py``, see
``data``), the keyword arguments of its ``ForestKernel`` (``forest``) and,
by its ``kernel_method``, the reference's weights
(``bench/weights/<kernel_method>.py``, see ``reference``); the mix names
its operation (``bench/ops/<op>.py``, class ``Op``) and its loop
(``bench/loops/<loop>.py``: ``prepare``, ``run``, ``end_to_end``); each
per-layer metric is read by ``bench/metrics/<metric>.py`` (``read``).
This file keeps set-up, the check and the report.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

from . import data, trace
from .lookup import load
from .reference import ReferenceForest

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
CACHE = ROOT / ".bench_cache" / "jax"


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- the spec --
def load_spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as fh:
        return json.load(fh)


def load_cell(name: str, spec: dict, sizes: Optional[dict] = None) -> tuple:
    """(cell, configuration, traffic) of workload ``name``.  Tests shrink
    the cell with ``sizes`` ({"cfg": ..., "forest": ..., "traffic": ...}
    updates)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(ROOT / conf["file"]) as fh:
        cfg = json.load(fh)
    with open(BENCH / "traffic" / f"{cell['traffic']}.json") as fh:
        traffic = json.load(fh)
    if sizes:
        cfg.update(sizes.get("cfg", {}))
        cfg["forest"].update(sizes.get("forest", {}))
        traffic.update(sizes.get("traffic", {}))
    return cell, cfg, traffic


def metrics_for(spec: dict, cell: str, kind: str) -> List[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics."""
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


# ------------------------------------------------------------ the chip --
def require_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found {devs[0].platform!r} only")
    if len(devs) < chips:
        raise NoDevice(f"cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Compilations (count, seconds) from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def configure(cache: Optional[Path] = None) -> None:
    """The program on the path; JAX's persistent compilation cache at a
    fixed directory of this checkout (``CACHE``), whatever the environment
    names, with every program of the cell written to it (a cell compiles a
    few programs, not thousands)."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax
    cache = cache or CACHE
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ------------------------------------------------------------- set-up --
@dataclasses.dataclass
class Setup:
    cfg: dict
    kernel: object            # repro.core.api.ForestKernel
    X_train: np.ndarray
    y_train: np.ndarray


def fit(cfg: dict) -> Setup:
    """The configuration's training rows, host fit and factorization: every
    key of its ``forest`` is a ``ForestKernel`` field, passed as it is
    (``dtype`` by name); the seed comes from ``model_seed``."""
    from repro.core.api import ForestKernel
    f = dict(cfg["forest"])
    fields = {k.name for k in dataclasses.fields(ForestKernel)}
    unknown = sorted(set(f) - fields)
    if unknown:
        raise ValueError(f"forest keys {unknown} of configuration "
                         f"{cfg.get('name')!r} are not ForestKernel fields")
    if "dtype" in f:
        f["dtype"] = np.dtype(f["dtype"]).type
    X, y = data.training_rows(cfg)
    fk = ForestKernel(**f, seed=data.forest_seed(cfg),
                      n_jobs=os.cpu_count() or 1)
    fk.fit(X, y)
    return Setup(cfg=cfg, kernel=fk, X_train=X, y_train=y)


def reference(s: Setup) -> ReferenceForest:
    forest = s.kernel.forest
    return ReferenceForest(forest.trees_, forest.inbag_, s.X_train,
                           s.y_train, s.cfg["forest"]["kernel_method"],
                           tree_weights=forest.tree_weights_)


# ----------------------------------------------------------- a window --
@dataclasses.dataclass
class Call:
    """One call of the op; ``X`` the rows it was sent."""
    op: str
    X: np.ndarray
    t0: float
    t_route: float
    t1: float
    ok: bool = True
    bytes: Optional[int] = None


@dataclasses.dataclass
class Window:
    calls: List[Call]
    answers: list              # the answers of the calls that returned
    seconds: float


def annotate(on: bool):
    """``span(name)``: a ``bench:<name>`` span on the profiler's clock."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return lambda name: jax.profiler.TraceAnnotation(trace.SPAN + name)


@dataclasses.dataclass
class RunData:
    """What the per-layer readers read."""
    calls: List[Call]
    trace: Optional[trace.Reduction]
    device_kind: str


def setup(cfg: dict, traffic: dict, seed: int) -> tuple:
    """(set-up, op, loop, the loop's prepared traffic)."""
    s = fit(cfg)
    op = load("ops", traffic["op"]).Op(s, traffic)
    loop = load("loops", traffic["loop"])
    return s, op, loop, loop.prepare(op, s.kernel.engine, cfg, traffic, seed)


def check(op, ref: ReferenceForest, w: Window, n_rows: int, seed: int,
          precision: str = "float64") -> dict:
    """The op's compared numbers over a seeded sample of the rows the
    window answered."""
    if not w.answers:
        return {k: sys.float_info.max for k in op.numbers}
    X = np.concatenate([c.X for c in w.calls if c.ok])
    rng = data.seed_rng(seed, "sample")
    idx = np.sort(rng.choice(len(X), size=min(n_rows, len(X)),
                             replace=False))
    gaps = op.gaps(ref, X[idx], op.sample(w.answers, idx), precision)
    # a wrong shape or a non-finite answer reads inf: JSON has no inf
    return {k: min(v, sys.float_info.max) for k, v in gaps.items()}


# ------------------------------------------------------------ a run --
def run(workload: str, seed: int, seconds: float, traced: bool,
        t_start: float, spec: Optional[dict] = None,
        devices: Optional[Callable] = None,
        sizes: Optional[dict] = None) -> dict:
    """One run of ``workload``: the result line's object.  Tests stand in
    for the chip with ``devices``."""
    spec = load_spec() if spec is None else spec
    cell, cfg, traffic = load_cell(workload, spec, sizes)
    devs = (devices or require_devices)(cell["chips"])
    counter = CompileCounter()
    try:
        return _run(spec, cell, cfg, traffic, seed, seconds, traced,
                    t_start, devs, counter)
    finally:
        counter.close()


def _run(spec, cell, cfg, traffic, seed, seconds, traced, t_start, devs,
         counter) -> dict:
    s, op, loop, prepared = setup(cfg, traffic, seed)
    setup_s = time.perf_counter() - t_start
    print(f"set-up {setup_s:.3f} s, {counter.n} compiles "
          f"({counter.secs:.3f} s), {s.kernel.ctx.total_leaves} leaves",
          file=sys.stderr)

    tdir = None
    if traced:
        import jax
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
    n0 = counter.n
    try:
        with annotate(traced)("window"):
            w = loop.run(op, s.kernel.engine, prepared, seconds, traced)
    finally:
        if traced:
            jax.profiler.stop_trace()
    print(f"window {w.seconds:.3f} s, {len(w.calls)} calls, "
          f"{counter.n - n0} compiles inside it", file=sys.stderr)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    red = None
    if traced:
        red = trace.Reduction(trace.extract(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s

    # the program's state goes before the reference runs
    s.kernel.engine = op.engine = None
    ref = reference(s)
    numbers = check(op, ref, w, traffic["check_rows"], seed)
    limits = traffic["limits"]
    failed = sum(not c.ok for c in w.calls)
    correct = failed == 0 and bool(w.answers) and all(
        numbers[k] <= limits[k] for k in op.numbers)

    if traced:
        for c in w.calls:
            if c.ok:
                c.bytes = op.work_bytes(len(c.X))
        rd = RunData(w.calls, red, device["kind"])
        metrics = {}
        for m in metrics_for(spec, cell["name"], "per_layer"):
            v = load("metrics", m["name"]).read(rd)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = dict(loop.end_to_end(w), setup_s=setup_s)
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in metrics_for(spec, cell["name"], "end_to_end")
                   if m["name"] in e2e}
    out = {"correct": bool(correct), "attempted": len(w.calls),
           "failed": failed, "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = {"device_ops": red.top_ops(),
                            "idle_gaps": red.idle_gaps()}
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in op.numbers}
    return out


def report(result: dict) -> None:
    """Compared numbers as the last lines of stderr, then the result line
    as the last line of stdout."""
    for k, c in result["checks"].items():
        ok = c["value"] <= c["limit"]
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv: Optional[list] = None, t_start: float = 0.0) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    configure()
    try:
        res = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start)
    except NoDevice as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    report(res)
    return 0
