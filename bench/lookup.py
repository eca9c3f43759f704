"""The benchmark's pieces found by name: the module ``bench/<kind>/<name>.py``.

A configuration names its data generator (``generators/<generator>.py``)
and, through its forest's ``kernel_method``, the reference's weight
assignment (``weights/<kernel_method>.py``); a traffic mix names its op
(``ops``) and its loop (``loops``); each per-layer metric has its reader
(``metrics``).  A new deployment or cell is then new files, never an edit.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``; a missing file is an error
    that names the path looked for."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
