"""Files found by name: ``bench/<folder>/<name>.py`` under a checkout's root.

Each kind of part that a later cell may need sits in a folder of its own,
and a cell names it in its data files:

- ``entries/<entry>.py``: the caller a traffic file's ``entry`` names
  (:mod:`bench.callers`);
- ``generators/<generator>.py``: the demand generator a configuration's
  ``demand.generator`` names (:mod:`bench.demand`);
- ``policies/<policy>.py``: the reference's rule for a traffic file's
  ``policy`` (:mod:`bench.reference`);
- ``metrics/<name>.py``: the reader of a per-layer metric of
  ``BENCHMARK.json`` (:mod:`bench.harness`).
"""
from __future__ import annotations

import importlib.util
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = "bench"


def module(folder: str, name: str, root=ROOT):
    """The module ``bench/<folder>/<name>.py`` under ``root``."""
    path = pathlib.Path(root) / HERE / folder / f"{name}.py"
    if not path.is_file():
        have = sorted(p.stem for p in path.parent.glob("*.py"))
        raise KeyError(f"no {folder} file {name!r} under {path.parent}: have {have}")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
