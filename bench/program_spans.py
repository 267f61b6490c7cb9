"""The program's own spans (``repro.obs`` telemetry), read by per-layer
metrics of the traced run.

A reader of such a metric calls :func:`start` when it is imported: the
harness imports the readers of a traced run before its set-up, so a live
registry is installed before any program code runs.  The harness's set-up
opens a registry of its own and, when it closes, restores this one, so
the measured window's spans land here.  The first :func:`ms_per_call`
after the window puts the replaced registry back, so nothing stays live in
the process, and keeps the window's samples for the other readers.

A program without the span (an older checkout) gives no samples, and the
metric is left out of the result line.
"""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: the kind of run (``ctx["run"]["kind"]``) whose entry opens spans of
#: each prefix
KINDS = {"provision/": "plan", "serving/advance/": "live"}

_run: dict = {}


def start() -> None:
    """Install a fresh live registry for this run, unless one is live."""
    if "live" in _run:
        return
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.obs import Telemetry, set_telemetry

    live = Telemetry()
    _run.clear()
    _run.update(live=live, replaced=set_telemetry(live))


def _frozen():
    """The window's registry; on the first call after :func:`start`, put
    the replaced registry back first."""
    if "live" in _run:
        from repro.obs import set_telemetry

        set_telemetry(_run.pop("replaced"))
        _run["frozen"] = _run.pop("live")
    return _run.get("frozen")


def ms_per_call(name: str, ctx) -> float | None:
    """Sum of the program span ``name`` in ms over the window's calls, or
    None where the run is of the other kind or the span has no samples."""
    frozen = _frozen()
    run = ctx["run"]
    kind = next(k for prefix, k in KINDS.items() if name.startswith(prefix))
    if frozen is None or run["kind"] != kind or not run["calls"]:
        return None
    samples = frozen.samples(f"span/{name}")
    return sum(samples) / run["calls"] if samples else None
