"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

- Device busy time: the union of the intervals of the device's operations
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged over
  the chips that ran any.
- Idle gaps: the stretches of the traced window in which no operation ran,
  each named by the benchmark's host span (``bench/...``) that was open at
  its midpoint, ``(no span)`` where none was.
- Kernel time: the summed durations of the operations whose HLO text (the
  event's name on a TPU's ``XLA Ops`` line) holds a given pattern.

Events carry nanoseconds on one clock for host and device planes.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def short_name(name: str) -> str:
    """``%while.13 = (...) while(...)`` -> ``while.13``; a custom call keeps
    its target: ``name (tpu_custom_call)``."""
    short = name.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="' in name:
        short += " (" + name.split('custom_call_target="', 1)[1].split('"', 1)[0] + ")"
    return short


def read(path: str) -> dict:
    """Plain lists from an xplane file: ``ops`` per device plane as
    ``(start_ns, end_ns, short name, HLO text)`` and host ``spans`` as
    ``(start_ns, end_ns, name)`` for the benchmark's own annotations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    names = {}          # one (short, text) pair per distinct HLO text
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    if name not in names:
                        names[name] = (short_name(name), name)
                    ops.append((ev.start_ns, ev.end_ns) + names[name])
            if ops:
                devices[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
    return {"devices": devices, "spans": sorted(spans)}


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def covering_span(spans, t) -> str:
    """Name of the benchmark span open at time ``t`` (the benchmark's spans
    do not nest: the latest one to start before ``t`` is the only
    candidate)."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    if i >= 0 and spans[i][0] <= t <= spans[i][1]:
        return spans[i][2]
    return "(no span)"


def reduce(raw: dict, *, window_ns: tuple[float, float] | None = None,
           kernels: dict | None = None) -> dict:
    """Summary of one traced window.

    ``window_ns``: ``(start, end)`` of the window on the trace's clock;
    default the extent of the benchmark's host spans.  ``kernels``:
    ``{label: pattern}`` of kernels whose time to sum.  Returns seconds:
    ``window_s``, ``busy_s`` (mean over the chips that ran), per-op totals
    ``ops``, idle gaps by host span ``gaps``, and ``kernels``.
    """
    spans = raw["spans"]
    if window_ns is None:
        if not spans:
            raise ValueError("no benchmark spans in the trace and no window given")
        window_ns = (min(s for s, _, _ in spans), max(e for _, e, _ in spans))
    lo, hi = window_ns
    busy, ops, gaps, kern = [], defaultdict(float), defaultdict(float), defaultdict(float)
    kernels = kernels or {}
    for name, dev in raw["devices"].items():
        inside = [o for o in dev if o[1] > lo and o[0] < hi]
        merged = clip(union(inside), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for s, e, op, detail in inside:
            d = (min(e, hi) - max(s, lo)) / 1e9
            ops[op] += d
            for label, pat in kernels.items():
                if pat in detail:
                    kern[label] += d
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps[covering_span(spans, (s + e) / 2)] += (e - s) / 1e9
    n_dev = max(len(busy), 1)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "n_devices": len(busy),
        "ops": {k: v / n_dev for k, v in ops.items()},
        "gaps": {k: v / n_dev for k, v in gaps.items()},
        "kernels": {k: v / n_dev for k, v in kern.items()},
    }


def top(d: dict, n: int = 10) -> list[list]:
    """The ``n`` largest ``[name, seconds]`` entries of a dict."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
