"""Host-side telemetry: counters, gauges, histograms, and span timers.

One :class:`Telemetry` instance is a process-local registry of metrics plus
a buffer of timing events, exportable two ways:

  * **Chrome trace-event JSON** (:meth:`Telemetry.chrome_trace`) — every
    ``span()`` becomes a complete ("ph": "X") event, loadable in Perfetto
    (https://ui.perfetto.dev) or ``chrome://tracing`` for a flame view of
    where a benchmark's wall time went;
  * **JSON-lines metrics** (:meth:`Telemetry.metrics_records`) — one JSON
    object per counter/gauge/histogram, machine-diffable next to
    ``BENCH_provision.json``.

The process-global default is a :class:`NullTelemetry`: every instrumented
call site reads ``get_telemetry()`` and gets an object whose methods do
nothing, so instrumentation left in library code costs one attribute lookup
and one no-op call when nobody is collecting.  That is the **zero-overhead
contract** (docs/observability.md): telemetry never allocates, never times,
and — crucially — never crosses the jit boundary when disabled.  Spans wrap
*host-side* work (a ``provision`` call, a benchmark cell); in-graph
provenance is :mod:`repro.obs.provenance`'s job.  On a live registry a span
is also a ``jax.profiler.TraceAnnotation``: under a profiler trace
(:func:`repro.obs.profile_to`) the program's phases sit on the host plane
beside the device's ops, on one clock.

Enable collection for a region with::

    from repro.obs import Telemetry, telemetry_session

    with telemetry_session() as tel:          # or telemetry_session(Telemetry())
        run_benchmark()
    tel.write_chrome_trace("bench.trace.json")
    tel.write_metrics_jsonl("bench.metrics.jsonl")

Labels: every metric accepts keyword labels (``tel.count("cells", policy="A1")``);
a (name, labels) pair is one series.  All methods are thread-safe.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import os
import pathlib
import threading
import time


def _label_key(labels: dict) -> tuple:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


class _OpenSpans(threading.local):
    """The spans open on each thread, innermost last."""

    def __init__(self) -> None:
        self.spans: list = []


class Telemetry:
    """A live metric registry + trace-event buffer (see module docstring)."""

    enabled: bool = True

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: dict[tuple, float] = {}
        self._gauges: dict[tuple, float] = {}
        self._hists: dict[tuple, list[float]] = {}
        self._events: list[dict] = []
        self._t0_ns = time.perf_counter_ns()
        self._open = _OpenSpans()
        self._calls = itertools.count()

    # ------------------------------------------------------------- metrics
    def count(self, name: str, value: float = 1.0, **labels) -> None:
        """Increment counter ``name`` (monotone; value may be fractional)."""
        k = (name, _label_key(labels))
        with self._lock:
            self._counters[k] = self._counters.get(k, 0.0) + value

    def gauge(self, name: str, value: float, **labels) -> None:
        """Set gauge ``name`` to its latest value."""
        with self._lock:
            self._gauges[(name, _label_key(labels))] = float(value)

    def observe(self, name: str, value: float, **labels) -> None:
        """Record one sample into histogram ``name``."""
        k = (name, _label_key(labels))
        with self._lock:
            self._hists.setdefault(k, []).append(float(value))

    def counter_value(self, name: str, **labels) -> float:
        return self._counters.get((name, _label_key(labels)), 0.0)

    def gauge_value(self, name: str, **labels) -> float | None:
        return self._gauges.get((name, _label_key(labels)))

    def samples(self, name: str, **labels) -> list[float]:
        return list(self._hists.get((name, _label_key(labels)), ()))

    def quantile(self, name: str, q: float, **labels) -> float | None:
        """The q-quantile (0..1, nearest-rank) of histogram ``name``."""
        vals = self._hists.get((name, _label_key(labels)))
        if not vals:
            return None
        s = sorted(vals)
        i = min(len(s) - 1, max(0, round(q * (len(s) - 1))))
        return s[i]

    # --------------------------------------------------------------- spans
    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0_ns) / 1e3

    def span(self, name: str, **args) -> "_Span":
        """Time a host-side region: a Chrome "X" event + a duration sample.

        The duration (ms) also lands in histogram ``span/<name>``, so p50/
        p99 of a repeated span are one :meth:`quantile` call away.  A span
        opened inside another on the same thread records the enclosing
        span's name as ``args.parent``; every span under one outermost span
        shares its integer ``args.call``.  The region is also a
        ``jax.profiler.TraceAnnotation``, so while a profiler trace runs
        the span sits on the trace's host plane, on the device ops' clock.
        Use as ``with tel.span(...) as sp:``; ``sp.set(k=v)`` adds args
        known only inside the region.
        """
        return _Span(self, name, args)

    # ------------------------------------------------------------- exports
    def chrome_trace(self) -> dict:
        """The buffered spans as a Chrome trace-event JSON object.

        Loadable as-is in Perfetto / ``chrome://tracing`` (the
        ``traceEvents`` envelope with microsecond timestamps).
        """
        with self._lock:
            events = list(self._events)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path) -> pathlib.Path:
        path = pathlib.Path(path)
        path.write_text(json.dumps(self.chrome_trace(), indent=1) + "\n")
        return path

    def metrics_records(self) -> list[dict]:
        """One JSON-able record per metric series (counters, gauges, and
        histograms with count/sum/min/max/p50/p99)."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {k: list(v) for k, v in self._hists.items()}
        out: list[dict] = []
        for (name, labels), v in sorted(counters.items()):
            out.append({"type": "counter", "name": name,
                        "labels": dict(labels), "value": v})
        for (name, labels), v in sorted(gauges.items()):
            out.append({"type": "gauge", "name": name,
                        "labels": dict(labels), "value": v})
        for (name, labels), vals in sorted(hists.items()):
            s = sorted(vals)
            out.append({
                "type": "histogram", "name": name, "labels": dict(labels),
                "count": len(s), "sum": sum(s), "min": s[0], "max": s[-1],
                "p50": s[round(0.5 * (len(s) - 1))],
                "p99": s[min(len(s) - 1, round(0.99 * (len(s) - 1)))],
            })
        return out

    def write_metrics_jsonl(self, path) -> pathlib.Path:
        path = pathlib.Path(path)
        lines = [json.dumps(r) for r in self.metrics_records()]
        path.write_text("\n".join(lines) + ("\n" if lines else ""))
        return path


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``; jax is imported here, not at
    module load (the registry itself needs no jax)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class _Span:
    """One live span (see :meth:`Telemetry.span`)."""

    __slots__ = ("_tel", "name", "args", "_ts", "_parent", "_call", "_ann")

    def __init__(self, tel: Telemetry, name: str, args: dict) -> None:
        self._tel, self.name, self.args = tel, name, args

    def set(self, **args) -> None:
        """Add or replace args of the span's event."""
        self.args.update(args)

    def __enter__(self) -> "_Span":
        stack = self._tel._open.spans
        if stack:
            self._parent, self._call = stack[-1].name, stack[-1]._call
        else:
            self._parent, self._call = None, next(self._tel._calls)
        stack.append(self)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self._ts = self._tel._now_us()
        return self

    def __exit__(self, *exc) -> None:
        tel = self._tel
        dur = tel._now_us() - self._ts
        self._ann.__exit__(*exc)
        tel._open.spans.pop()
        ev_args = {k: str(v) for k, v in self.args.items()}
        if self._parent is not None:
            ev_args["parent"] = self._parent
        ev_args["call"] = self._call
        ev = {
            "name": self.name, "ph": "X", "ts": self._ts, "dur": dur,
            "pid": os.getpid(), "tid": threading.get_ident(),
            "cat": "repro", "args": ev_args,
        }
        with tel._lock:
            tel._events.append(ev)
        tel.observe(f"span/{self.name}", dur / 1e3)


class _NullSpan:
    """The disabled span: one shared instance, no timing, no annotation."""

    __slots__ = ()

    def set(self, **args) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullTelemetry(Telemetry):
    """The disabled default: every method is a no-op and ``span`` neither
    times nor allocates.  Instrumented library code runs against this unless
    a caller installs a live :class:`Telemetry` (``telemetry_session``)."""

    enabled = False

    def __init__(self) -> None:  # no buffers, no lock traffic
        pass

    def count(self, name, value=1.0, **labels):
        pass

    def gauge(self, name, value, **labels):
        pass

    def observe(self, name, value, **labels):
        pass

    def counter_value(self, name, **labels):
        return 0.0

    def gauge_value(self, name, **labels):
        return None

    def samples(self, name, **labels):
        return []

    def quantile(self, name, q, **labels):
        return None

    def span(self, name, **args):
        return _NULL_SPAN

    def chrome_trace(self):
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def metrics_records(self):
        return []


#: the process-global registry every instrumented call site reads
_ACTIVE: Telemetry = NullTelemetry()


def get_telemetry() -> Telemetry:
    """The active registry (a no-op :class:`NullTelemetry` by default)."""
    return _ACTIVE


def set_telemetry(tel: Telemetry) -> Telemetry:
    """Install ``tel`` as the process-global registry; returns the old one."""
    global _ACTIVE
    old, _ACTIVE = _ACTIVE, tel
    return old


@contextlib.contextmanager
def telemetry_session(tel: Telemetry | None = None):
    """Install a live registry for a ``with`` region, restoring the previous
    one on exit.  ``telemetry_session()`` creates a fresh :class:`Telemetry`."""
    tel = Telemetry() if tel is None else tel
    old = set_telemetry(tel)
    try:
        yield tel
    finally:
        set_telemetry(old)
