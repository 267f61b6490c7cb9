"""JAX runtime health: compile accounting and profiler hooks.

:class:`CompileWatcher` is the one implementation of the jit-cache-delta
pattern that used to be hand-rolled in three places (the eval harness's
``_engine_cache_size``, ``benchmarks/provision_bench.py``'s cache gates,
and ``benchmarks/cr_eval.py``'s mesh smoke): snapshot the compiled-program
count of a set of jitted functions, run something, and report how many
programs the run added.  The engine's entrypoints — one jitted body per
route: ``_run``/``_run_noise_sweep`` and their streaming twins
``_run_stream``/``_run_stream_noise`` (which share ``_scan_sweep``),
``_sharded_grid`` and ``_sharded_stream_grid`` (which share
``_grid_inputs``/``_grid_map``) — are separate jitted functions
*precisely so* their compiles are observable here.

The count rides JAX's private ``_cache_size`` API; when that API is gone
the watcher degrades exactly like the code it replaced: ``snapshot()``
returns -1 and ``added`` is -1 (callers treat negative as "unobservable",
never as a failure).

Where available, :func:`install_monitoring` additionally forwards JAX's own
``jax.monitoring`` event stream (backend compile durations, tracing events)
into a :class:`~repro.obs.telemetry.Telemetry` registry, and
:func:`profile_to` wraps a region in ``jax.profiler.trace`` — the hook the
benchmark CLIs expose as ``--profile DIR``.
"""
from __future__ import annotations

import contextlib

from .telemetry import Telemetry, get_telemetry


def engine_fns() -> tuple:
    """The provisioning engine's countable jitted entrypoints: the
    monolithic and streaming bodies of both routes."""
    from repro.core.jax_provision import (
        _run,
        _run_noise_sweep,
        _run_stream,
        _run_stream_noise,
        _sharded_grid,
        _sharded_stream_grid,
    )

    return (_run, _run_noise_sweep, _sharded_grid,
            _run_stream, _run_stream_noise, _sharded_stream_grid)


class CompileWatcher:
    """Count compiled-program cache growth across a region.

    ``fns``: the jitted functions to watch (default: :func:`engine_fns`).
    Use as a context manager::

        with CompileWatcher() as w:
            provision(spec)
        assert w.added == 1          # cold compile; 0 on a warmed re-run

    or imperatively via :meth:`snapshot` deltas.  ``added`` is -1 whenever
    the private ``_cache_size`` API is unavailable on any watched function
    (same contract as the three helpers this class replaced).  On context
    exit the delta is also counted into the active telemetry registry
    (counter ``jax/compiles``) when one is installed.
    """

    def __init__(self, fns=None, telemetry: Telemetry | None = None):
        self.fns = tuple(fns) if fns is not None else engine_fns()
        self.telemetry = telemetry
        self._start: int | None = None
        self.added: int = -1

    @property
    def available(self) -> bool:
        return all(hasattr(f, "_cache_size") for f in self.fns)

    def snapshot(self) -> int:
        """Total compiled-program count over the watched functions, or -1
        if the private JAX cache API is gone."""
        if not self.available:
            return -1
        return sum(f._cache_size() for f in self.fns)

    def __enter__(self) -> "CompileWatcher":
        self._start = self.snapshot()
        return self

    def __exit__(self, *exc) -> bool:
        now = self.snapshot()
        self.added = -1 if (self._start is None or self._start < 0 or now < 0) \
            else now - self._start
        tel = self.telemetry if self.telemetry is not None else get_telemetry()
        if self.added > 0:
            tel.count("jax/compiles", self.added)
        return False


def engine_cache_size() -> int:
    """Compiled-program count across the engine entrypoints (-1 if the
    private JAX cache API is gone) — the drop-in form of the old
    ``repro.eval.harness._engine_cache_size``."""
    return CompileWatcher().snapshot()


_MONITORING_INSTALLED = False


def install_monitoring(telemetry: Telemetry | None = None) -> bool:
    """Forward ``jax.monitoring`` events into telemetry, where available.

    Registers one event listener (→ counter ``jax_event/<name>``) and one
    duration listener (→ histogram ``jax_duration/<name>``, seconds).  The
    listeners read the *active* registry at event time (or the explicit
    ``telemetry``), so a NullTelemetry default keeps them free.  Installs at
    most once per process; returns False when the API is missing.
    """
    global _MONITORING_INSTALLED
    if _MONITORING_INSTALLED:
        return True
    try:
        import jax.monitoring as monitoring

        def _tel() -> Telemetry:
            return telemetry if telemetry is not None else get_telemetry()

        def on_event(name: str, **kw) -> None:
            _tel().count(f"jax_event{name if name.startswith('/') else '/' + name}")

        def on_duration(name: str, secs: float, **kw) -> None:
            _tel().observe(
                f"jax_duration{name if name.startswith('/') else '/' + name}",
                secs,
            )

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)
    except Exception:
        return False
    _MONITORING_INSTALLED = True
    return True


@contextlib.contextmanager
def profile_to(directory=None):
    """``jax.profiler.trace`` over a region when ``directory`` is set, a
    no-op otherwise — the implementation behind the benchmark CLIs'
    ``--profile DIR`` flag (view the result with TensorBoard's profile
    plugin or Perfetto)."""
    if directory is None:
        yield
        return
    import jax

    with jax.profiler.trace(str(directory)):
        yield
