"""The benchmark harness: one cell of ``BENCHMARK.json`` per run.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- a configuration: the ``file`` of its entry in ``configs``, whose demand
  generator is ``bench/generators/<generator>.py``;
- a traffic mix: ``bench/traffic/<traffic>.json``, read by the caller its
  ``entry`` names (``bench/entries/<entry>.py``) and checked by the
  reference's rule for its ``policy`` (``bench/policies/<policy>.py``);
- a per-layer metric: ``bench/metrics/<name>.py``, whose ``read(ctx)``
  returns the number or None where it finds nothing to read, and whose
  optional ``KERNELS`` maps a label to the name of a kernel whose device
  time it needs.  It is read in the cells its ``workloads`` list.

A run builds its inputs from the seed, warms up every shape its window
uses (set-up), measures for ``--seconds`` seconds with one closed-loop
caller, and then, with the device state freed, compares what the window
produced with the plain reference (:mod:`bench.compare`).  With
``--trace 1`` the window runs under the profiler and the line carries the
per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

from . import callers, compare, discover, spec_bytes
from . import trace as trace_mod

ROOT = discover.ROOT
HERE = discover.HERE
#: length of a traced run's window: the device trace of the scan route
#: records every operation of every scan step (about 0.5 M events a second)
TRACE_SECONDS = 2.0


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Discovery by name
# ---------------------------------------------------------------------------

def load_benchmark(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(root, bench: dict, workload: str):
    """(cell entry, configuration, traffic) of a workload name."""
    root = pathlib.Path(root)
    cell = _named(bench["workloads"], workload, "workload")
    cfg_entry = _named(bench["configs"], cell["config"], "configuration")
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((root / HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def reported(bench: dict, cell: dict):
    """(end-to-end, per-layer) metric entries the cell reports.  An
    end-to-end metric without ``workloads`` is every cell's; a per-layer
    metric names its cells."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    layer = [m for m in bench["per_layer"] if name in m["workloads"]]
    return e2e, layer


# ---------------------------------------------------------------------------
# JAX set-up: devices, compile cache, compile counting
# ---------------------------------------------------------------------------

_COUNTS = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}
_LISTENING = []


def _listen():
    """Count backend compiles and persistent-cache hits and misses (once
    per process: JAX keeps listeners for the process's lifetime)."""
    if _LISTENING:
        return
    import jax

    def on_duration(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            _COUNTS["compiles"] += 1

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            _COUNTS["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _COUNTS["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _LISTENING.append(True)


#: values of the program's ``REPRO_PALLAS_INTERPRET`` that force its
#: Pallas kernels into interpret mode
INTERPRET_ON = ("1", "true", "yes", "on")


def devices_for(chips: int, require_accelerator: bool):
    import jax

    devs = jax.devices()
    if require_accelerator:
        if devs[0].platform == "cpu":
            raise NoAccelerator("JAX finds no accelerator (platform cpu)")
        if len(devs) < chips:
            raise NoAccelerator(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
        if os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower() in INTERPRET_ON:
            raise NoAccelerator("REPRO_PALLAS_INTERPRET forces the kernels into "
                                "interpret mode on the accelerator")
    return devs


def enable_cache(root):
    """JAX's persistent compilation cache at a fixed path in the checkout."""
    import jax

    path = pathlib.Path(root) / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    # every program, the eager ops' small ones too, so that a warm set-up
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def memory_peak(dev) -> int | None:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else int(peak)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_accelerator: bool = True, cache: bool = True,
             keep_trace: str | None = None, hook=None) -> dict:
    """Run one cell; return the result line as a dict.

    ``t_start``: the process's start on ``time.perf_counter``'s clock (set-up
    counts from there).  ``require_accelerator=False`` skips the look for a
    chip (tests on the CPU); ``hook(caller)`` may replace parts of
    the caller after its set-up (tests that plant a fault).
    """
    bench = load_benchmark(root)
    cell, config, traffic = load_cell(root, bench, workload)
    e2e_entries, layer_entries = reported(bench, cell)
    readers = ({m["name"]: discover.module("metrics", m["name"], root) for m in layer_entries}
               if trace else {})

    sys.path.insert(0, str(pathlib.Path(root) / "src"))
    import jax

    devs = devices_for(int(cell["chips"]), require_accelerator)
    dev = devs[0]
    peaks = spec_bytes.peaks(dev.device_kind) if require_accelerator else None
    if cache:
        log(f"[setup] compile cache {enable_cache(root)}")
    _listen()
    before = dict(_COUNTS)

    from repro.obs import telemetry_session

    cl = callers.make(config, traffic, seed, root)
    # the program records its Pallas route (1 = interpret) while it traces
    with telemetry_session() as tel:
        cl.setup()
        if hook is not None:
            hook(cl)
        cl.warm()
    interpreted = tel.gauge_value("kernels/pallas_interpret") == 1.0
    setup_s = time.perf_counter() - t_start
    for label, secs in cl.first_calls.items():
        log(f"[setup] first call {label}: {secs:.3f} s")
    log("[setup] compiles {compiles}, persistent-cache hits {cache_hits}, misses "
        "{cache_misses}".format(**{k: _COUNTS[k] - before[k] for k in _COUNTS}))

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles0 = _COUNTS["compiles"]
    if trace:
        # host spans are the benchmark's own annotations; no Python tracer
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level, opts.python_tracer_level = 1, 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        seconds = min(seconds, TRACE_SECONDS)
    try:
        cl.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = _COUNTS["compiles"] - compiles0
    log(f"[window] {cl.calls} calls in {cl.window_s:.3f} s, compiles inside: {in_window}")
    peak = memory_peak(dev)
    e2e = dict(cl.end_to_end(), setup_s=setup_s)
    layer_run = cl.layer_inputs()
    cl.drop_state()

    summary = None
    if trace:
        kernels = {}
        for mod in readers.values():
            kernels.update(getattr(mod, "KERNELS", {}))
        try:
            path = trace_mod.find_xplane(log_dir)
            if keep_trace:
                shutil.copy(path, keep_trace)
            summary = trace_mod.reduce(trace_mod.read(path), kernels=kernels)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    t_check = time.perf_counter()
    nums, failed = cl.check()
    correct, checks = compare.verdict(nums, traffic["limits"])
    log(f"[check] reference took {time.perf_counter() - t_check:.3f} s")

    units = {m["name"]: m["unit"] for m in e2e_entries + layer_entries}
    metrics = {}
    if trace:
        ctx = {"run": layer_run, "trace": summary, "peaks": peaks, "cell": cell}
        for name, mod in readers.items():
            v = mod.read(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": units[name]}
    else:
        for m in e2e_entries:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": peak}
    if interpreted and require_accelerator:
        log("[setup] the program ran its Pallas kernels in interpret mode")
    result = {"correct": bool(correct and in_window == 0
                              and not (interpreted and require_accelerator)),
              "attempted": cl.calls, "failed": failed, "metrics": metrics,
              "device": device}
    if in_window:
        log(f"[window] {in_window} compiles inside the measured window")
    if trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": trace_mod.top(summary["ops"]),
                               "idle_gaps": trace_mod.top(summary["gaps"])}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"[check] {name} {c['value']} limit {c['limit']}")
    return result


def main(argv=None, t_start=None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's .xplane.pb to this path")
    args = ap.parse_args(argv)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start, keep_trace=args.keep_trace)
    except NoAccelerator as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
