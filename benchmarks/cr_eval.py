"""Competitive-ratio evaluation CLI: the paper's claims as a JSON artifact.

Runs ``repro.eval.evaluate`` over the scenario library and writes the
:class:`~repro.eval.report.EvalReport` to ``BENCH_provision.json`` — the
repo's provisioning-quality trajectory (CI uploads it per commit).

    PYTHONPATH=src python benchmarks/cr_eval.py --smoke   # CI leg, ~30 s
    PYTHONPATH=src python benchmarks/cr_eval.py           # full grid
    PYTHONPATH=src python benchmarks/cr_eval.py --profile /tmp/prof

The smoke leg also runs under a live :mod:`repro.obs.telemetry` registry
and drops two sidecar artifacts next to the report (CI uploads all three):
``BENCH_provision.trace.json`` — a Chrome trace of the harness spans +
compile events, viewable at https://ui.perfetto.dev — and
``BENCH_provision.metrics.jsonl`` — the counters/gauges/histogram
summaries, one JSON record per line.  ``--profile DIR`` additionally wraps
the run in ``jax.profiler.trace``.

Both legs hard-fail if any (policy, scenario, noise, α) cell's empirical CR
violates its paper bound beyond the grid tolerance, or if re-running the
grid recompiles anything (the whole grid must execute as warmed batched
device programs — one program per (policy, scenario), shapes shared across
scenarios).  Both grids carry ``TYPED_GROUPS`` — a two-generation
heterogeneous fleet — so every run also records multi-type AQ-det/AQ-rand
cells with per-type CR verdicts, gated against the Albers–Quedenfeld 2d
(and d·e/(e−1)) aggregate bounds.  Both grids also sweep
``DEFERRAL_SLACKS``: deferral cells run the defer-then-provision path and
are gated on the latency-SLO verdict (``slo_ok`` — zero deadline misses,
p99 queueing delay within the granted slack) on top of the CR bound.

Both legs also record the v5 ``streaming`` section
(:func:`streaming_latency`): the ``FleetProvisioner.advance()`` stepper
driven at T_chunk ∈ {1, 64, 1024}, its plan-latency p50/p99 from the
``PlanMetrics`` substrate, and a hard gate that the warmed loop adds zero
jit traces (the O(1)-state stepper's steady-state claim).  The latency
columns are machine facts — ``bench_diff.py`` diffs them informationally,
never gated.
"""
from __future__ import annotations

import argparse
import dataclasses
import pathlib
import sys

from repro.core import ServerGroup
from repro.eval import EvalGrid, EvalReport, evaluate
from repro.launch.compile_cache import enable_compile_cache
from repro.lint.sanitize import tracer_sanitizer
from repro.obs import (
    Telemetry,
    install_monitoring,
    profile_to,
    telemetry_session,
)
from repro.scenarios import Scenario

#: the benchmark's heterogeneous fleet: two server generations (Albers–
#: Quedenfeld d=2).  "efficient" is the paper's normalized server; "legacy"
#: burns 1.5× the power with proportionally pricier toggles (same Δ, so the
#: per-type ski-rental structure is identical and only routing differs).
TYPED_GROUPS = (
    ServerGroup("efficient", 96, P=1.0, beta_on=3.0, beta_off=3.0),
    ServerGroup("legacy", 96, P=1.5, beta_on=4.5, beta_off=4.5),
)

#: the deferral-slack sweep (slots): 0 is the rigid fixed point (bit-exact
#: with no deferral at all), the rest trace the cost-vs-slack curve
DEFERRAL_SLACKS = (0, 2, 6, 12)

#: the serving-loop chunk sizes the streaming section measures — one slot
#: at a time (the latency floor), a typical scrape interval, and a bulk
#: backfill chunk
STREAM_CHUNKS = (1, 64, 1024)

SMOKE_GRID = EvalGrid(
    noise_stds=(0.0, 0.2),
    windows=(0, 2, 4),
    n_traces=4,
    n_slots=288,
    typed_groups=TYPED_GROUPS,
    deferral_slacks=DEFERRAL_SLACKS,
)

FULL_GRID = EvalGrid(
    noise_stds=(0.0, 0.1, 0.25, 0.5),
    windows=(0, 1, 2, 3, 4, 5),
    n_traces=16,
    typed_groups=TYPED_GROUPS,
    deferral_slacks=DEFERRAL_SLACKS,
)


def mesh_smoke() -> None:
    """One mesh-path grid cell through ``evaluate``: the sharded Pallas
    fleet engine must reproduce the lax.scan cells bit-exactly AND compile
    exactly one ``_sharded_grid`` program for the whole (policy, scenario)
    block — the fleet-path analogue of the existing no-recompile gates."""
    import jax

    from repro.core.jax_provision import _sharded_grid

    grid = EvalGrid(
        policies=("A1",),
        scenarios=(Scenario("sinusoidal", target_pmr=4.0, mean_jobs=16.0),),
        noise_stds=(0.0, 0.2),
        windows=(0, 2),
        n_traces=2,
        n_slots=144,
    )
    plain = evaluate(grid)
    # the gated sanitizer raises RecompileError unless the whole block
    # compiled exactly one _sharded_grid program (degrades silently when
    # the private cache API is gone, like the hand-rolled delta it replaced)
    with tracer_sanitizer(fns=(_sharded_grid,), exact_compiles=1):
        meshed = evaluate(dataclasses.replace(
            grid, mesh=jax.make_mesh((len(jax.devices()),), ("data",))
        ))
    if meshed.cells != plain.cells:
        raise AssertionError(
            "mesh-path eval cells diverge from the lax.scan path: the "
            "Pallas fleet engine is supposed to be bit-exact"
        )
    print(
        f"# mesh smoke: {len(meshed.cells)} cells bit-exact through the "
        "fleet path, 1 sharded compile", file=sys.stderr,
    )


def streaming_latency(smoke: bool) -> list:
    """The v5 ``streaming`` section: drive ``FleetProvisioner.advance()``
    at each ``STREAM_CHUNKS`` size over one demand stream, record the
    stepper's plan-latency p50/p99 through the ``PlanMetrics`` substrate,
    and gate the zero-steady-state-recompile claim — after the warmup call
    owns the chunk bucket's trace, the measured loop must add no jit
    entries at all."""
    import numpy as np

    from repro.core.costs import PAPER_COSTS
    from repro.eval.report import StreamingRow
    from repro.serving import stepper
    from repro.serving.autoscaler import FleetProvisioner
    from repro.serving.metrics import PlanMetrics

    rows = []
    rng = np.random.default_rng(0)
    for t_chunk in STREAM_CHUNKS:
        chunks = min(32, max(4, (256 if smoke else 8192) // t_chunk))
        demand = rng.integers(0, 48, size=((chunks + 1) * t_chunk,))
        prov = FleetProvisioner(PAPER_COSTS, policy="A1", max_replicas=64)
        prov.advance(demand[:t_chunk])      # warmup owns the bucket's trace
        prov.metrics = PlanMetrics()
        # hard zero-recompile gate on the warmed steady state (RecompileError
        # on violation), while watch.added still feeds the report row
        with tracer_sanitizer(fns=(stepper.stepper_chunk,
                                   stepper.stepper_tick)) as watch:
            for i in range(1, chunks + 1):
                prov.advance(demand[i * t_chunk:(i + 1) * t_chunk])
        rows.append(StreamingRow(
            policy="A1", t_chunk=t_chunk, chunks=chunks,
            slots=chunks * t_chunk, compiles=watch.added,
            p50_ms=prov.metrics.latency_quantile(0.5),
            p99_ms=prov.metrics.latency_quantile(0.99),
        ))
    print(
        "# streaming: " + "; ".join(
            f"t_chunk={r.t_chunk} p50={r.p50_ms:.2f}ms p99={r.p99_ms:.2f}ms "
            f"compiles={r.compiles}" for r in rows
        ),
        file=sys.stderr,
    )
    return rows


def run(grid: EvalGrid, out: pathlib.Path, check_warm: bool = True,
        streaming: list | None = None) -> EvalReport:
    report = evaluate(grid)
    report.streaming = streaming
    try:
        if check_warm:
            # the grid again, same shapes: every cell must hit the jit cache
            second = evaluate(grid)
            if second.jit_entries_added > 0:
                raise AssertionError(
                    f"warmed re-run recompiled {second.jit_entries_added} "
                    "program(s): a spec field leaked into the compile keys"
                )
        if report.jit_entries_added > report.expected_compiles:
            raise AssertionError(
                f"{report.jit_entries_added} compiles for "
                f"{len(report.grid['policies'])} policies — expected at most "
                f"{report.expected_compiles} (one per policy + offline); "
                "per-cell recompiles defeat the batched harness"
            )
        if not report.bounds_ok:
            lines = "\n".join(
                f"  {c.policy} on {c.scenario} (std={c.noise_std:g}, w={c.window}): "
                f"mean CR {c.mean_cr:.4f} > bound {c.bound:.4f}"
                for c in report.violations()
            )
            raise AssertionError(f"paper-bound violations:\n{lines}")
        if report.grid.get("typed_groups"):
            d = len(report.grid["typed_groups"])
            det = [c for c in report.cells
                   if c.group_mean_cr is not None and c.policy == "AQ-det"]
            if not det:
                raise AssertionError(
                    "grid declares typed_groups but produced no AQ-det "
                    "multi-type cell"
                )
            off = [c for c in det if c.bound != 2.0 * d]
            if off:
                raise AssertionError(
                    f"AQ-det typed cells must carry the 2d = {2.0 * d:g} "
                    f"aggregate bound, got {sorted({c.bound for c in off})}"
                )
        if report.grid.get("deferral_slacks"):
            dcells = [c for c in report.cells if c.slack is not None]
            want = (
                len(report.grid["deferral_slacks"])
                * len(report.grid["deferral_policies"])
                * len(report.grid["scenario_labels"])
            )
            if len(dcells) != want:
                raise AssertionError(
                    f"grid declares deferral_slacks but produced "
                    f"{len(dcells)} deferral cells, expected {want}"
                )
            bad_slo = [c for c in dcells if not c.slo_ok]
            if bad_slo:
                lines = "\n".join(
                    f"  {c.policy} on {c.scenario} slack={c.slack}: "
                    f"p99={c.p99_delay} misses={c.deadline_misses}"
                    for c in bad_slo
                )
                raise AssertionError(f"latency-SLO violations:\n{lines}")
            # the slack axis must actually buy something: per (policy,
            # scenario), the widest-slack cell may not cost more than rigid
            by_ps: dict[tuple, list] = {}
            for c in dcells:
                by_ps.setdefault((c.policy, c.scenario), []).append(c)
            for (policy, scenario), cs in by_ps.items():
                cs = sorted(cs, key=lambda c: c.slack)
                if cs[-1].mean_cost > cs[0].mean_cost:
                    raise AssertionError(
                        f"deferral bought nothing: {policy} on {scenario} "
                        f"costs {cs[0].mean_cost:.1f} rigid but "
                        f"{cs[-1].mean_cost:.1f} at slack={cs[-1].slack}"
                    )
    finally:
        # always leave the report on disk — a gate failure is exactly when
        # the per-cell diagnostics are needed (CI uploads it unconditionally)
        report.save(out)
    return report


def write_telemetry_artifacts(tel: Telemetry, out: pathlib.Path) -> None:
    """Drop the Chrome trace + metrics JSONL next to the report and assert
    both load back (the trace must be Perfetto-openable: a ``traceEvents``
    list with at least the harness's eval spans in it)."""
    import json

    trace_path = out.with_name(out.stem + ".trace.json")
    metrics_path = out.with_name(out.stem + ".metrics.jsonl")
    tel.write_chrome_trace(trace_path)
    tel.write_metrics_jsonl(metrics_path)
    loaded = json.loads(trace_path.read_text())
    events = loaded.get("traceEvents")
    if not isinstance(events, list) or not any(
        e.get("name", "").startswith("eval/") for e in events
    ):
        raise AssertionError(
            f"{trace_path} is not a loadable Chrome trace with eval spans"
        )
    records = [json.loads(line) for line in
               metrics_path.read_text().splitlines() if line]
    if not any(r.get("name", "").startswith("span/eval/") for r in records):
        raise AssertionError(f"{metrics_path} is missing the eval span metrics")
    print(f"# wrote {trace_path} ({len(events)} events) and "
          f"{metrics_path} ({len(records)} records)", file=sys.stderr)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small grid for CI (short traces, fewer cells)")
    ap.add_argument("--out", type=pathlib.Path,
                    default=pathlib.Path(__file__).parent.parent / "BENCH_provision.json",
                    help="report path (default: repo-root BENCH_provision.json)")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="write a jax.profiler trace of the run to DIR")
    args = ap.parse_args()

    enable_compile_cache()
    install_monitoring()
    with telemetry_session() as tel, profile_to(args.profile):
        if args.smoke:
            mesh_smoke()
        stream_rows = streaming_latency(smoke=args.smoke)
        report = run(SMOKE_GRID if args.smoke else FULL_GRID, args.out,
                     streaming=stream_rows)
    if args.smoke:
        write_telemetry_artifacts(tel, args.out)
    for line in report.summary_lines():
        print(line)
    worst = report.worst(1)[0]
    print(
        f"# {len(report.cells)} cells ({'smoke' if args.smoke else 'full'}), "
        f"backend={report.backend}, {report.elapsed_s:.1f}s, "
        f"compiles={report.jit_entries_added}/{report.expected_compiles}, "
        f"tightest cell: {worst.policy} on {worst.scenario} "
        f"(mean CR {worst.mean_cr:.4f} vs bound {worst.bound:.4f})",
        file=sys.stderr,
    )
    print(f"# wrote {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
