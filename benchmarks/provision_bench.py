"""Provisioning-engine benchmarks: throughput of the declarative jitted fleet
provisioner (traces x alpha-sweep x levels as one `provision(spec)` program),
the fused Pallas scan path, heterogeneous per-level cost models, and the
event-driven brick simulator (cluster-controller capacity).

Run standalone for the CI smoke leg:

    PYTHONPATH=src python benchmarks/provision_bench.py --smoke

The smoke run uses small shapes and additionally asserts that re-pricing a
fleet (new CostModel values, same shapes/policy) does NOT grow the engine's
jit cache — the spec's cost fields are pytree data, not compile keys — that
one mesh-path (S, W, B) grid cell compiles exactly one `_sharded_grid`
program (none on a warmed re-run), and that the observability layer keeps
its zero-overhead contract (`telemetry_overhead` row: a live telemetry
registry adds 0 compiles to the warmed default path, and
``record_decisions=True`` leaves the schedule bit-exact).

``--profile DIR`` wraps the run in ``jax.profiler.trace``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    RANDOMIZED_POLICIES,
    CostModel,
    PolicySpec,
    PredictionNoise,
    ProvisionSpec,
    ServerGroup,
    Workload,
    generate_brick_trace,
    msr_like_trace,
    provision,
    simulate,
)
from repro.core.ski_rental import A1Deterministic
from repro.kernels.provision_scan import provision_scan
from repro.launch.compile_cache import enable_compile_cache
from repro.lint.sanitize import tracer_sanitizer
from repro.obs import CompileWatcher, profile_to, telemetry_session

COSTS = CostModel(P=1.0, beta_on=3.0, beta_off=3.0)
DELTA = int(COSTS.delta)
N_SLOTS = 1008


def _trace(n_levels: int, seed: int = 0, n_slots: int = N_SLOTS) -> np.ndarray:
    return msr_like_trace(
        np.random.default_rng(seed), mean_jobs=n_levels / 4.0, n_slots=n_slots
    )


def _spec(a, n_levels, policy="A1", windows=None, costs=COSTS, key=None):
    return ProvisionSpec(
        costs=costs,
        workload=Workload(demand=jnp.asarray(a, jnp.int32)),
        policy=PolicySpec(
            policy, window=2, windows=windows,
            key=key if policy in RANDOMIZED_POLICIES else None,
        ),
        n_levels=n_levels,
    )


def jax_provisioner_throughput(rows: list[str], sizes=(64, 512, 4096)) -> None:
    """Single-trace A1 path (the serving autoscaler's hot loop)."""
    for n_levels in sizes:
        a = _trace(n_levels)
        spec = _spec(a, n_levels)
        def fn():
            return provision(spec).x

        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(fn())
        us = (time.perf_counter() - t0) / 5 * 1e6
        rows.append(
            f"jax_provision_levels{n_levels},{us:.1f},"
            f"slots={len(a)};decisions_per_s={n_levels * len(a) / (us / 1e6):.3e}"
        )


def batched_sweep_throughput(rows: list[str], n_levels=256, n_traces=32) -> None:
    """The batched engine: (traces x alpha values x levels) per second."""
    n_windows = DELTA
    windows = jnp.arange(n_windows, dtype=jnp.int32)
    for policy in ("A1", "A3"):
        a = np.stack([_trace(n_levels, seed=s) for s in range(n_traces)])
        spec = _spec(a, n_levels, policy, windows=windows, key=jax.random.key(0))
        def fn():
            return provision(spec).cost

        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn())
        us = (time.perf_counter() - t0) / 3 * 1e6
        cells = n_traces * n_windows * n_levels * a.shape[1]
        rows.append(
            f"batched_sweep_{policy}_b{n_traces}_w{n_windows}_n{n_levels},{us:.1f},"
            f"decisions_per_s={cells / (us / 1e6):.3e}"
        )


def heterogeneous_throughput(rows: list[str], n_levels=256) -> None:
    """Per-level cost arrays (two server classes) vs the scalar model."""
    a = _trace(n_levels)
    beta = np.where(np.arange(n_levels) < n_levels // 2, 4.5, 1.5)
    het = CostModel(P=1.0, beta_on=beta, beta_off=beta)
    for tag, costs in (("homog", COSTS), ("hetero", het)):
        spec = _spec(a, n_levels, costs=costs)
        def fn():
            return provision(spec).cost

        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(fn())
        us = (time.perf_counter() - t0) / 5 * 1e6
        rows.append(
            f"provision_{tag}_n{n_levels},{us:.1f},"
            f"decisions_per_s={n_levels * len(a) / (us / 1e6):.3e}"
        )


def typed_fleet_throughput(rows: list[str], n_total=256) -> None:
    """Typed d=2 fleet (CostModel.from_groups) under AQ-det vs the untyped
    scalar model under delayedoff on the same demand — same per-level timer
    mechanics, so the delta is the cost of the group axis (group_cost
    reduction + routing-priority concatenation)."""
    half = n_total // 2
    typed = CostModel.from_groups(
        ServerGroup("efficient", half, P=1.0, beta_on=3.0, beta_off=3.0),
        ServerGroup("legacy", n_total - half, P=1.5, beta_on=4.5, beta_off=4.5),
    )
    a = _trace(n_total)
    for tag, costs, policy in (
        ("untyped_delayedoff", COSTS, "delayedoff"),
        ("typed2_AQ-det", typed, "AQ-det"),
    ):
        spec = ProvisionSpec(
            costs=costs,
            workload=Workload(demand=jnp.asarray(a, jnp.int32)),
            policy=PolicySpec(policy),
            n_levels=n_total,
        )
        def fn():
            return provision(spec).cost

        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(fn())
        us = (time.perf_counter() - t0) / 5 * 1e6
        rows.append(
            f"provision_{tag}_n{n_total},{us:.1f},"
            f"decisions_per_s={n_total * len(a) / (us / 1e6):.3e}"
        )


def pallas_scan_throughput(rows: list[str], sizes=(512, 4096)) -> None:
    """Fused Pallas per-level scan (interpret mode off-TPU)."""
    for n_levels in sizes:
        a = jnp.asarray(_trace(n_levels), jnp.int32)
        thresholds = jnp.full((n_levels,), float(DELTA - 3), jnp.float32)
        fn = jax.jit(
            lambda a_, m_: provision_scan(a_, m_, delta=DELTA, horizon=3)
        )
        jax.block_until_ready(fn(a, thresholds))
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn(a, thresholds))
        us = (time.perf_counter() - t0) / 3 * 1e6
        mode = "tpu" if jax.default_backend() == "tpu" else "interpret"
        rows.append(
            f"pallas_scan_{mode}_levels{n_levels},{us:.1f},"
            f"decisions_per_s={n_levels * a.shape[0] / (us / 1e6):.3e}"
        )


def _mesh_grid_spec(n_levels, n_traces, n_windows, n_stds, n_slots, mesh,
                    use_pallas=True):
    ab = np.stack([_trace(n_levels, seed=s, n_slots=n_slots)
                   for s in range(n_traces)])
    noise = PredictionNoise(
        std_frac=jnp.linspace(0.0, 0.4, n_stds), key=jax.random.key(5)
    )
    return ProvisionSpec(
        costs=COSTS,
        workload=Workload(demand=jnp.asarray(ab, jnp.int32), noise=noise),
        policy=PolicySpec("A3", windows=jnp.arange(n_windows, dtype=jnp.int32),
                          key=jax.random.key(0)),
        n_levels=n_levels,
        mesh=mesh,
        use_pallas=use_pallas,
    )


def mesh_grid_throughput(rows: list[str], n_levels=256, n_traces=8,
                         n_windows=4, n_stds=2, n_slots=N_SLOTS) -> None:
    """The sharded fleet path on the full (S, W, B) grid: fused Pallas grid
    kernel vs the sharded lax.scan body on identical cells (A3, so the wait
    tables ride along too).  Off-TPU the kernel row is interpret-mode (CPU
    emulation) — the derived decisions/s is the comparable number."""
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    mode = "tpu" if jax.default_backend() == "tpu" else "interpret"
    for tag, use_pallas in ((f"pallas_{mode}", True), ("lax_scan", False)):
        spec = _mesh_grid_spec(n_levels, n_traces, n_windows, n_stds, n_slots,
                               mesh, use_pallas=use_pallas)
        def fn():
            return provision(spec).cost

        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn())
        us = (time.perf_counter() - t0) / 3 * 1e6
        cells = n_stds * n_windows * n_traces * n_levels * n_slots
        rows.append(
            f"mesh_grid_{tag}_s{n_stds}_w{n_windows}_b{n_traces}_n{n_levels},"
            f"{us:.1f},decisions_per_s={cells / (us / 1e6):.3e}"
        )


def mesh_grid_compile_gate(rows: list[str], n_levels=48, n_slots=168) -> None:
    """One mesh-path grid cell as a smoke gate: the sharded engine body
    (`_sharded_grid`) must compile exactly once for the (S, W, B) program
    and a warmed re-run must add nothing — mirroring the `_run` guard."""
    from repro.core.jax_provision import _sharded_grid

    if not CompileWatcher(fns=(_sharded_grid,)).available:
        rows.append("mesh_grid_compiles,0.0,skipped=no_cache_size_api")
        return
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    spec = _mesh_grid_spec(n_levels, 2, 2, 2, n_slots, mesh)
    # one gated implementation (repro.lint.sanitize) instead of hand-rolled
    # cache deltas: cold run compiles exactly one program, warm run zero
    with tracer_sanitizer(fns=(_sharded_grid,), exact_compiles=1) as cold:
        jax.block_until_ready(provision(spec).cost)
    with tracer_sanitizer(fns=(_sharded_grid,), exact_compiles=0) as warm:
        jax.block_until_ready(provision(spec).cost)  # warmed re-run
    rows.append(
        f"mesh_grid_compiles,0.0,cold={cold.added};warm_added={warm.added}"
    )


def deferral_cost_vs_slack(rows: list[str], n_levels=256,
                           slacks=(0, 2, 6, 12)) -> None:
    """The defer-then-provision path: provisioning cost as a function of the
    granted queueing slack, one row per slack.  Slack is pytree data (the
    specs share ``max_slack``), so the whole curve reuses one compiled
    program; the widest-slack schedule must not cost more than rigid."""
    from repro.deferral import DeferralSpec

    a = _trace(n_levels)
    max_slack = max(slacks)
    curve = []
    for slack in slacks:
        spec = ProvisionSpec(
            costs=COSTS,
            workload=Workload(
                demand=jnp.asarray(a, jnp.int32),
                deferral=DeferralSpec(slack=slack, max_slack=max_slack),
            ),
            policy=PolicySpec("A1", window=2),
            n_levels=n_levels,
        )
        res = provision(spec)
        jax.block_until_ready(res.cost)
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(provision(spec).cost)
        us = (time.perf_counter() - t0) / 3 * 1e6
        curve.append(float(res.cost))
        rows.append(
            f"deferral_slack{slack}_n{n_levels},{us:.1f},"
            f"cost={curve[-1]:.1f};p99={int(res.p99_delay)};"
            f"miss={int(res.deadline_misses)}"
        )
    assert curve[-1] <= curve[0], (
        f"deferral bought nothing: rigid costs {curve[0]:.1f}, "
        f"slack={slacks[-1]} costs {curve[-1]:.1f}"
    )


def brick_simulator_throughput(rows: list[str]) -> None:
    rng = np.random.default_rng(1)
    tr = generate_brick_trace(rng, horizon=2000.0, rate=3.0, mean_duration=4.0)
    t0 = time.perf_counter()
    simulate(tr, A1Deterministic(alpha=0.5), COSTS)
    us = (time.perf_counter() - t0) * 1e6
    rows.append(
        f"brick_sim_{len(tr.jobs)}jobs,{us:.1f},"
        f"events_per_s={2 * len(tr.jobs) / (us / 1e6):.3e}"
    )


def jit_cache_reuse(rows: list[str]) -> None:
    """Re-pricing the fleet must hit the compiled program, not rebuild it.

    The spec's cost fields are pytree leaves; only (policy, shapes, Δ's
    static scan bound) key the jit cache.  A regression here (e.g. a field
    accidentally made a meta/static) blows the cache up per price point.
    """
    from repro.core.jax_provision import _run

    if not CompileWatcher(fns=(_run,)).available:
        rows.append("jit_cache_repricing,0.0,skipped=no_cache_size_api")
        return
    a = _trace(32, n_slots=160)
    # vary the price point but keep ceil(max Delta) fixed (it IS a shape key)
    with tracer_sanitizer(fns=(_run,), max_compiles=1) as watch:
        for beta in (2.6, 2.75, 2.9, 3.0):
            spec = _spec(a, 32, costs=CostModel(P=1.0, beta_on=beta, beta_off=beta))
            jax.block_until_ready(provision(spec).cost)
    rows.append(f"jit_cache_repricing,0.0,entries_added={watch.added}")


def telemetry_overhead(rows: list[str]) -> None:
    """The observability layer's zero-overhead contract, as a smoke gate.

    With a live telemetry registry installed, re-running the warmed default
    path must add 0 compiled programs (spans are host-side; ``record`` is a
    static jit arg that defaults off, so the default jaxpr is unchanged) —
    and turning ``record_decisions=True`` on must leave the schedule
    bit-exact (provenance is extra scan outputs, never a decision input).
    """
    from repro.core.jax_provision import _run

    a = _trace(32, n_slots=160)
    spec = _spec(a, 32)
    base = np.asarray(jax.block_until_ready(provision(spec).x))   # warm
    with telemetry_session():
        # zero-compile gate on the warmed default path, leak checking on
        with tracer_sanitizer(fns=(_run,)) as watch:
            lit = np.asarray(jax.block_until_ready(provision(spec).x))
    assert (lit == base).all(), "telemetry changed the schedule"
    rec = provision(spec, record_decisions=True)
    assert np.array_equal(np.asarray(rec.x), base), (
        "record_decisions=True changed the schedule"
    )
    assert rec.decisions is not None
    rows.append(
        f"telemetry_overhead,0.0,extra_compiles={max(watch.added, 0)};"
        "record_bitexact=1"
    )


def run(rows: list[str]) -> None:
    jax_provisioner_throughput(rows)
    batched_sweep_throughput(rows)
    heterogeneous_throughput(rows)
    typed_fleet_throughput(rows)
    pallas_scan_throughput(rows)
    mesh_grid_throughput(rows)
    deferral_cost_vs_slack(rows)
    brick_simulator_throughput(rows)
    jit_cache_reuse(rows)
    mesh_grid_compile_gate(rows)
    telemetry_overhead(rows)


def run_smoke(rows: list[str]) -> None:
    """CI leg: small shapes, every code path, plus the jit-cache assertions
    (re-pricing must not recompile; the mesh grid compiles exactly once;
    telemetry adds zero compiles to the disabled path)."""
    jax_provisioner_throughput(rows, sizes=(64,))
    batched_sweep_throughput(rows, n_levels=32, n_traces=4)
    heterogeneous_throughput(rows, n_levels=32)
    typed_fleet_throughput(rows, n_total=32)
    pallas_scan_throughput(rows, sizes=(128,))
    mesh_grid_throughput(rows, n_levels=32, n_traces=2, n_windows=2, n_stds=2,
                         n_slots=160)
    deferral_cost_vs_slack(rows, n_levels=32, slacks=(0, 4))
    jit_cache_reuse(rows)
    mesh_grid_compile_gate(rows)
    telemetry_overhead(rows)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + jit-cache assertion (CI)")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="write a jax.profiler trace of the run to DIR")
    args = ap.parse_args()
    enable_compile_cache()
    rows: list[str] = []
    with profile_to(args.profile):
        (run_smoke if args.smoke else run)(rows)
    print("name,us_per_call,derived")
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
