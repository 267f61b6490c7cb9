"""Declarative JAX provisioning engine == numpy reference engines."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    A2Randomized,
    A3Randomized,
    A1Deterministic,
    CostModel,
    PolicySpec,
    ProvisionSpec,
    Workload,
    brick_trace_from_fluid,
    fluid_cost,
    fluid_scan,
    msr_like_trace,
    on_matrix_cost,
    provision,
    simulate,
)
from repro.core.jax_provision import (
    _level_schedule,
    _uniforms,
    _waits_from_uniforms,
)
from repro.core.traces import with_prediction_error
from repro.kernels.provision_scan import provision_scan

COSTS = CostModel(P=1.0, beta_on=3.0, beta_off=3.0)
B = int(COSTS.delta)


def run(a, *, policy="A1", window=0, windows=None, predicted=None, key=None,
        costs=COSTS, n_levels=None, mesh=None, use_pallas=True):
    return provision(ProvisionSpec(
        costs=costs,
        workload=Workload(
            demand=jnp.asarray(a, jnp.int32),
            predicted=None if predicted is None else jnp.asarray(predicted, jnp.int32),
        ),
        policy=PolicySpec(policy, window=window, windows=windows, key=key),
        n_levels=n_levels if n_levels is not None else int(np.asarray(a).max()) + 1,
        mesh=mesh,
        use_pallas=use_pallas,
    ))


@pytest.mark.parametrize("window", [0, 1, 3, 5, 8])
@pytest.mark.parametrize("seed", range(4))
def test_a1_jax_matches_numpy_scan(window, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 8, size=60)
    want = fluid_scan(a, "A1", COSTS, window=window)
    got = run(a, window=window, policy="A1")
    np.testing.assert_array_equal(np.asarray(got.x), want.x)


@pytest.mark.parametrize("seed", range(4))
def test_offline_jax_matches_optimal_cost(seed):
    rng = np.random.default_rng(seed + 100)
    a = rng.integers(0, 6, size=50)
    res = run(a, policy="offline")
    want = fluid_cost(a, "offline", COSTS).cost
    assert float(res.cost) == pytest.approx(want, rel=1e-6)


def test_a1_jax_cost_matches_numpy_cost():
    a = msr_like_trace(np.random.default_rng(1), n_slots=300, mean_jobs=15.0)
    for w in (0, 2, 5):
        res = run(a, window=w, policy="A1")
        want = fluid_scan(a, "A1", COSTS, window=w).cost
        assert float(res.cost) == pytest.approx(want, rel=1e-6)
        # result invariants: cost decomposes over levels and into components
        assert float(res.level_cost.sum()) == pytest.approx(float(res.cost))
        assert float(res.energy + res.toggle_cost) == pytest.approx(float(res.cost))


# ---------------------------------------------------------------------------
# Batched multi-policy engine: A2/A3, batching, sweep, Pallas scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["A2", "A3"])
@pytest.mark.parametrize("window", [0, 2, 4])
def test_randomized_jax_matches_fluid_scan_in_expectation(policy, window):
    """Jitted A2/A3 mean cost over keys == numpy slot-scan mean over seeds."""
    rng = np.random.default_rng(0)
    a = rng.integers(0, 6, size=60)
    runs = 300
    ab = np.tile(a, (runs, 1))
    costs = run(ab, policy=policy, windows=jnp.array([window]),
                key=jax.random.key(7), n_levels=int(a.max()) + 1).cost
    jit_mean = float(jnp.mean(costs[0]))
    ref_mean = np.mean([
        fluid_scan(a, policy, COSTS, window=window,
                   rng=np.random.default_rng(r)).cost
        for r in range(runs)
    ])
    assert jit_mean == pytest.approx(ref_mean, rel=0.02)


@pytest.mark.parametrize("policy,cls", [("A2", A2Randomized), ("A3", A3Randomized)])
@pytest.mark.parametrize("window", [0, 2, 4])
def test_randomized_jax_matches_event_simulator_in_expectation(policy, cls, window):
    """Jitted A2/A3 match core/online.py brick-simulator costs in expectation.

    The fluid (slot) and brick (continuous) models differ by a fixed
    discretization factor; deterministic A1 measures it exactly, and the
    randomized policies must sit at the same factor within sampling noise.
    """
    rng = np.random.default_rng(1)
    a = rng.integers(0, 6, size=80)
    alpha = min(1.0, (window + 1) / COSTS.delta)
    tr = brick_trace_from_fluid(a)

    calibration = (
        fluid_scan(a, "A1", COSTS, window=window).cost
        / simulate(tr, A1Deterministic(alpha=alpha), COSTS).cost
    )
    runs = 300
    ab = np.tile(a, (runs, 1))
    costs = run(ab, policy=policy, windows=jnp.array([window]),
                key=jax.random.key(3), n_levels=int(a.max()) + 1).cost
    jit_mean = float(jnp.mean(costs[0]))
    brick_mean = np.mean([
        simulate(tr, cls(alpha=alpha), COSTS, rng=np.random.default_rng(r)).cost
        for r in range(150)
    ])
    assert jit_mean / brick_mean == pytest.approx(calibration, rel=0.05)


@pytest.mark.parametrize("policy", ["A1", "A3", "delayedoff", "offline"])
def test_batched_matches_unbatched(policy):
    """(B, T) demand == stacking per-trace (T,) schedules (split keys)."""
    rng = np.random.default_rng(2)
    n_traces = 5
    ab = rng.integers(0, 7, size=(n_traces, 60))
    key = jax.random.key(11)
    kw = dict(n_levels=7, window=2, policy=policy)
    xb = run(ab, **kw, key=key if policy in ("A2", "A3") else None).x
    keys = jax.random.split(key, n_traces)
    for i in range(n_traces):
        ki = keys[i] if policy in ("A2", "A3") else None
        xi = run(ab[i], **kw, key=ki).x
        np.testing.assert_array_equal(np.asarray(xb[i]), np.asarray(xi))


def test_sweep_matches_individual_windows():
    """One windows= sweep == W separate single-window A1 programs."""
    a = msr_like_trace(np.random.default_rng(5), n_slots=200, mean_jobs=10.0)
    xs = run(a, windows=jnp.arange(B), policy="A1").x
    for w in range(B):
        want = run(a, window=w, policy="A1").x
        np.testing.assert_array_equal(np.asarray(xs[w]), np.asarray(want))


def test_sweep_matches_single_schedule_randomized():
    """For a (T,) trace, sweep and single-window calls share the key stream."""
    rng = np.random.default_rng(14)
    a = rng.integers(0, 6, size=60)
    key = jax.random.key(21)
    xs = run(a, windows=jnp.arange(3), policy="A3", key=key, n_levels=6).x
    for w in range(3):
        want = run(a, window=w, policy="A3", key=key, n_levels=6).x
        np.testing.assert_array_equal(np.asarray(xs[w]), np.asarray(want))


def test_randomized_requires_key():
    a = np.zeros((10,), np.int64)
    with pytest.raises(ValueError, match="randomized"):
        run(a, policy="A2", n_levels=4)


def test_unknown_policy_names_valid_set():
    a = np.zeros((10,), np.int64)
    with pytest.raises(ValueError, match="A1.*A2.*A3.*offline.*delayedoff"):
        run(a, policy="A9", n_levels=4)
    with pytest.raises(ValueError, match="valid policies"):
        _level_schedule(jnp.zeros((10,), jnp.int32), 4, B, 0, "a1")


def test_delayedoff_jax_matches_numpy_scan():
    rng = np.random.default_rng(6)
    a = rng.integers(0, 8, size=80)
    want = fluid_scan(a, "delayedoff", COSTS)
    got = run(a, policy="delayedoff")
    np.testing.assert_array_equal(np.asarray(got.x), want.x)


@pytest.mark.parametrize("window", [0, 2, 5])
def test_pallas_scan_matches_scan_engine(window):
    """Fused Pallas kernel (interpret mode) == lax.scan engine, exactly."""
    rng = np.random.default_rng(8)
    a = rng.integers(0, 9, size=90)
    n = int(a.max()) + 1
    aj = jnp.asarray(a, jnp.int32)
    horizon = int(min(window + 1, B))
    # deterministic thresholds (A1)
    m = max(0.0, B - window - 1)
    want = _level_schedule(aj, n, B, window, "A1")
    got = provision_scan(aj, jnp.full((n,), m, jnp.float32), delta=B,
                         horizon=horizon)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # sampled wait table (A2) — same table through both paths
    key = jax.random.key(9)
    u0, u = _uniforms(key, len(a), n)
    waits = _waits_from_uniforms("A2", u0, u, window, B)
    want = _level_schedule(aj, n, B, window, "A2", key=key)
    got = provision_scan(aj, waits, delta=B, horizon=horizon)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_scan_distinct_prediction_trace():
    """The kernel's peek reads the scalar-prefetched predicted trace, not a."""
    rng = np.random.default_rng(15)
    a = rng.integers(0, 8, size=100)
    pred = with_prediction_error(a, rng, 0.4)
    assert not np.array_equal(pred, a)
    n = int(max(a.max(), pred.max())) + 1
    w = 2
    aj = jnp.asarray(a, jnp.int32)
    pj = jnp.asarray(pred, jnp.int32)
    want = _level_schedule(aj, n, B, w, "A1", predicted=pj)
    got = provision_scan(aj, jnp.full((n,), float(B - w - 1), jnp.float32),
                         delta=B, horizon=w + 1, predicted=pj)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and erroneous predictions must actually change the schedule somewhere
    exact = provision_scan(aj, jnp.full((n,), float(B - w - 1), jnp.float32),
                           delta=B, horizon=w + 1)
    assert not np.array_equal(np.asarray(got), np.asarray(exact))


@pytest.mark.parametrize("window", [0, 1, 2, 3])
def test_pallas_scan_fractional_delta_peek_boundary(window):
    """Per-level Δ_l ∈ {2.5, 3.0}: the kernel's fractional peek mask
    (``float(h) < Δ_l``) must agree with the engine at the boundary where
    the unrolled slot index straddles a non-integer horizon (h = 2 is
    peeked under Δ=2.5 iff the horizon row says 2.0 < 2.5, but h = 3 is
    not), and the A1 thresholds clip at zero (Δ − w − 1 < 0)."""
    rng = np.random.default_rng(21)
    a = rng.integers(0, 9, size=80)
    n = int(a.max()) + 1
    delta_lv = np.where(np.arange(n) % 2 == 0, 2.5, 3.0)
    max_h = 3                                    # ceil(max Δ)
    aj = jnp.asarray(a, jnp.int32)
    want = _level_schedule(aj, n, delta_lv, window, "A1")
    thr = jnp.asarray(np.maximum(0.0, delta_lv - window - 1), jnp.float32)
    lh = jnp.asarray(np.minimum(window + 1.0, delta_lv), jnp.float32)
    got = provision_scan(aj, thr, delta=max_h, horizon=min(window + 1, max_h),
                         level_horizon=lh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("policy", ["A2", "A3"])
def test_pallas_scan_wait_consumed_at_first_idle_slot(policy):
    """A trace that goes idle at the first scan step: the kernel's
    first-newly-idle wait consumption (``idle & (r == 0.0)``) must pick up
    the slot-1 table row exactly like the engine — including levels that
    were never busy (they never consume a draw) and a level re-idling
    after a later busy burst (fresh draw, not the stale one)."""
    a = np.zeros(40, np.int64)
    a[0] = 5                   # levels 0-4 on at t=0, all newly idle at t=1
    a[20:23] = 3               # levels 0-2 busy again, re-idle at t=23
    n = 6                      # level 5 never turns on at all
    window = 1
    key = jax.random.key(33)
    aj = jnp.asarray(a, jnp.int32)
    u0, u = _uniforms(key, len(a), n)
    waits = _waits_from_uniforms(policy, u0, u, window, B)
    want = _level_schedule(aj, n, B, window, policy, key=key)
    got = provision_scan(aj, waits, delta=B, horizon=window + 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the schedule must actually exercise the idle path (turn-offs happen)
    assert np.asarray(want)[:, :5].sum() < 5 * len(a)


def test_pallas_scan_heterogeneous_per_level_horizon():
    """Per-level Δ: thresholds AND peek reach vary per level, masked in-kernel."""
    rng = np.random.default_rng(16)
    a = rng.integers(0, 9, size=90)
    n = int(a.max()) + 1
    w = 2
    delta_lv = np.where(np.arange(n) % 2 == 0, 6.0, 3.0)
    aj = jnp.asarray(a, jnp.int32)
    want = _level_schedule(aj, n, delta_lv, w, "A1")
    thr = jnp.asarray(np.maximum(0.0, delta_lv - w - 1), jnp.float32)
    lh = jnp.asarray(np.minimum(w + 1.0, delta_lv), jnp.float32)
    got = provision_scan(aj, thr, delta=6, horizon=w + 1, level_horizon=lh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sharded_randomized_matches_unsharded():
    """Sharded Pallas path (1 device => same key stream) == jitted engine."""
    rng = np.random.default_rng(10)
    a = rng.integers(0, 6, size=70)
    key = jax.random.key(12)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    if len(jax.devices()) > 1:
        pytest.skip("key-stream equality only holds unsharded")
    got = run(a, window=2, policy="A3", key=key, n_levels=6, mesh=mesh)
    want = run(a, window=2, policy="A3", key=key, n_levels=6)
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
    np.testing.assert_allclose(np.asarray(got.level_cost),
                               np.asarray(want.level_cost), rtol=1e-6)


def test_sharded_path_consumes_predicted_trace():
    """The shard_map/Pallas fleet path peeks an erroneous prediction trace
    and matches the lax.scan engine bit-exactly (the old sharded API
    silently dropped ``predicted``)."""
    rng = np.random.default_rng(11)
    a = msr_like_trace(rng, n_slots=150, mean_jobs=12.0)
    pred = with_prediction_error(a, rng, 0.3)
    n = int(max(a.max(), pred.max())) + 1
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    for use_pallas in (True, False):
        got = run(a, window=2, predicted=pred, n_levels=n, mesh=mesh,
                  use_pallas=use_pallas)
        want = run(a, window=2, predicted=pred, n_levels=n)
        np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
    # the noisy prediction must differ from the exact-prediction schedule
    exact = run(a, window=2, n_levels=n)
    assert not np.array_equal(np.asarray(got.x), np.asarray(exact.x))


def test_batched_cost_matches_per_trace_cost():
    rng = np.random.default_rng(13)
    ab = rng.integers(0, 6, size=(4, 50))
    ons = np.stack([
        np.asarray(_level_schedule(jnp.asarray(ai, jnp.int32), 6, B, 1, "A1"))
        for ai in ab
    ])
    batched = on_matrix_cost(jnp.asarray(ab), jnp.asarray(ons), COSTS)
    for i in range(4):
        single = on_matrix_cost(jnp.asarray(ab[i]), jnp.asarray(ons[i]), COSTS)
        assert float(batched[i]) == pytest.approx(float(single))


def test_sharded_fleet_matches_single_device():
    """shard_map level-sharded provisioning == single-device result."""
    a = msr_like_trace(np.random.default_rng(2), n_slots=200, mean_jobs=20.0)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    got = run(a, window=2, mesh=mesh)
    want = run(a, window=2, policy="A1")
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
    np.testing.assert_allclose(np.asarray(got.level_cost),
                               np.asarray(want.level_cost), rtol=1e-6)


def test_sharded_multi_device_padding_masked():
    """4 forced host devices, n_levels not divisible: the padded phantom
    levels must not inflate x(t) when demand exceeds the fleet cap."""
    import os
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
)
import numpy as np, jax, jax.numpy as jnp
from repro.core import PAPER_COSTS, PolicySpec, ProvisionSpec, Workload, provision
assert len(jax.devices()) == 4, jax.devices()
rng = np.random.default_rng(0)
a = rng.integers(0, 11, size=80)          # peak demand above the fleet cap
n = 6                                      # n_padded = 8 -> 2 phantom levels
mesh = jax.make_mesh((4,), ("data",))
def spec(mesh=None):
    return ProvisionSpec(
        costs=PAPER_COSTS,
        workload=Workload(demand=jnp.asarray(a, jnp.int32)),
        policy=PolicySpec("A1", window=2), n_levels=n, mesh=mesh)
got = provision(spec(mesh))
want = provision(spec())
np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
np.testing.assert_allclose(np.asarray(got.level_cost),
                           np.asarray(want.level_cost), rtol=1e-6)
# randomized: uniforms drawn at n_levels, so the (trace, key) -> schedule
# contract must hold across mesh sizes too
def spec3(mesh=None):
    return ProvisionSpec(
        costs=PAPER_COSTS,
        workload=Workload(demand=jnp.asarray(a, jnp.int32)),
        policy=PolicySpec("A3", window=2, key=jax.random.key(12)),
        n_levels=n, mesh=mesh)
np.testing.assert_array_equal(np.asarray(provision(spec3(mesh)).x),
                              np.asarray(provision(spec3()).x))
# the full (S, W, B) grid across 4 real shards: the psum / tiled
# all_gather / per-shard base offsets must reassemble the level axis in
# order (a 1-device mesh makes every collective a no-op, so only this
# forced-multi-device run exercises them)
from repro.core import PredictionNoise
ab = rng.integers(0, 11, size=(2, 60))
def spec_grid(mesh=None, use_pallas=True):
    return ProvisionSpec(
        costs=PAPER_COSTS,
        workload=Workload(
            demand=jnp.asarray(ab, jnp.int32),
            noise=PredictionNoise(jnp.asarray([0.0, 0.3]), jax.random.key(7))),
        policy=PolicySpec("A3", windows=jnp.arange(3), key=jax.random.key(8)),
        n_levels=n, mesh=mesh, use_pallas=use_pallas)
want = provision(spec_grid())
for use_pallas in (True, False):
    got = provision(spec_grid(mesh, use_pallas))
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
    np.testing.assert_allclose(np.asarray(got.level_cost),
                               np.asarray(want.level_cost), rtol=1e-6)
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=dict(os.environ), timeout=300)
    assert r.returncode == 0 and "OK" in r.stdout, (r.stdout, r.stderr[-2000:])


def test_mesh_rejects_offline():
    a = np.ones((30,), np.int64)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    with pytest.raises(ValueError, match="online policies"):
        run(a, policy="offline", mesh=mesh, n_levels=4)


def test_n_levels_inference_under_jit_raises_clearly():
    """Default n_levels needs concrete demand; under an outer jit/vmap the
    old ``int(ab.max())`` exploded with an opaque ConcretizationTypeError —
    now a ValueError names the fix (regression)."""
    from repro.core import PAPER_COSTS

    a = jnp.asarray(np.ones(20), jnp.int32)

    def cost(ai, n_levels=None):
        return provision(ProvisionSpec(
            costs=PAPER_COSTS,
            workload=Workload(demand=ai),
            policy=PolicySpec("A1", window=1),
            n_levels=n_levels,
        )).cost

    with pytest.raises(ValueError, match="n_levels"):
        jax.jit(cost)(a)
    with pytest.raises(ValueError, match="jit/vmap"):
        jax.vmap(lambda ai: cost(ai))(a[None])
    # explicit n_levels works under jit; a level-pinned CostModel also works
    assert float(jax.jit(lambda ai: cost(ai, n_levels=2))(a)) == \
        pytest.approx(float(cost(a, n_levels=2)))


# ---------------------------------------------------------------------------
# Batched (S, W, B) axes through the mesh/Pallas fleet path
# ---------------------------------------------------------------------------

MESH_GRID_CASES = [
    # policy, batched, windows, noise-swept
    ("A1", True, True, False),
    ("A1", True, False, True),
    ("A2", True, True, True),
    ("A3", True, True, True),
    ("A3", False, True, False),
    ("A3", False, False, True),
    ("delayedoff", True, True, True),
]


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("policy,batched,sweep_w,sweep_s", MESH_GRID_CASES)
def test_mesh_grid_matches_unsharded(policy, batched, sweep_w, sweep_s,
                                     use_pallas):
    """The sharded fleet path accepts the full (S, W, B) grid and is
    bit-exact against the lax.scan programs on every axis combination —
    kernel and sharded-lax.scan bodies alike (common random numbers)."""
    from repro.core import PredictionNoise

    rng = np.random.default_rng(42)
    a = rng.integers(0, 7, size=(3, 50) if batched else (50,))
    n = int(a.max()) + 1
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    kw = dict(
        policy=policy,
        n_levels=n,
        key=jax.random.key(5) if policy in ("A2", "A3") else None,
        windows=jnp.arange(3) if sweep_w else None,
        window=2,
    )
    noise = (
        PredictionNoise(jnp.asarray([0.0, 0.3]), jax.random.key(6))
        if sweep_s else None
    )

    def go(**extra):
        return provision(ProvisionSpec(
            costs=COSTS,
            workload=Workload(demand=jnp.asarray(a, jnp.int32), noise=noise),
            policy=PolicySpec(kw["policy"], window=kw["window"],
                              windows=kw["windows"], key=kw["key"]),
            n_levels=n,
            **extra,
        ))

    got = go(mesh=mesh, use_pallas=use_pallas)
    want = go()
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
    np.testing.assert_allclose(np.asarray(got.level_cost),
                               np.asarray(want.level_cost), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got.cost), np.asarray(want.cost),
                               rtol=1e-6)


def test_mesh_path_works_under_outer_jit():
    """provision(mesh=...) traced by an outer jit must still run (the
    static peek unroll falls back to the Δ bound when the windows values
    are tracers) and agree with the eager meshed and unmeshed results.
    ``windows`` enters as a jit *argument* so its values really are
    tracers inside the trace — pinning the fallback branch, not just the
    constant-folded path."""
    rng = np.random.default_rng(44)
    a = rng.integers(0, 6, size=50)
    n = int(a.max()) + 1
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))

    def cost(ai, ws, m=None):
        return provision(ProvisionSpec(
            costs=COSTS,
            workload=Workload(demand=ai),
            policy=PolicySpec("A1", windows=ws),
            n_levels=n,
            mesh=m,
        )).cost

    aj = jnp.asarray(a, jnp.int32)
    ws = jnp.arange(3)
    got = jax.jit(lambda ai, w: cost(ai, w, mesh))(aj, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(cost(aj, ws, mesh)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(cost(aj, ws)),
                               rtol=1e-6)


def test_mesh_grid_heterogeneous_fractional_delta():
    """(S, W, B) mesh grid with per-level Δ ∈ {2.5, 6.0} — fractional peek
    reach and per-level thresholds through the batched kernel."""
    from repro.core import PredictionNoise

    rng = np.random.default_rng(43)
    ab = rng.integers(0, 6, size=(2, 40))
    n = int(ab.max()) + 1
    half = np.where(np.arange(n) % 2 == 0, 3.0, 1.25)      # Δ 6.0 / 2.5
    costs = CostModel(P=1.0, beta_on=half, beta_off=half)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    spec = ProvisionSpec(
        costs=costs,
        workload=Workload(
            demand=jnp.asarray(ab, jnp.int32),
            noise=PredictionNoise(jnp.asarray([0.0, 0.25]), jax.random.key(1)),
        ),
        policy=PolicySpec("A1", windows=jnp.arange(3)),
        n_levels=n,
    )
    want = provision(spec)
    got = provision(dataclasses.replace(spec, mesh=mesh))
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
    np.testing.assert_allclose(np.asarray(got.level_cost),
                               np.asarray(want.level_cost), rtol=1e-6)


def test_prediction_noise_workload():
    """Workload.noise synthesizes the predicted trace (Sec. V-C) on device."""
    from repro.core import PredictionNoise

    rng = np.random.default_rng(17)
    a = msr_like_trace(rng, n_slots=120, mean_jobs=10.0)
    noise = PredictionNoise(std_frac=0.5, key=jax.random.key(2))
    spec = ProvisionSpec(
        costs=COSTS,
        workload=Workload(demand=jnp.asarray(a, jnp.int32), noise=noise),
        policy=PolicySpec("A1", window=3),
        n_levels=int(a.max()) + 1,
    )
    res = provision(spec)
    # identical to passing the synthesized trace explicitly
    pred = noise.apply(jnp.asarray(a, jnp.int32))
    want = run(a, window=3, predicted=pred)
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(want.x))
    # and different from the exact-prediction schedule
    exact = run(a, window=3)
    assert not np.array_equal(np.asarray(res.x), np.asarray(exact.x))
    with pytest.raises(ValueError, match="not both"):
        provision(dataclasses.replace(
            spec, workload=Workload(jnp.asarray(a, jnp.int32), predicted=pred,
                                    noise=noise)))
    # batched noise reduces to its unbatched rows (key split per trace,
    # same convention as PolicySpec.key)
    ab = np.stack([a, a[::-1].copy()])
    bres = provision(dataclasses.replace(
        spec, workload=Workload(jnp.asarray(ab, jnp.int32), noise=noise)))
    keys = jax.random.split(noise.key, 2)
    for i in range(2):
        ri = provision(dataclasses.replace(
            spec, workload=Workload(jnp.asarray(ab[i], jnp.int32),
                                    noise=PredictionNoise(0.5, keys[i]))))
        np.testing.assert_array_equal(np.asarray(bres.x[i]), np.asarray(ri.x))


def test_predicted_shape_must_match_demand():
    ab = np.random.default_rng(18).integers(0, 5, size=(4, 25))
    with pytest.raises(ValueError, match="must match demand shape"):
        run(ab, predicted=ab.T, n_levels=5)       # same size, wrong shape
    with pytest.raises(ValueError, match="must match demand shape"):
        run(ab[0], predicted=ab[0][:-1], n_levels=5)


# ---------------------------------------------------------------------------
# Typed server groups: AQ policies + the group-aligned kernel layout
# ---------------------------------------------------------------------------

from repro.core import ServerGroup  # noqa: E402

TYPED = CostModel.from_groups(
    ServerGroup("efficient", 5, P=1.0, beta_on=2.0, beta_off=2.0),
    ServerGroup("legacy", 4, P=1.5, beta_on=4.5, beta_off=4.5),
)


def _typed_trace(seed=11, n_slots=96):
    rng = np.random.default_rng(seed)
    return np.minimum(msr_like_trace(rng, mean_jobs=4.0, n_slots=n_slots),
                      TYPED.n_levels)


def test_aq_det_is_delayedoff_on_a_single_type():
    """d = 1 AQ-det IS the paper's delayed-off: same break-even timer Δ, no
    peek — the schedules must be bit-identical."""
    a = np.random.default_rng(7).integers(0, 9, size=120)
    got, want = run(a, policy="AQ-det"), run(a, policy="delayedoff")
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
    np.testing.assert_array_equal(np.asarray(got.level_cost),
                                  np.asarray(want.level_cost))


@pytest.mark.parametrize("policy", ["A1", "A3", "offline", "delayedoff",
                                    "AQ-det", "AQ-rand"])
def test_single_group_typed_model_bit_exact_vs_untyped(policy):
    """The d=1 regression gate: one ServerGroup carrying the untyped scalar
    parameters must reproduce the untyped engine bit-exactly (same PRNG
    stream included) on the lax.scan path."""
    from repro.core.jax_provision import KEYED

    a = np.random.default_rng(8).integers(0, 9, size=120)
    n = int(a.max()) + 1
    typed = CostModel.from_groups(
        ServerGroup("std", n, P=1.0, beta_on=3.0, beta_off=3.0))
    key = jax.random.key(5) if policy in KEYED else None
    got = run(a, policy=policy, key=key, costs=typed, n_levels=n)
    want = run(a, policy=policy, key=key, n_levels=n)
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
    np.testing.assert_array_equal(np.asarray(got.level_cost),
                                  np.asarray(want.level_cost))
    # the typed result additionally carries the (single) group reduction
    np.testing.assert_allclose(np.asarray(got.group_cost)[..., 0],
                               np.asarray(got.cost), rtol=1e-6)
    assert want.group_cost is None


@pytest.mark.parametrize("policy", ["A1", "AQ-det", "AQ-rand"])
def test_single_group_typed_model_bit_exact_on_fleet_path(policy):
    """Same d=1 gate through the sharded Pallas fleet path (the group-
    aligned routed kernel layout vs the contiguous one)."""
    from repro.core.jax_provision import KEYED

    a = np.random.default_rng(9).integers(0, 9, size=96)
    n = int(a.max()) + 1
    typed = CostModel.from_groups(
        ServerGroup("std", n, P=1.0, beta_on=3.0, beta_off=3.0))
    key = jax.random.key(6) if policy in KEYED else None
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    got = run(a, policy=policy, window=2, key=key, costs=typed, n_levels=n,
              mesh=mesh)
    want = run(a, policy=policy, window=2, key=key, n_levels=n, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(want.x))
    np.testing.assert_array_equal(np.asarray(got.level_cost),
                                  np.asarray(want.level_cost))


@pytest.mark.parametrize("policy", ["A1", "AQ-det", "AQ-rand"])
@pytest.mark.parametrize("use_pallas", [True, False])
def test_multi_type_fleet_path_matches_unsharded(policy, use_pallas):
    """Multi-type parity: the sharded fleet path (Pallas routed kernel and
    the sharded lax.scan body) must reproduce the unsharded engine on a
    genuinely heterogeneous d=2 fleet, group_cost included."""
    from repro.core.jax_provision import KEYED

    ab = np.stack([_typed_trace(s) for s in (11, 12)])
    key = jax.random.key(3) if policy in KEYED else None
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    plain = run(ab, policy=policy, window=2, key=key, costs=TYPED,
                n_levels=TYPED.n_levels)
    fleet = run(ab, policy=policy, window=2, key=key, costs=TYPED,
                n_levels=TYPED.n_levels, mesh=mesh, use_pallas=use_pallas)
    np.testing.assert_array_equal(np.asarray(plain.x), np.asarray(fleet.x))
    np.testing.assert_array_equal(np.asarray(plain.level_cost),
                                  np.asarray(fleet.level_cost))
    np.testing.assert_allclose(np.asarray(plain.group_cost),
                               np.asarray(fleet.group_cost), rtol=1e-6)
    # group_cost is the exact per-group reduction of level_cost
    np.testing.assert_allclose(
        np.asarray(plain.group_cost).sum(axis=-1), np.asarray(plain.cost),
        rtol=1e-6)


def test_aq_rand_respects_per_type_bound_in_expectation():
    """AQ-rand's per-type guarantee: over PRNG replicas, each type's mean
    cost stays within e/(e−1) of that type's offline share (plus sampling
    slack) — the randomized full-span waits are doing their job."""
    import math

    a = _typed_trace(21, n_slots=288)
    opt = run(a, policy="offline", costs=TYPED, n_levels=TYPED.n_levels)
    opt_group = np.asarray(opt.group_cost, np.float64)
    reps = [
        np.asarray(run(a, policy="AQ-rand", key=jax.random.key(s),
                       costs=TYPED, n_levels=TYPED.n_levels).group_cost,
                   np.float64)
        for s in range(12)
    ]
    mean_group = np.mean(reps, axis=0)
    bound = math.e / (math.e - 1.0)
    assert (mean_group <= opt_group * (bound + 0.15)).all(), (
        f"per-type mean cost {mean_group} vs offline {opt_group}")


# ---------------------------------------------------------------------------
# One input through every planning route
# ---------------------------------------------------------------------------

#: two server types, the second with a fractional critical interval
#: (Δ = 4 and Δ = 5.25 / 1.5 = 3.5), so waits, peek reach and the group-
#: aligned mesh layout all differ per type
TWO_TYPES = CostModel.from_groups(
    ServerGroup("efficient", 5, P=1.0, beta_on=2.0, beta_off=2.0),
    ServerGroup("legacy", 4, P=1.5, beta_on=2.5, beta_off=2.75),
)


@pytest.mark.parametrize("policy", ["A1", "A2", "A3", "delayedoff", "AQ-det",
                                    "AQ-rand"])
def test_every_planning_route_agrees(policy):
    """provision and provision_stream, each unsharded and on a one-device
    mesh through both the Pallas kernel and the lax.scan body, give the
    same x, level_cost and group_cost bit for bit: the shared wait draws,
    scan sweep and grid layout feed every route the same decisions."""
    from repro.core import provision_stream
    from repro.core.jax_provision import KEYED

    ab = np.stack([_typed_trace(s, n_slots=48) for s in (31, 32)])
    mesh = jax.make_mesh((1,), ("data",))
    spec = ProvisionSpec(
        costs=TWO_TYPES,
        workload=Workload(demand=jnp.asarray(ab, jnp.int32)),
        policy=PolicySpec(policy, windows=jnp.asarray([0, 1, 3], jnp.int32),
                          key=jax.random.key(4) if policy in KEYED else None),
        n_levels=TWO_TYPES.n_levels,
    )
    routes = {"provision": provision(spec)}
    for use_pallas in (True, False):
        routes[f"provision mesh use_pallas={use_pallas}"] = provision(
            dataclasses.replace(spec, mesh=mesh, use_pallas=use_pallas))
    routes["provision_stream"] = provision_stream(spec, t_chunk=16)
    for use_pallas in (True, False):
        routes[f"provision_stream mesh use_pallas={use_pallas}"] = provision_stream(
            dataclasses.replace(spec, mesh=mesh, use_pallas=use_pallas),
            t_chunk=16)
    want = routes.pop("provision")
    assert want.x.shape == (3, 2, 48) and want.group_cost.shape == (3, 2, 2)
    for name, got in routes.items():
        for field in ("x", "level_cost", "group_cost"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
                err_msg=f"{name}: {field}")
