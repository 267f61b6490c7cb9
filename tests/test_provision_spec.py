"""Property tests for the declarative ProvisionSpec API.

Covers the heterogeneous-cost reduction laws (per-level arrays that all
share ``PAPER_COSTS`` must reproduce the homogeneous ``fluid_cost`` /
``fluid_scan`` / ``schedule_cost`` numbers) and the per-level-group
decomposition of genuinely heterogeneous fleets.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # property tests skip; the rest of the file still runs
    given = None

from repro.core import (
    CostModel,
    PAPER_COSTS,
    PolicySpec,
    ProvisionSpec,
    Workload,
    fluid_cost,
    fluid_scan,
    provision,
    schedule_cost,
)
from repro.core.stepfn import StepFn


def spec_for(a, costs, policy="A1", window=0, windows=None, key=None,
             n_levels=None, predicted=None):
    return ProvisionSpec(
        costs=costs,
        workload=Workload(
            demand=jnp.asarray(a, jnp.int32),
            predicted=None if predicted is None else jnp.asarray(predicted, jnp.int32),
        ),
        policy=PolicySpec(policy, window=window, windows=windows, key=key),
        n_levels=n_levels if n_levels is not None else int(np.asarray(a).max()) + 1,
    )


def hetero_paper_costs(n_levels):
    """A per-level CostModel where every level is PAPER_COSTS."""
    return CostModel(
        P=np.full(n_levels, 1.0),
        beta_on=np.full(n_levels, 3.0),
        beta_off=np.full(n_levels, 3.0),
    )


# ---------------------------------------------------------------------------
# Heterogeneous arrays that are secretly homogeneous == the scalar numbers
# (hypothesis property tests; the reduction law itself, one fixed example
# each, also runs without hypothesis below)
# ---------------------------------------------------------------------------

def check_reduces_to_fluid_scan_a1(a, window):
    n = int(a.max()) + 1
    res = provision(spec_for(a, hetero_paper_costs(n), "A1", window=window))
    want = fluid_scan(a, "A1", PAPER_COSTS, window=window)
    np.testing.assert_array_equal(np.asarray(res.x), want.x)
    assert float(res.cost) == pytest.approx(want.cost, rel=1e-6)
    # and the schedule x(t), priced as a step function (paper eq. 5 boundary),
    # carries the same homogeneous schedule_cost
    x = np.asarray(res.x, np.float64)
    fn = StepFn(times=[float(t) for t in range(len(x))], values=list(x),
                horizon=float(len(x)))
    assert schedule_cost(fn, PAPER_COSTS, final_level=float(a[-1])) == \
        pytest.approx(float(res.cost), rel=1e-6)


def check_reduces_to_fluid_cost_offline(a):
    n = int(a.max()) + 1
    res = provision(spec_for(a, hetero_paper_costs(n), "offline"))
    want = fluid_cost(a, "offline", PAPER_COSTS).cost
    assert float(res.cost) == pytest.approx(want, rel=1e-6)


def check_matches_scalar_model_randomized(a, window, seed):
    """Same key => A2/A3 under the per-level array model are bit-identical to
    the scalar model (not just in expectation)."""
    n = int(a.max()) + 1
    key = jax.random.key(seed)
    het = provision(spec_for(a, hetero_paper_costs(n), "A3", window=window, key=key))
    homog = provision(spec_for(a, PAPER_COSTS, "A3", window=window, key=key))
    np.testing.assert_array_equal(np.asarray(het.x), np.asarray(homog.x))
    np.testing.assert_array_equal(np.asarray(het.level_cost),
                                  np.asarray(homog.level_cost))


if given is not None:
    traces = st.lists(st.integers(min_value=0, max_value=6), min_size=8,
                      max_size=40).map(lambda xs: np.asarray(xs, np.int64))

    @settings(max_examples=25, deadline=None)
    @given(a=traces, window=st.integers(min_value=0, max_value=6))
    def test_hetero_paper_costs_reduce_to_fluid_scan_a1(a, window):
        check_reduces_to_fluid_scan_a1(a, window)

    @settings(max_examples=25, deadline=None)
    @given(a=traces)
    def test_hetero_paper_costs_reduce_to_fluid_cost_offline(a):
        check_reduces_to_fluid_cost_offline(a)

    @settings(max_examples=15, deadline=None)
    @given(a=traces, window=st.integers(min_value=0, max_value=4),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_hetero_paper_costs_match_scalar_model_randomized(a, window, seed):
        check_matches_scalar_model_randomized(a, window, seed)


def test_hetero_reduction_fixed_examples():
    """The reduction laws on fixed traces (runs even without hypothesis)."""
    rng = np.random.default_rng(30)
    for window in (0, 3, 6):
        check_reduces_to_fluid_scan_a1(rng.integers(0, 7, size=40), window)
    check_reduces_to_fluid_cost_offline(rng.integers(0, 7, size=40))
    check_matches_scalar_model_randomized(rng.integers(0, 7, size=40), 2, 77)


# ---------------------------------------------------------------------------
# Genuinely heterogeneous fleets decompose per level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["A1", "A2", "offline", "delayedoff"])
def test_hetero_level_groups_match_their_homogeneous_engine(policy):
    """Levels are independent ski-rental instances: a two-class fleet's
    per-level costs must equal the matching columns of single-class runs."""
    rng = np.random.default_rng(3)
    a = rng.integers(0, 8, size=120)
    n = int(a.max()) + 1
    beta = np.where(np.arange(n) % 2 == 0, 3.0, 1.5)       # Delta 6 / 3
    key = jax.random.key(5) if policy == "A2" else None
    het = provision(spec_for(a, CostModel(P=1.0, beta_on=beta, beta_off=beta),
                             policy, window=2, key=key))
    for half in (3.0, 1.5):
        homog = provision(spec_for(
            a, CostModel(P=1.0, beta_on=np.full(n, half), beta_off=np.full(n, half)),
            policy, window=2, key=key))
        cols = np.flatnonzero(beta == half)
        np.testing.assert_allclose(
            np.asarray(het.level_cost)[cols], np.asarray(homog.level_cost)[cols],
            rtol=1e-6,
        )


def test_all_policies_run_heterogeneous_end_to_end():
    """Acceptance: one (n_levels,) CostModel through every policy, as one
    jitted program each — schedule covers demand, costs decompose."""
    rng = np.random.default_rng(4)
    a = rng.integers(0, 10, size=100)
    n = int(a.max()) + 1
    costs = CostModel(
        P=np.linspace(0.8, 1.2, n),
        beta_on=np.linspace(1.0, 4.0, n),
        beta_off=np.linspace(1.0, 4.0, n)[::-1].copy(),
    )
    for policy in ("A1", "A2", "A3", "offline", "delayedoff"):
        key = jax.random.key(9) if policy in ("A2", "A3") else None
        res = provision(spec_for(a, costs, policy, window=2, key=key))
        assert (np.asarray(res.x) >= a).all(), policy
        assert float(res.cost) == pytest.approx(float(res.level_cost.sum()), rel=1e-6)
        assert np.isfinite(np.asarray(res.level_cost)).all(), policy


def test_cost_model_validation():
    assert PAPER_COSTS.delta == 6.0 and not PAPER_COSTS.is_heterogeneous
    het = CostModel(P=np.array([1.0, 2.0]), beta_on=np.array([3.0, 4.0]),
                    beta_off=np.array([3.0, 4.0]))
    np.testing.assert_allclose(np.asarray(het.delta), [6.0, 4.0])
    assert het.is_heterogeneous and het.n_levels == 2 and het.delta_slots() == 6
    with pytest.raises(ValueError, match="pinned to 2 levels"):
        het.per_level(3)
    with pytest.raises(ValueError, match="inconsistent"):
        CostModel(P=np.ones(2), beta_on=np.ones(3)).n_levels
    # n_levels defaults to the cost model's own length
    res = provision(ProvisionSpec(
        costs=het,
        workload=Workload(demand=jnp.asarray([1, 2, 1, 0, 0, 1], jnp.int32)),
        policy=PolicySpec("A1"),
    ))
    assert res.level_cost.shape == (2,)


# ---------------------------------------------------------------------------
# Serving front-end
# ---------------------------------------------------------------------------

def test_fleet_provisioner_takes_policy_spec():
    from repro.serving import FleetProvisioner

    a = np.random.default_rng(24).integers(0, 5, size=80)
    planner = FleetProvisioner(
        PAPER_COSTS, policy=PolicySpec("A1", window=2), max_replicas=8,
    )
    res = planner.plan(a)
    want = provision(spec_for(a, PAPER_COSTS, "A1", window=2, n_levels=8))
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(want.x))
    assert float(res.cost) == pytest.approx(float(want.cost))


def test_fleet_provisioner_mesh_sweeps_and_batches():
    """The planner's mesh= path now takes batched demand and windows sweeps
    (it used to raise): same cells, level axis sharded, bit-exact."""
    from repro.serving import FleetProvisioner

    ab = np.random.default_rng(25).integers(0, 5, size=(2, 60))
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    meshed = FleetProvisioner(PAPER_COSTS, policy="A1", max_replicas=8, mesh=mesh)
    plain = FleetProvisioner(PAPER_COSTS, policy="A1", max_replicas=8)
    windows = np.arange(3)
    np.testing.assert_array_equal(
        meshed.plan_sweep(ab, windows), plain.plan_sweep(ab, windows)
    )
    np.testing.assert_allclose(
        meshed.sweep_costs(ab, windows), plain.sweep_costs(ab, windows),
        rtol=1e-6,
    )


def test_unknown_policy_value_errors_name_valid_set():
    from repro.serving import FleetProvisioner
    from repro.serving.autoscaler import ReplicaAutoscaler

    with pytest.raises(ValueError, match="valid policies"):
        FleetProvisioner(PAPER_COSTS, policy="A7")
    with pytest.raises(ValueError, match="valid policies"):
        ReplicaAutoscaler(4, PAPER_COSTS, policy="nope")


# ---------------------------------------------------------------------------
# Typed server groups: CostModel.from_groups reduction laws
# ---------------------------------------------------------------------------

from repro.core import ServerGroup  # noqa: E402


def _single_group(n):
    return CostModel.from_groups(
        ServerGroup("std", n, P=1.0, beta_on=3.0, beta_off=3.0))


def check_typed_d1_reduces_to_untyped(a, policy, window, seed):
    """One group with the untyped scalar parameters == the untyped engine,
    bit-exact (schedule, per-level cost, PRNG stream)."""
    from repro.core.jax_provision import KEYED

    n = int(a.max()) + 1
    key = jax.random.key(seed) if policy in KEYED else None
    typed = provision(spec_for(a, _single_group(n), policy, window=window,
                               key=key, n_levels=n))
    untyped = provision(spec_for(a, PAPER_COSTS, policy, window=window,
                                 key=key, n_levels=n))
    np.testing.assert_array_equal(np.asarray(typed.x), np.asarray(untyped.x))
    np.testing.assert_array_equal(np.asarray(typed.level_cost),
                                  np.asarray(untyped.level_cost))


def check_merging_identical_types_cost_invariant(a, sizes, window):
    """Splitting one server type into several identically-parameterized
    groups is pure relabeling: schedule and total cost are unchanged, and
    the split group_cost columns sum to the merged one."""
    n = sum(sizes)
    a = np.minimum(a, n)
    merged = CostModel.from_groups(
        ServerGroup("all", n, P=1.0, beta_on=3.0, beta_off=3.0))
    split = CostModel.from_groups(*(
        ServerGroup(f"g{i}", s, P=1.0, beta_on=3.0, beta_off=3.0)
        for i, s in enumerate(sizes)
    ))
    rm = provision(spec_for(a, merged, "A1", window=window, n_levels=n))
    rs = provision(spec_for(a, split, "A1", window=window, n_levels=n))
    np.testing.assert_array_equal(np.asarray(rm.x), np.asarray(rs.x))
    np.testing.assert_array_equal(np.asarray(rm.level_cost),
                                  np.asarray(rs.level_cost))
    np.testing.assert_allclose(
        np.asarray(rs.group_cost).sum(axis=-1),
        np.asarray(rm.group_cost)[..., 0], rtol=1e-6)


if given is not None:
    typed_traces = st.lists(
        st.integers(min_value=0, max_value=6), min_size=8, max_size=40
    ).map(lambda xs: np.asarray(xs, np.int64))

    @settings(max_examples=15, deadline=None)
    @given(a=typed_traces,
           policy=st.sampled_from(["A1", "A3", "offline", "delayedoff",
                                   "AQ-det", "AQ-rand"]),
           window=st.integers(min_value=0, max_value=4),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_typed_d1_reduces_to_untyped(a, policy, window, seed):
        check_typed_d1_reduces_to_untyped(a, policy, window, seed)

    @settings(max_examples=15, deadline=None)
    @given(a=typed_traces,
           sizes=st.lists(st.integers(min_value=1, max_value=4),
                          min_size=2, max_size=4),
           window=st.integers(min_value=0, max_value=3))
    def test_merging_identical_types_cost_invariant(a, sizes, window):
        check_merging_identical_types_cost_invariant(a, tuple(sizes), window)


def test_typed_reduction_fixed_examples():
    """The typed reduction laws on fixed traces (runs without hypothesis)."""
    rng = np.random.default_rng(31)
    a = rng.integers(0, 7, size=40)
    for policy in ("A1", "AQ-det", "AQ-rand"):
        check_typed_d1_reduces_to_untyped(a, policy, 2, 99)
    check_merging_identical_types_cost_invariant(a, (3, 2, 2), 1)


def test_from_groups_orders_by_energy_and_validates():
    eff = ServerGroup("eff", 2, P=1.0, beta_on=2.0, beta_off=2.0)
    leg = ServerGroup("leg", 3, P=1.5, beta_on=4.5, beta_off=4.5)
    cm = CostModel.from_groups(leg, eff)          # any order in...
    assert cm.group_names == ("eff", "leg")       # ...ascending P out
    assert cm.group_sizes == (2, 3)
    assert cm.n_groups == 2 and cm.n_levels == 5
    assert cm.group_offsets == (0, 2)
    assert cm.groups == (eff, leg)                # reconstructs the inputs
    np.testing.assert_allclose(np.asarray(cm.P), [1.0, 1.0, 1.5, 1.5, 1.5])
    with pytest.raises(ValueError, match="duplicate group names"):
        CostModel.from_groups(eff, dataclasses.replace(leg, name="eff"))
    with pytest.raises(ValueError, match="n_servers"):
        ServerGroup("empty", 0).validate()
    with pytest.raises(ValueError, match="P"):
        ServerGroup("free", 1, P=0.0).validate()


def test_group_cost_sums_to_total():
    from repro.core.jax_provision import KEYED

    cm = CostModel.from_groups(
        ServerGroup("eff", 4, P=1.0, beta_on=2.0, beta_off=2.0),
        ServerGroup("leg", 3, P=1.5, beta_on=4.5, beta_off=4.5),
    )
    a = np.random.default_rng(32).integers(0, cm.n_levels + 1, size=60)
    for policy in ("A1", "AQ-det", "AQ-rand"):
        key = jax.random.key(1) if policy in KEYED else None
        res = provision(spec_for(a, cm, policy, key=key,
                                 n_levels=cm.n_levels))
        gc = np.asarray(res.group_cost)
        assert gc.shape[-1] == 2
        np.testing.assert_allclose(gc.sum(axis=-1), np.asarray(res.cost),
                                   rtol=1e-6)
        # each column is exactly that group's slice of level_cost
        lc = np.asarray(res.level_cost)
        np.testing.assert_allclose(gc[..., 0], lc[..., :4].sum(axis=-1),
                                   rtol=1e-6)
        np.testing.assert_allclose(gc[..., 1], lc[..., 4:].sum(axis=-1),
                                   rtol=1e-6)


def test_fleet_provisioner_pins_typed_fleet_size():
    from repro.serving import FleetProvisioner

    cm = CostModel.from_groups(
        ServerGroup("eff", 6, P=1.0, beta_on=2.0, beta_off=2.0),
        ServerGroup("leg", 4, P=1.5, beta_on=4.5, beta_off=4.5),
    )
    planner = FleetProvisioner(cm, policy="AQ-det")
    assert planner.max_replicas == 10             # pinned by the model
    res = planner.plan(np.array([0, 3, 8, 8, 2, 0]))
    assert np.asarray(res.group_cost).shape == (2,)
    with pytest.raises(ValueError, match="pinned fleet size"):
        FleetProvisioner(cm, policy="A1", max_replicas=12)
    # scalar models keep the old planning default
    assert FleetProvisioner(PAPER_COSTS, policy="A1").max_replicas == 1024
