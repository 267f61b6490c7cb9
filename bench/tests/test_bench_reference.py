"""The benchmark's copied generator and plain reference against the
program, at sizes a CPU holds; and the control the comparison rejects."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import compare, discover, reference  # noqa: E402

MSR = discover.module("generators", "msr_diurnal")
COSTS = {"P": 1.0, "beta_on": 3.0, "beta_off": 3.0}
TRAFFIC = ROOT / "bench" / "traffic"


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_generator_equals_program(seed):
    from repro.scenarios import Scenario, generate

    n, T, mean = 3, 700, 120.0
    want = generate(Scenario("msr_diurnal", seed=seed, target_pmr=4.63,
                             mean_jobs=mean), n, T)
    for i in range(n):
        got = MSR.trace(seed, i, T, target_pmr=4.63, mean_jobs=mean)
        np.testing.assert_array_equal(got, want[i])


def _trace(seed, T=400, mean=40.0, n_levels=200):
    return np.minimum(MSR.trace(seed, 0, T, target_pmr=4.63, mean_jobs=mean), n_levels)


@pytest.mark.parametrize("policy,windows", [("A1", range(6)), ("delayedoff", (0,))])
def test_reference_equals_fluid_scan(policy, windows):
    from repro.core import PAPER_COSTS, fluid_scan

    a = _trace(11)
    ref = reference.slot_loop(a, 200, COSTS, policy=policy, windows=list(windows))
    for k, w in enumerate(windows):
        want = fluid_scan(a, policy, PAPER_COSTS, window=w)
        np.testing.assert_array_equal(ref["x"][k], want.x)
        assert ref["cost"][k] == pytest.approx(want.cost, rel=1e-12)


def test_reference_equals_engine_on_a3_given_the_draws():
    import jax
    import jax.numpy as jnp

    from repro.core import PAPER_COSTS, PolicySpec, ProvisionSpec, Workload, provision

    a, n = _trace(5, T=300, mean=30.0, n_levels=150), 150
    key = jax.random.key(1234)
    windows = [0, 2, 5]
    res = provision(ProvisionSpec(
        costs=PAPER_COSTS, workload=Workload(demand=jnp.asarray(a, jnp.int32)),
        policy=PolicySpec("A3", windows=jnp.asarray(windows, jnp.int32), key=key),
        n_levels=n))
    waits = reference.policy_rule("A3").waits(key, len(a), n, windows, 6.0)
    ref = reference.slot_loop(a, n, COSTS, policy="A3", windows=windows, waits=waits)
    nums = compare.numbers(res.x, res.level_cost, np.asarray(res.cost)[None], ref)
    assert nums["x_mismatch"] == 0
    assert nums["level_cost_max_abs_err"] == 0.0
    assert nums["cost_max_rel_err"] < 1e-6


def test_reference_without_final_off_equals_the_stepper():
    from repro.core import PAPER_COSTS
    from repro.serving import FleetProvisioner

    a, n = _trace(3, T=250, mean=30.0, n_levels=120), 120
    fleet = FleetProvisioner(PAPER_COSTS, policy="delayedoff", max_replicas=n)
    xs, lc = [], np.zeros(n)
    for t in range(len(a)):
        xs.append(fleet.advance(a[t:t + 1]))
        lc += np.asarray(fleet.last_plan.level_cost, np.float64)
    ref = reference.slot_loop(a, n, COSTS, policy="delayedoff", final_off=False)
    np.testing.assert_array_equal(np.concatenate(xs), ref["x"][0])
    np.testing.assert_array_equal(lc, ref["level_cost"][0])


@pytest.mark.parametrize("traffic", ["plan-a1", "plan-a3", "stream-a1", "live-delayedoff"])
def test_control_in_bfloat16_is_rejected(traffic):
    """The reference computed in bfloat16, put in the program's place, fails
    one of the numbers under the limits the traffic file states."""
    import jax

    tr = json.loads((TRAFFIC / f"{traffic}.json").read_text())
    policy = tr["policy"]
    windows = tr.get("windows", [0])
    a, n = _trace(21, T=500, mean=300.0, n_levels=1500), 1500
    waits = None
    rule = reference.policy_rule(policy)
    if hasattr(rule, "waits"):
        waits = rule.waits(jax.random.key(9), len(a), n, windows, 6.0)
    final_off = tr["entry"] != "live"
    kw = dict(policy=policy, windows=windows, waits=waits, final_off=final_off)
    ref = reference.slot_loop(a, n, COSTS, **kw)
    ctl = reference.slot_loop(a, n, COSTS, acc_dtype="bfloat16", **kw)
    nums = compare.numbers(ctl["x"], ctl["level_cost"],
                           ctl["cost"][None] if final_off else None, ref)
    limits = {k: v for k, v in tr["limits"].items() if k in nums}
    ok, checks = compare.verdict(nums, limits)
    assert not ok, checks
