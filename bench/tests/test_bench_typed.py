"""The typed fleet's plain reference (``bench/typed_reference.py``) against
the program at sizes a CPU holds, against the untyped reference at d = 1,
and the controls and planted faults its comparison has to reject; the new
cells through the harness at tiny sizes."""
import json
import pathlib
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import compare, discover, harness, reference, typed_reference  # noqa: E402

MSR = discover.module("generators", "msr_diurnal")
AQ_RAND = discover.module("policies", "AQ-rand")
TYPED = discover.module("entries", "typed_stream")
LIMITS = json.loads((ROOT / "bench/traffic/stream-aqrand.json").read_text())["limits"]
#: four types, none a multiple of 8, the largest above 128 (128-lane groups)
SMALL = [(141, 1.0), (37, 1.25), (21, 1.5), (13, 2.0)]


def _groups(sizes):
    return [{"name": f"type{k + 1}", "n_servers": n, "P": p, "beta_on": 3.0, "beta_off": 3.0}
            for k, (n, p) in enumerate(sizes)]


def _trace(seed, T, mean, n_levels):
    return np.minimum(MSR.trace(seed, 0, T, target_pmr=4.63, mean_jobs=mean), n_levels)


def _numbers(x, lc, cost, gc, ref):
    nums = compare.numbers(x, lc, np.asarray(cost).reshape(-1, 1), ref)
    nums["group_cost_max_rel_err"] = TYPED.group_cost_max_rel_err(gc, ref)
    return nums


@pytest.mark.parametrize("route", ["stream-mesh", "scan"])
@pytest.mark.parametrize("policy", ["AQ-rand", "AQ-det"])
def test_typed_reference_equals_the_program(policy, route):
    import jax
    import jax.numpy as jnp

    from repro.core import (CostModel, PolicySpec, ProvisionSpec, ServerGroup, Workload,
                            provision, provision_stream)

    groups = _groups(SMALL)
    n = sum(g["n_servers"] for g in groups)
    a = _trace(17, 200, 60.0, n)
    key = jax.random.key(2**31 + 3) if policy == "AQ-rand" else None
    costs = CostModel.from_groups(*(ServerGroup(**g) for g in groups))
    mesh = jax.make_mesh((1,), ("data",)) if route == "stream-mesh" else None
    spec = ProvisionSpec(costs=costs, workload=Workload(demand=jnp.asarray(a, jnp.int32)),
                         policy=PolicySpec(policy, key=key), n_levels=n, mesh=mesh)
    res = (provision_stream if mesh is not None else provision)(spec)
    waits = None
    if key is not None:
        delta = typed_reference.per_level(groups)[3]
        waits = AQ_RAND.waits(key, len(a), n, [0], delta)[0]
    ref = typed_reference.slot_loop(a, groups, waits=waits)
    nums = _numbers(res.x, res.level_cost, res.cost, res.group_cost, ref)
    assert nums["x_mismatch"] == 0, nums
    assert nums["level_cost_max_abs_err"] == 0.0, nums
    assert nums["cost_max_rel_err"] < 1e-6 and nums["group_cost_max_rel_err"] < 1e-6, nums


@pytest.mark.parametrize("policy,untyped", [("AQ-rand", "AQ-rand"), ("AQ-det", "delayedoff")])
def test_one_type_equals_the_untyped_reference(policy, untyped):
    import jax

    groups = _groups([(150, 1.0)])
    a = _trace(5, 300, 30.0, 150)
    waits = None
    if policy == "AQ-rand":
        waits = AQ_RAND.waits(jax.random.key(7), len(a), 150, [0], 6.0)
    costs = {"P": 1.0, "beta_on": 3.0, "beta_off": 3.0}
    want = reference.slot_loop(a, 150, costs, policy=untyped, waits=waits)
    got = typed_reference.slot_loop(a, groups, waits=None if waits is None else waits[0])
    np.testing.assert_array_equal(got["x"], want["x"])
    np.testing.assert_array_equal(got["level_cost"], want["level_cost"])
    np.testing.assert_array_equal(got["cost"], want["cost"])
    np.testing.assert_array_equal(got["group_cost"][:, 0], want["cost"])


def _control_case():
    groups = _groups([(701, 1.0), (403, 1.25), (197, 1.5), (163, 2.0)])
    n = sum(g["n_servers"] for g in groups)
    a = _trace(21, 500, 300.0, n)
    return a, groups, _waits(a, groups, typed_reference.per_level(groups)[3])


def _waits(a, groups, delta):
    import jax

    n = sum(g["n_servers"] for g in groups)
    return AQ_RAND.waits(jax.random.key(9), len(a), n, [0], delta)[0]


def test_control_in_bfloat16_is_rejected():
    """The typed reference computed in bfloat16, put in the program's place,
    fails one of the numbers under the cell's limits."""
    a, groups, waits = _control_case()
    ref = typed_reference.slot_loop(a, groups, waits=waits)
    ctl = typed_reference.slot_loop(a, groups, waits=waits, acc_dtype="bfloat16")
    nums = _numbers(ctl["x"], ctl["level_cost"], ctl["cost"], ctl["group_cost"], ref)
    ok, checks = compare.verdict(nums, LIMITS)
    assert not ok, checks


def _permute_group_cost(res):
    return dict(res, group_cost=res["group_cost"][:, ::-1])


@pytest.mark.parametrize("fault", ["delta", "group_cost"])
def test_planted_fault_in_the_reference_is_rejected(fault):
    """Waits drawn with type 2's Delta at 4 slots, not 4.8 (the costs
    unchanged), or the per-type rows of ``group_cost`` permuted."""
    a, groups, waits = _control_case()
    ref = typed_reference.slot_loop(a, groups, waits=waits)
    if fault == "delta":
        delta = typed_reference.per_level(groups)[3].copy()
        delta[701:701 + 403] = 4.0
        bad = typed_reference.slot_loop(a, groups, waits=_waits(a, groups, delta))
    else:
        bad = _permute_group_cost(ref)
    nums = _numbers(bad["x"], bad["level_cost"], bad["cost"], bad["group_cost"], ref)
    ok, checks = compare.verdict(nums, LIMITS)
    assert not ok, checks


# ---------------------------------------------------------------------------
# The new cells through the harness, tiny
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The benchmark's data with msr-dc cut to 128 levels and 300 slots and
    msr-dc-typed4 to the four small types and 200 slots."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "data"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    path = root / "bench/configs/msr-dc.json"
    cfg = json.loads(path.read_text())
    cfg["fleet"]["n_levels"], cfg["n_slots"] = 128, 300
    cfg["demand"]["mean_jobs"] = 25.0
    path.write_text(json.dumps(cfg))
    path = root / "bench/configs/msr-dc-typed4.json"
    cfg = json.loads(path.read_text())
    cfg["groups"], cfg["n_slots"] = _groups(SMALL), 200
    cfg["demand"]["mean_jobs"] = 45.0
    path.write_text(json.dumps(cfg))
    return root


def run(root, workload, trace=False, hook=None):
    return harness.run_cell(root, workload, 2**31 + 99, 0.3, trace,
                            t_start=time.perf_counter(), require_accelerator=False,
                            cache=False, hook=hook)


TYPED_METRICS = {"plan_group_cost_ms", "layout_pad_pct", "wait_table_mb"}


def test_tiny_typed_run_is_correct_and_reads_its_gauges(tiny_root):
    """A traced tiny run reads correct, and the gauge and span readers find
    the layout (640 lanes, 428 of them pad), the (1, 200, 640) float32
    wait table and the group_cost span."""
    res = run(tiny_root, "msr-dc-typed4.stream-aqrand", trace=True)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == set(LIMITS)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert TYPED_METRICS <= set(got), got
    assert got["layout_pad_pct"] == pytest.approx(100.0 * 428 / 640)
    assert got["wait_table_mb"] == 200 * 640 * 4 / 1e6
    assert got["plan_group_cost_ms"] > 0


def test_tiny_grid_run_is_correct(tiny_root):
    res = run(tiny_root, "msr-dc.plan-grid")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"plan_decisions_per_s", "setup_s"}


def _alter_cost_model(cl):
    """The program given one type's Delta altered: type 2's toggle costs
    2.5, not 3 (Delta 4 slots, not 4.8)."""
    from repro.core import CostModel, ServerGroup

    groups = [dict(g, beta_on=2.5, beta_off=2.5) if k == 1 else g
              for k, g in enumerate(cl.groups)]
    costs = CostModel.from_groups(*(ServerGroup(**g) for g in groups))
    cl.specs = [type(s)(**{**s.__dict__, "costs": costs}) for s in cl.specs]


def _permute_rows(cl):
    """The served ``group_cost`` with its per-type rows permuted."""
    entry = cl.entry

    def permuted(spec):
        res = entry(spec)
        return type(res)(**{**res.__dict__, "group_cost": res.group_cost[..., ::-1]})

    cl.entry = permuted


@pytest.mark.parametrize("fault", [_alter_cost_model, _permute_rows])
def test_planted_fault_in_the_typed_cell_reads_incorrect(tiny_root, fault):
    res = run(tiny_root, "msr-dc-typed4.stream-aqrand", hook=fault)
    assert not res["correct"], res["checks"]
