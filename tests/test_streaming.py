"""The streaming engine's contracts, end to end.

Three layers, one invariant each:

* ``provision_stream`` (batch planning on arbitrarily long traces) is
  **bit-exact** against monolithic ``provision`` at every chunk size —
  across policies, deferral slacks and typed fleets, because both routes
  run the identical per-slot update (``_slot_update``) on the identical
  CRN wait tables and only the tiling differs.
* the kernel carry (``provision_scan_stream``) chains across calls: two
  half-trace calls with the carry threaded equal one whole-trace call.
* ``FleetProvisioner.advance()`` (the O(1)-state serving stepper) is
  chunk-size **invariant** for the no-peek policies, matches ``plan()``
  when handed the whole trace at once, and replays one compiled program
  across any chunk-size mix inside a warmed pow2 bucket (the
  zero-steady-state-recompile gate).
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import (  # noqa: E402
    CostModel,
    PolicySpec,
    ProvisionSpec,
    ServerGroup,
    Workload,
    provision,
    provision_stream,
)
from repro.core.costs import PAPER_COSTS  # noqa: E402
from repro.deferral import (  # noqa: E402
    DeferralSpec,
    defer_demand,
    defer_stream,
    defer_stream_init,
    queue_scan,
    queue_stream,
    queue_stream_finalize,
    queue_stream_init,
)
from repro.serving import (  # noqa: E402
    FleetProvisioner,
    pow2_bucket,
    stepper,
)

T = 96
KEY = jax.random.PRNGKey(11)


@pytest.fixture(scope="module")
def demand():
    rng = np.random.default_rng(5)
    return jnp.asarray(rng.integers(0, 18, size=(T,)), jnp.int32)


def _assert_same(r0, r1, *, record=False):
    """Every populated ProvisionResult field bit-identical."""
    assert (np.asarray(r0.x) == np.asarray(r1.x)).all()
    for f in ("cost", "energy", "toggle_cost", "level_cost", "group_cost",
              "backlog", "max_delay", "p99_delay", "deadline_misses",
              "unserved"):
        v0, v1 = getattr(r0, f), getattr(r1, f)
        assert (v0 is None) == (v1 is None), f
        if v0 is not None:
            assert (np.asarray(v0) == np.asarray(v1)).all(), f
    if record:
        assert r1.decisions is None      # streaming records aggregates only
        for k in r0.decision_counts:
            assert (np.asarray(r0.decision_counts[k])
                    == np.asarray(r1.decision_counts[k])).all(), k


# --------------------------------------------------------------- batch route
@pytest.mark.parametrize("policy", ["A1", "A2", "A3", "delayedoff",
                                    "AQ-det", "AQ-rand"])
def test_provision_stream_bitexact_across_policies_and_slacks(policy, demand):
    """The tentpole exactness matrix: every online policy × rigid/deferred
    × chunk sizes that split waits mid-flight (t_chunk=1 splits *every*
    pending wait across a boundary; 13 is coprime to everything)."""
    for slack in (None, 3):
        d = None if slack is None else DeferralSpec(slack=slack)
        spec = ProvisionSpec(
            costs=PAPER_COSTS,
            workload=Workload(demand=demand, deferral=d),
            policy=PolicySpec(name=policy, window=2, key=KEY),
            n_levels=18,
        )
        ref = provision(spec)
        for tc in (1, 13, T):
            _assert_same(ref, provision_stream(spec, t_chunk=tc))


def test_provision_stream_typed_fleet_with_record(demand):
    costs = CostModel.from_groups(
        ServerGroup("small", 8, P=1.0, beta_on=2.0, beta_off=2.0),
        ServerGroup("big", 10, P=2.5, beta_on=4.0, beta_off=4.0),
    )
    spec = ProvisionSpec(
        costs=costs,
        workload=Workload(demand=demand),
        policy=PolicySpec(name="AQ-rand", key=KEY),
    )
    ref = provision(spec, record_decisions=True)
    got = provision_stream(spec, t_chunk=17, record_decisions=True)
    _assert_same(ref, got, record=True)
    assert got.group_cost.shape == (2,)


def test_provision_stream_mesh_route_matches(demand):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    spec = ProvisionSpec(
        costs=PAPER_COSTS,
        workload=Workload(demand=demand, deferral=DeferralSpec(slack=2)),
        policy=PolicySpec(name="A1", windows=jnp.arange(2)),
        n_levels=18,
        mesh=mesh,
    )
    _assert_same(provision(spec), provision_stream(spec, t_chunk=23))


def test_provision_stream_rejects_offline(demand):
    spec = ProvisionSpec(
        costs=PAPER_COSTS,
        workload=Workload(demand=demand),
        policy=PolicySpec(name="offline"),
        n_levels=18,
    )
    with pytest.raises(ValueError, match="online-only"):
        provision_stream(spec)


# ------------------------------------------------------------- kernel carry
def test_kernel_stream_carry_chains_across_calls(demand):
    """Two half-trace kernel calls with the carry threaded == one call."""
    from repro.kernels.provision_scan import provision_scan_stream

    n = 18
    ab = demand[None, :]
    thr = jnp.full((1, 1, n), 4.0, jnp.float32)
    z = jnp.zeros((1,), jnp.int32)
    x_full, _, _ = provision_scan_stream(
        ab, ab, thr, z, z, z, z, horizon=2, t_chunk=16, n_levels=n)
    cut = 41                            # mid-chunk AND mid-wait boundary
    xa, _, carry = provision_scan_stream(
        ab[:, :cut], ab[:, :cut], thr, z, z, z, z,
        horizon=2, t_chunk=16, n_levels=n)
    xb, _, _ = provision_scan_stream(
        ab[:, cut:], ab[:, cut:], thr, z, z, z, z,
        horizon=2, t_chunk=16, n_levels=n, carry=carry)
    got = np.concatenate([np.asarray(xa), np.asarray(xb)], axis=1)
    # the second call cannot see demand before its own range: the peek at
    # the first call's tail reads quiet, so only the carried state (not
    # the x values near the seam's peek window) must agree exactly
    assert (got == np.asarray(x_full)).all()


# (T, t_chunk, n lanes, n_levels, time-varying thresholds, record): the
# streaming kernel sums each tile's on rows once, so the cases put that
# sum at a tile's edges — one tile or several, a ragged pad tail or none,
# pad lanes of a partial block and real lanes cut off by n_levels
STREAM_TILE_CASES = {
    "one-tile": (32, 64, 18, 18, False, False),
    "three-tiles-exact-time_varying": (96, 32, 150, 140, True, False),
    "ragged-tail-record": (100, 32, 150, 140, False, True),
    "ragged-tail-time_varying-record": (77, 16, 130, 129, True, True),
    "one-tile-pad-lanes-time_varying-record": (40, 512, 200, 170, True, True),
}


@pytest.mark.parametrize("case", sorted(STREAM_TILE_CASES))
def test_kernel_stream_tile_sums_match_grid_on_matrix(case):
    """provision_scan_stream's x and per-lane totals equal the grid
    kernel's on-matrix summed over lanes and over slots, bit for bit."""
    from repro.kernels.provision_scan import (
        provision_scan_grid,
        provision_scan_stream,
    )

    T_, t_chunk, n, n_levels, time_varying, record = STREAM_TILE_CASES[case]
    horizon = 3
    rng = np.random.default_rng(T_ * 1009 + n)
    traces = jnp.asarray(rng.integers(0, n + 8, size=(2, T_)), jnp.int32)
    predicted = jnp.asarray(rng.integers(0, n + 8, size=(2, T_)), jnp.int32)
    thr = jnp.asarray(rng.uniform(0.0, 6.0, size=(2, T_ if time_varying else 1, n)),
                      jnp.float32)
    hor = jnp.asarray(rng.uniform(0.0, horizon + 0.5, size=(2, n)), jnp.float32)
    cells = (jnp.asarray([0, 1], jnp.int32), jnp.asarray([1, 0], jnp.int32),
             jnp.asarray([0, 1], jnp.int32), jnp.asarray([1, 0], jnp.int32))

    x, acc, _ = provision_scan_stream(
        traces, predicted, thr, *cells, horizon=horizon, t_chunk=t_chunk,
        n_levels=n_levels, level_horizon=hor, record=record)
    grid = provision_scan_grid(
        traces, predicted, thr, *cells, delta=horizon, horizon=horizon,
        level_horizon=hor, record=record)
    ons, counts = grid if record else (grid, None)
    ons = np.asarray(ons)[:, :, :n_levels].astype(np.int32)   # (G, T, lanes)

    assert np.asarray(x).dtype == np.int32
    assert (np.asarray(x) == ons.sum(-1)).all()
    # totals over the kernel's lanes: the n_levels cut-off lanes read 0;
    # edges against the virtual x(0) = a(0) boundary (no toggle at t = 0)
    want = {
        "run": ons.sum(1),
        "up": (ons[:, 1:] & (1 - ons[:, :-1])).sum(1),
        "down": (ons[:, :-1] & (1 - ons[:, 1:])).sum(1),
    }
    if record:
        names = ("demand_rise", "wait_expired", "peek_fired", "toggle_off")
        want.update({k: np.asarray(counts)[:, i, :n_levels]
                     for i, k in enumerate(names)})
    assert sorted(acc) == sorted(want)
    for k, v in want.items():
        got = np.asarray(acc[k])
        assert got.shape == (2, n), k
        assert (got[:, :n_levels] == v).all(), k
        assert (got[:, n_levels:] == 0).all(), k


def test_interpret_env_override_and_telemetry_gauge(monkeypatch):
    from repro.kernels.provision_scan import _resolve_interpret
    from repro.obs.telemetry import telemetry_session

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with telemetry_session() as tel:
        assert _resolve_interpret(None) is True
        assert tel.gauge_value("kernels/pallas_interpret") == 1.0
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    with telemetry_session() as tel:
        assert _resolve_interpret(None) is False
        assert tel.gauge_value("kernels/pallas_interpret") == 0.0
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "sideways")
    with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
        _resolve_interpret(None)
    # an explicit argument wins over the env var
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert _resolve_interpret(True) is True


# ----------------------------------------------------------- deferral carry
def test_defer_stream_chunk_invariant_and_causal(demand):
    a = demand
    for K in (1, 4):
        full, _ = defer_stream(a, defer_stream_init(K), slack=K)
        st = defer_stream_init(K)
        outs = []
        for lo, hi in ((0, 1), (1, 40), (40, T)):
            o, st = defer_stream(a[lo:hi], st, slack=K)
            outs.append(np.asarray(o))
        assert (np.concatenate(outs) == np.asarray(full)).all()
        A, S = np.cumsum(np.asarray(a)), np.cumsum(np.asarray(full))
        assert (S <= A).all()                  # causal: never serves early
        assert (S[K:] >= A[:T - K]).all()      # every deadline met
    # the documented divergence from the batch rule: OA water-filling is
    # anticipative (it sees the t=2 burst at t=0), the stream rule is not
    burst = jnp.asarray([3, 0, 300], jnp.int32)
    oa = np.asarray(defer_demand(burst, 2))
    causal, _ = defer_stream(burst, defer_stream_init(2), slack=2)
    assert oa[0] == 3 and int(causal[0]) < 3


def test_queue_stream_matches_queue_scan_chunked(demand):
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(0, 18, size=(T,)), jnp.int32)
    for rule in ("EDF", "SPT"):
        K = 5
        ref = queue_scan(demand, x, K, rule=rule, max_slack=K)
        st = queue_stream_init(K)
        outs = []
        for lo, hi in ((0, 7), (7, 55), (55, T)):
            o, st = queue_stream(demand[lo:hi], x[lo:hi], st,
                                 rule=rule, max_slack=K)
            outs.append(np.asarray(o))
        assert (np.concatenate(outs) == np.asarray(ref["backlog"])).all()
        fin = queue_stream_finalize(st, max_slack=K)
        for k in ("served_by_age", "deadline_misses", "unserved",
                  "max_delay", "p99_delay"):
            assert (np.asarray(fin[k]) == np.asarray(ref[k])).all(), (rule, k)


# ----------------------------------------------------------------- stepper
def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (1, 7, 8, 9, 64, 65, 1000)] == \
        [8, 8, 8, 16, 64, 128, 1024]


def test_advance_one_shot_matches_plan(demand):
    a = np.asarray(demand)
    for policy, w in (("A1", 0), ("A1", 3), ("delayedoff", 0), ("AQ-det", 0)):
        prov = FleetProvisioner(PAPER_COSTS, policy=policy, window=w,
                                max_replicas=18)
        got = prov.advance(a)
        ref = FleetProvisioner(PAPER_COSTS, policy=policy, window=w,
                               max_replicas=18).plan(a)
        assert (got == np.asarray(ref.x)).all(), (policy, w)


def test_advance_chunk_invariant_no_peek_splits_pending_waits(demand):
    """delayedoff holds each idle level for Δ = 6 slots, so slot-by-slot
    advancing splits every pending wait across a chunk boundary — the
    carried (r, on, wait) state must make the schedule identical."""
    a = np.asarray(demand)
    for policy in ("delayedoff", "AQ-rand"):
        key = KEY if policy == "AQ-rand" else None
        full = FleetProvisioner(PAPER_COSTS, policy=policy, max_replicas=18,
                                key=key).advance(a)
        for sizes in ((1,) * T, (5, 3, 88), (41, 55)):
            prov = FleetProvisioner(PAPER_COSTS, policy=policy,
                                    max_replicas=18, key=key)
            pos, outs = 0, []
            for s in sizes:
                outs.append(prov.advance(a[pos:pos + s]))
                pos += s
            assert (np.concatenate(outs) == full).all(), (policy, sizes)


def test_advance_chunk_cost_plus_final_off_matches_plan(demand):
    """The stepper's chunk-local cost omits only the forced end-of-trace
    off toggles (the trace has not ended); adding them reproduces plan()'s
    total exactly."""
    a = np.asarray(demand)
    prov = FleetProvisioner(PAPER_COSTS, policy="A1", max_replicas=18)
    prov.advance(a)
    ref = FleetProvisioner(PAPER_COSTS, policy="A1", max_replicas=18).plan(a)
    final_off = int((np.asarray(prov.state.on)
                     & ~(a[-1] > np.arange(18))).sum())
    got = float(prov.last_plan.cost) + PAPER_COSTS.beta_off * final_off
    assert got == pytest.approx(float(ref.cost))


_LEVEL_RNG = np.random.default_rng(17)
#: planners whose ticks the cost and transfer tests step: the paper's
#: scalar model, per-level (N,) cost arrays, a two-type fleet, a keyed
#: policy, and deferral.  Costs are whole numbers, so every float32 sum
#: below is exact in any order.
TICK_CASES = {
    "paper": dict(costs=PAPER_COSTS, policy="delayedoff"),
    "per-level": dict(costs=CostModel(
        P=_LEVEL_RNG.integers(1, 3, 18).astype(np.float32),
        beta_on=_LEVEL_RNG.integers(1, 4, 18).astype(np.float32),
        beta_off=_LEVEL_RNG.integers(1, 4, 18).astype(np.float32),
    ), policy="A1", window=2),
    "typed": dict(costs=CostModel.from_groups(
        ServerGroup("old", 10, P=2.0, beta_on=4.0, beta_off=2.0),
        ServerGroup("new", 8, P=1.0, beta_on=3.0, beta_off=3.0),
    ), policy="AQ-det"),
    "A3-key": dict(costs=PAPER_COSTS, policy="A3", window=1, key=KEY),
    "deferral": dict(costs=PAPER_COSTS, policy="A1",
                     deferral=DeferralSpec(slack=3)),
}


def _tick_planner(case):
    kw = dict(TICK_CASES[case])
    return FleetProvisioner(kw.pop("costs"), max_replicas=18, **kw)


@pytest.mark.parametrize("case", sorted(TICK_CASES))
def test_advance_costs_equal_the_eager_formulas(demand, case):
    """Every ``last_plan`` cost field of a tick equals the per-level
    formulas applied with numpy to ``stepper_chunk``'s run/up/down totals
    on the same chunk and carry; ``level_cost`` bit for bit, and ``x`` is
    the scan's schedule."""
    prov = _tick_planner(case)
    c, pol, spec = prov.costs, prov.policy, prov.deferral
    delta = jnp.asarray(c.delta, jnp.float32)
    ref = stepper.stepper_init(18, delta, policy=pol.name, window=pol.window,
                               deferral=spec)
    P, bon, boff = (np.broadcast_to(np.asarray(f, np.float32), (18,))
                    for f in (c.P, c.beta_on, c.beta_off))
    a, pos = np.asarray(demand), 0
    for n in (5, 11, 1, 8):
        t_pad = pow2_bucket(n)
        a_pad = jnp.asarray(np.pad(a[pos:pos + n], (0, t_pad - n)), jnp.int32)
        defer = None
        if spec is not None:
            a_pad, defer = defer_stream(
                a_pad, ref.defer, slack=spec.bound(), cap=spec.cap,
                valid=jnp.arange(t_pad) < n)
        x, (r, on, wait), tot = stepper.stepper_chunk(
            a_pad, jnp.int32(n), jnp.int32(pos), pol.key, ref.r, ref.on,
            ref.wait, delta, policy=pol.name, n_levels=18,
            max_h=c.delta_slots(), window=pol.window, t_pad=t_pad)
        ref = stepper.StepperState(t=pos + n, r=r, on=on, wait=wait,
                                   defer=defer)
        run, up, down = (np.asarray(tot[k]).astype(np.float32)
                         for k in ("run", "up", "down"))
        level_cost = P * run + bon * up + boff * down

        got_x = prov.advance(a[pos:pos + n])
        plan = prov.last_plan
        assert (got_x == np.asarray(x)[:n]).all(), (case, pos)
        assert (np.asarray(plan.x) == got_x).all(), (case, pos)
        lc = np.asarray(plan.level_cost)
        assert lc.dtype == np.float32
        assert (lc.view(np.uint32) == level_cost.view(np.uint32)).all(), (case, pos)
        assert float(plan.cost) == level_cost.sum(dtype=np.float64)
        assert float(plan.energy) == (P * run).sum(dtype=np.float64)
        assert float(plan.toggle_cost) == \
            (bon * up + boff * down).sum(dtype=np.float64)
        if c.group_sizes is None:
            assert plan.group_cost is None
        else:
            bounds = np.cumsum((0,) + c.group_sizes)
            want = [level_cost[lo:hi].sum() for lo, hi in zip(bounds, bounds[1:])]
            assert np.asarray(plan.group_cost).tolist() == want
        pos += n


@pytest.mark.parametrize("case", ["paper", "per-level", "typed", "A3-key"])
def test_warmed_advance_makes_no_implicit_upload(demand, case):
    """Once a tick has run, the next one uploads only through its explicit
    ``device_put``s: no numpy argument to a jitted program, no
    ``jnp.asarray`` of host data, no ``jnp.int32`` scalars.  (Deferral
    keeps its eager ``defer_stream``/``queue_stream`` dispatches.)"""
    a = np.asarray(demand)
    prov, ref = _tick_planner(case), _tick_planner(case)
    prov.advance(a[:3])
    ref.advance(a[:3])
    with jax.transfer_guard_host_to_device("disallow"):
        x = prov.advance(a[3:8])
    assert (x == ref.advance(a[3:8])).all()


def test_advance_zero_recompiles_in_warmed_bucket(demand, tracer_sanitizer):
    """The satellite gate: after one warmup call, three *different* chunk
    sizes inside the same pow2 bucket add zero jit traces."""
    a = np.asarray(demand)
    prov = FleetProvisioner(PAPER_COSTS, policy="A1", max_replicas=18)
    prov.advance(a[:8])                             # warmup owns bucket 8
    with tracer_sanitizer(fns=(stepper.stepper_chunk, stepper.stepper_tick)):
        prov.advance(a[8:13])                       # 5 -> bucket 8
        prov.advance(a[13:16])                      # 3 -> bucket 8
        prov.advance(a[16:24])                      # 8 -> bucket 8
    assert prov.metrics.plans == 4


def test_advance_deferral_mid_flight_backlog_chunk_invariant():
    """A burst pushes work into the queue; chunk boundaries cut straight
    through the live backlog and the schedule must not notice."""
    rng = np.random.default_rng(13)
    a = rng.integers(0, 6, size=(T,))
    a[30:34] = 40                                   # burst >> fleet absorbs
    spec = DeferralSpec(slack=4)
    full_p = FleetProvisioner(PAPER_COSTS, policy="A1", max_replicas=24,
                              deferral=spec)
    full = full_p.advance(a)
    assert int(np.asarray(full_p.last_plan.backlog).max()) > 0
    for sizes in ((31, 2, 63), (1,) * T):
        prov = FleetProvisioner(PAPER_COSTS, policy="A1", max_replicas=24,
                                deferral=spec)
        pos, outs = 0, []
        for s in sizes:
            outs.append(prov.advance(a[pos:pos + s]))
            pos += s
        assert (np.concatenate(outs) == full).all(), sizes
        assert int(prov.last_plan.deadline_misses) == 0
        assert (np.asarray(prov.last_plan.backlog)
                == np.asarray(full_p.last_plan.backlog)[pos - sizes[-1]:pos]).all()


def test_advance_rejections_and_reset(demand):
    a = np.asarray(demand)
    with pytest.raises(ValueError, match="hindsight"):
        FleetProvisioner(PAPER_COSTS, policy="offline",
                         max_replicas=18).advance(a[:8])
    with pytest.raises(ValueError, match="scalar slack"):
        FleetProvisioner(
            PAPER_COSTS, policy="A1", max_replicas=64,
            deferral=DeferralSpec(slack=np.ones(T, np.int32), max_slack=4),
        ).advance(a[:8])
    prov = FleetProvisioner(PAPER_COSTS, policy="A1", max_replicas=18)
    first = prov.advance(a[:16])
    prov.reset()
    assert prov.state is None and prov._history.size == 0
    assert (prov.advance(a[:16]) == first).all()    # fresh trace, same plan
