"""The observability layer: telemetry registry, compile accounting, and
per-slot decision provenance.

The load-bearing contracts:

- the default registry is a no-op (``NullTelemetry``) and the disabled
  path is bit-exact AND compile-count-identical to a build without the
  layer — observability must cost nothing when off;
- ``record_decisions=True`` emits per-slot per-level reason codes whose
  toggle bits reconstruct the schedule *exactly* (provenance is derived
  from the same scan that decided, never re-simulated);
- the mesh/Pallas fleet route reports the same aggregate decision counts
  as the lax.scan route on identical specs.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CostModel,
    PolicySpec,
    ProvisionSpec,
    ServerGroup,
    Workload,
    msr_like_trace,
    provision,
)
from repro.obs import (
    COUNT_ORDER,
    DEMAND_RISE,
    CompileWatcher,
    NullTelemetry,
    Telemetry,
    decision_counts,
    engine_cache_size,
    explain_slot,
    get_telemetry,
    profile_to,
    reconstruct_schedule,
    set_telemetry,
    telemetry_session,
    toggles_from_decisions,
)

COSTS = CostModel(P=1.0, beta_on=3.0, beta_off=3.0)


def _spec(a, n_levels, policy="A1", mesh=None, use_pallas=True, key=None,
          windows=None):
    return ProvisionSpec(
        costs=COSTS,
        workload=Workload(demand=jnp.asarray(a, jnp.int32)),
        policy=PolicySpec(policy, window=2, windows=windows, key=key),
        n_levels=n_levels,
        mesh=mesh,
        use_pallas=use_pallas,
    )


# ---------------------------------------------------------------- telemetry


def test_counters_gauges_histograms():
    tel = Telemetry()
    tel.count("requests")
    tel.count("requests", 2.0)
    tel.gauge("depth", 7.0)
    tel.gauge("depth", 3.0)
    for v in (1.0, 2.0, 3.0, 4.0):
        tel.observe("lat", v)
    assert tel.counter_value("requests") == 3.0
    assert tel.gauge_value("depth") == 3.0
    assert tel.samples("lat") == [1.0, 2.0, 3.0, 4.0]
    assert tel.quantile("lat", 0.0) == 1.0
    assert tel.quantile("lat", 1.0) == 4.0


def test_labels_key_separate_series():
    tel = Telemetry()
    tel.count("toggles", 1, policy="A1")
    tel.count("toggles", 5, policy="A3")
    assert tel.counter_value("toggles", policy="A1") == 1
    assert tel.counter_value("toggles", policy="A3") == 5


def test_span_emits_chrome_event_and_histogram():
    tel = Telemetry()
    with tel.span("work", policy="A1"):
        pass
    trace = tel.chrome_trace()
    events = trace["traceEvents"]
    assert any(e["name"] == "work" and e["ph"] == "X" for e in events)
    assert len(tel.samples("span/work")) == 1


def test_trace_and_metrics_files_round_trip(tmp_path):
    tel = Telemetry()
    with tel.span("phase"):
        tel.count("n")
    tp = tel.write_chrome_trace(tmp_path / "t.json")
    mp = tel.write_metrics_jsonl(tmp_path / "m.jsonl")
    loaded = json.loads(tp.read_text())
    assert isinstance(loaded["traceEvents"], list) and loaded["traceEvents"]
    records = [json.loads(line) for line in mp.read_text().splitlines()]
    assert any(r["name"] == "n" for r in records)


def test_default_registry_is_disabled_noop():
    tel = get_telemetry()
    assert isinstance(tel, NullTelemetry) and not tel.enabled
    tel.count("x")
    tel.observe("x", 1.0)
    with tel.span("x"):
        pass
    assert tel.chrome_trace()["traceEvents"] == []


def test_telemetry_session_installs_and_restores():
    before = get_telemetry()
    with telemetry_session() as tel:
        assert get_telemetry() is tel and tel.enabled
        tel.count("inside")
    assert get_telemetry() is before
    assert tel.counter_value("inside") == 1


def test_set_telemetry_returns_previous():
    tel = Telemetry()
    old = set_telemetry(tel)
    try:
        assert get_telemetry() is tel
    finally:
        set_telemetry(old)


# ---------------------------------------------------------- CompileWatcher


def test_compile_watcher_counts_cold_then_warm():
    f = jax.jit(lambda x: x * 2)
    watch = CompileWatcher(fns=(f,))
    if not watch.available:
        pytest.skip("private jit _cache_size API unavailable")
    with watch:
        jax.block_until_ready(f(jnp.ones(4)))
    assert watch.added == 1
    with watch:
        jax.block_until_ready(f(jnp.ones(4)))
    assert watch.added == 0


def test_compile_watcher_degrades_to_minus_one():
    watch = CompileWatcher(fns=(lambda x: x,))    # not a jitted fn
    assert not watch.available
    assert watch.snapshot() == -1
    with watch:
        pass
    assert watch.added == -1


def test_compile_watcher_feeds_telemetry():
    f = jax.jit(lambda x: x + 1)
    tel = Telemetry()
    watch = CompileWatcher(fns=(f,), telemetry=tel)
    if not watch.available:
        pytest.skip("private jit _cache_size API unavailable")
    with watch:
        jax.block_until_ready(f(jnp.ones(3)))
    assert tel.counter_value("jax/compiles") == 1


def test_engine_cache_size_returns_int():
    assert isinstance(engine_cache_size(), int)


@pytest.mark.parametrize("mesh", [False, True], ids=["scan", "mesh"])
def test_default_watcher_counts_streaming_compiles(mesh):
    """The default watch set covers provision_stream on both routes, so
    recompile gates see the streaming bodies too."""
    from repro.core import provision_stream

    watch = CompileWatcher()
    if not watch.available:
        pytest.skip("private jit _cache_size API unavailable")
    # a trace length no other test streams: the first call must compile
    a = np.random.default_rng(7).integers(0, 9, size=53)
    spec = _spec(a, 12, policy="delayedoff",
                 mesh=jax.make_mesh((1,), ("data",)) if mesh else None)
    with watch:
        jax.block_until_ready(provision_stream(spec, t_chunk=11).x)
    assert watch.added >= 1
    with watch:
        jax.block_until_ready(provision_stream(spec, t_chunk=11).x)
    assert watch.added == 0


def test_profile_to_none_is_noop():
    with profile_to(None):
        pass


# ------------------------------------------------------ decision provenance


@pytest.mark.parametrize("policy, key", [
    ("A1", None),
    ("A2", jax.random.key(3)),
    ("delayedoff", None),
])
def test_reason_codes_reconstruct_schedule_exactly(policy, key):
    """The provenance property: cumulative toggle bits == the schedule.

    ``x(t) = x(0) + cumsum(rises - offs)`` must hold *exactly* — the codes
    come out of the same scan that decided, so any divergence is a bug in
    the recording, not noise."""
    n = 48
    a = msr_like_trace(np.random.default_rng(7), n_slots=200, mean_jobs=12.0)
    res = provision(_spec(a, n, policy, key=key), record_decisions=True)
    dec = np.asarray(res.decisions)
    assert dec.shape == (200, n) and dec.dtype == np.uint8
    x = np.asarray(res.x)
    x0 = min(int(a[0]), n)
    np.testing.assert_array_equal(reconstruct_schedule(dec, x0), x)
    # and the engine's on-device counts agree with the numpy reduction
    want = decision_counts(dec)
    assert set(res.decision_counts) == set(COUNT_ORDER)
    for name in COUNT_ORDER:
        np.testing.assert_array_equal(
            np.asarray(res.decision_counts[name]), want[name]
        )


def test_reconstruction_holds_on_batched_sweep():
    n = 32
    traces = np.stack([
        msr_like_trace(np.random.default_rng(s), n_slots=96, mean_jobs=8.0)
        for s in range(3)
    ])
    spec = _spec(traces, n, "A3", key=jax.random.key(0),
                 windows=jnp.arange(2, dtype=jnp.int32))
    res = provision(spec, record_decisions=True)
    dec = np.asarray(res.decisions)
    x = np.asarray(res.x)
    assert dec.shape == x.shape + (n,)
    for w in range(dec.shape[0]):
        for b in range(dec.shape[1]):
            x0 = min(int(traces[b, 0]), n)
            np.testing.assert_array_equal(
                reconstruct_schedule(dec[w, b], x0), x[w, b]
            )


def test_toggle_bits_match_schedule_diffs():
    n = 40
    a = msr_like_trace(np.random.default_rng(1), n_slots=150, mean_jobs=10.0)
    res = provision(_spec(a, n), record_decisions=True)
    rises, offs = toggles_from_decisions(np.asarray(res.decisions))
    dx = np.diff(np.asarray(res.x), prepend=min(int(a[0]), n))
    np.testing.assert_array_equal(rises - offs, dx)


def test_explain_slot_names_reasons():
    a = msr_like_trace(np.random.default_rng(2), n_slots=100, mean_jobs=8.0)
    res = provision(_spec(a, 32), record_decisions=True)
    dec = np.asarray(res.decisions)
    t = int(np.argmax((dec & DEMAND_RISE).any(axis=1)))
    reasons = explain_slot(dec, t)
    assert any("demand-rise" in line for line in reasons)


def test_record_default_off_and_offline_rejects_record():
    a = msr_like_trace(np.random.default_rng(3), n_slots=80, mean_jobs=6.0)
    res = provision(_spec(a, 16))
    assert res.decisions is None and res.decision_counts is None
    off = ProvisionSpec(
        costs=COSTS,
        workload=Workload(demand=jnp.asarray(a, jnp.int32)),
        policy=PolicySpec("offline"),
        n_levels=16,
    )
    with pytest.raises(ValueError, match="record"):
        provision(off, record_decisions=True)


def test_disabled_path_bit_exact_and_no_extra_compiles():
    """The zero-overhead contract: record off (the default) produces the
    same schedule AND hits the same compiled program as before the layer
    existed — even with a live telemetry registry installed."""
    from repro.core.jax_provision import _run

    a = msr_like_trace(np.random.default_rng(4), n_slots=120, mean_jobs=8.0)
    spec = _spec(a, 24)
    base = np.asarray(jax.block_until_ready(provision(spec).x))     # warm
    watch = CompileWatcher(fns=(_run,))
    with telemetry_session():
        with watch:
            lit = np.asarray(jax.block_until_ready(provision(spec).x))
    np.testing.assert_array_equal(lit, base)
    if watch.available:
        assert watch.added == 0
    # record=True must not change the decisions either, just annotate them
    rec = provision(spec, record_decisions=True)
    np.testing.assert_array_equal(np.asarray(rec.x), base)


def test_mesh_route_counts_match_scan_route():
    """The fleet path records aggregate counters only — but they must agree
    with the per-slot codes the scan route emits on the same spec."""
    n = 16
    traces = np.stack([
        msr_like_trace(np.random.default_rng(s), n_slots=96, mean_jobs=6.0)
        for s in range(2)
    ])
    plain = provision(_spec(traces, n), record_decisions=True)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    for use_pallas in (False, True):
        meshed = provision(
            _spec(traces, n, mesh=mesh, use_pallas=use_pallas),
            record_decisions=True,
        )
        np.testing.assert_array_equal(np.asarray(meshed.x),
                                      np.asarray(plain.x))
        for name in COUNT_ORDER:
            np.testing.assert_array_equal(
                np.asarray(meshed.decision_counts[name]),
                np.asarray(plain.decision_counts[name]),
                err_msg=f"{name} (use_pallas={use_pallas})",
            )


def test_typed_fleet_records_decisions():
    groups = (
        ServerGroup("fast", 8, P=1.0, beta_on=3.0, beta_off=3.0),
        ServerGroup("slow", 8, P=1.5, beta_on=4.5, beta_off=4.5),
    )
    a = msr_like_trace(np.random.default_rng(9), n_slots=96, mean_jobs=6.0)
    spec = ProvisionSpec(
        costs=CostModel.from_groups(*groups),
        workload=Workload(demand=jnp.asarray(a, jnp.int32)),
        policy=PolicySpec("AQ-det"),
        n_levels=16,
    )
    res = provision(spec, record_decisions=True)
    dec = np.asarray(res.decisions)
    x0 = min(int(a[0]), 16)
    np.testing.assert_array_equal(reconstruct_schedule(dec, x0),
                                  np.asarray(res.x))


def test_provision_spans_reach_telemetry():
    a = msr_like_trace(np.random.default_rng(5), n_slots=80, mean_jobs=6.0)
    with telemetry_session() as tel:
        provision(_spec(a, 16))
    assert len(tel.samples("span/provision")) == 1


def _events(tel, name):
    return [e for e in tel.chrome_trace()["traceEvents"] if e["name"] == name]


PLAN_PHASES = ("provision/prepare", "provision/dispatch", "provision/finish")
ADVANCE_PHASES = tuple(f"serving/advance/{p}" for p in
                       ("prepare", "dispatch", "fetch", "cost", "record"))


@pytest.mark.parametrize("entry", ["provision", "provision_stream"])
def test_planning_entries_span_their_phases(entry):
    """Each entry opens prepare, dispatch and finish once, nested in its
    outer span (same call, the outer span as parent), and the phases take
    no more than the whole call."""
    from repro.core import provision_stream

    fn = {"provision": provision, "provision_stream": provision_stream}[entry]
    a = msr_like_trace(np.random.default_rng(6), n_slots=80, mean_jobs=6.0)
    with telemetry_session() as tel:
        jax.block_until_ready(fn(_spec(a, 16)).x)
    (outer,) = _events(tel, entry)
    assert outer["args"]["n_levels"] == "16" and "parent" not in outer["args"]
    total = 0.0
    for name in PLAN_PHASES:
        (ev,) = _events(tel, name)
        assert ev["args"]["parent"] == entry
        assert ev["args"]["call"] == outer["args"]["call"]
        assert outer["ts"] <= ev["ts"] and ev["ts"] + ev["dur"] <= outer["ts"] + outer["dur"]
        total += ev["dur"]
    assert total <= outer["dur"]


#: four server generations of one Google cell (12,391 levels): 99 blocks of
#: 128 group-pure lanes, 281 of them pad
TYPED4 = (6732, 3863, 1001, 795)


@pytest.mark.parametrize("entry", ["provision", "provision_stream"])
def test_mesh_route_gauges_its_layout_and_wait_table(entry):
    """Every ``mesh=`` call sets the layout and wait-table gauges on a live
    registry; a typed fleet opens ``provision/finish/group_cost`` inside
    ``provision/finish``, an untyped one does not."""
    from repro.core import provision_stream

    fn = {"provision": provision, "provision_stream": provision_stream}[entry]
    T = 4
    costs = CostModel.from_groups(*(ServerGroup(f"type{k}", n, P=p) for k, (n, p)
                                    in enumerate(zip(TYPED4, (1.0, 1.25, 1.5, 2.0)))))
    a = jnp.asarray([12_000, 300, 9_000, 0], jnp.int32)
    mesh = jax.make_mesh((1,), ("data",))
    for policy, key, table in (("AQ-rand", jax.random.key(3), T * 12_672 * 4),
                               ("AQ-det", None, 0)):
        spec = ProvisionSpec(costs=costs, workload=Workload(demand=a),
                             policy=PolicySpec(policy, key=key), mesh=mesh,
                             use_pallas=False)
        with telemetry_session() as tel:
            jax.block_until_ready(fn(spec).x)
        assert tel.gauge_value("provision/layout_lanes") == 12_672
        assert tel.gauge_value("provision/layout_pad_lanes") == 281
        assert tel.gauge_value("provision/wait_table_bytes") == table
        (ev,) = _events(tel, "provision/finish/group_cost")
        assert ev["args"]["parent"] == "provision/finish"
    with telemetry_session() as tel:
        jax.block_until_ready(fn(_spec(np.arange(T), 16, mesh=mesh, use_pallas=False)).x)
    assert tel.gauge_value("provision/layout_lanes") == 16       # A1, untyped
    assert tel.gauge_value("provision/layout_pad_lanes") == 0
    assert tel.gauge_value("provision/wait_table_bytes") == 0
    assert not _events(tel, "provision/finish/group_cost")


def test_advance_spans_its_phases_and_times_the_whole_tick():
    from repro.serving import FleetProvisioner

    rng = np.random.default_rng(1)
    planner = FleetProvisioner(COSTS, policy="delayedoff", max_replicas=16)
    planner.advance(rng.integers(0, 12, size=4))                 # warm
    with telemetry_session() as tel:
        for _ in range(3):
            planner.advance(rng.integers(0, 12, size=4))
    ticks = tel.samples("span/serving/advance")
    phases = {name: tel.samples(f"span/{name}") for name in ADVANCE_PHASES}
    assert len(ticks) == 3 and all(len(v) == 3 for v in phases.values())
    calls = {e["args"]["call"] for e in _events(tel, "serving/advance")}
    for name in ADVANCE_PHASES:
        evs = _events(tel, name)
        assert {e["args"]["parent"] for e in evs} == {"serving/advance"}
        assert {e["args"]["call"] for e in evs} == calls
    latencies = planner.metrics.plan_latencies_ms[1:]
    for i, lat in enumerate(latencies):
        first_four = sum(phases[name][i] for name in ADVANCE_PHASES[:4])
        assert first_four <= lat <= ticks[i]


@pytest.mark.parametrize("live", [True, False], ids=["live", "default"])
def test_spans_reach_the_profilers_host_plane_only_when_live(tmp_path, live):
    import contextlib
    import glob

    from jax.profiler import ProfileData

    a = msr_like_trace(np.random.default_rng(7), n_slots=80, mean_jobs=6.0)
    spec = _spec(a, 16)
    jax.block_until_ready(provision(spec).x)                      # warm
    with jax.profiler.trace(str(tmp_path)):
        with telemetry_session() if live else contextlib.nullcontext():
            jax.block_until_ready(provision(spec).x)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert ("provision/prepare" in host) == live
    assert any(n.startswith("provision/") for n in host) == live


def test_disabled_span_is_one_shared_noop():
    assert NullTelemetry().span("a") is NullTelemetry().span("b")
    with NullTelemetry().span("a") as sp:
        sp.set(n=1)
    assert NullTelemetry().chrome_trace()["traceEvents"] == []


# --------------------------------------------------------- serving metrics


def test_plan_metrics_prometheus_text():
    from repro.serving import FleetProvisioner

    rng = np.random.default_rng(0)
    planner = FleetProvisioner(COSTS, policy="A1", max_replicas=16)
    for _ in range(3):
        planner.advance(rng.integers(0, 12, size=8))
    m = planner.metrics
    assert m.plans == 3 and len(m.plan_latencies_ms) == 3
    assert m.latency_quantile(0.5) is not None
    txt = m.prometheus_text()
    assert "repro_serving_plans_total 3" in txt
    assert 'quantile="0.99"' in txt
    assert "repro_serving_backlog_depth" in txt


def test_plan_metrics_mirror_into_telemetry():
    from repro.serving.metrics import PlanMetrics

    with telemetry_session() as tel:
        m = PlanMetrics()
        m.observe_plan(12.5, toggles=4, backlog=2)
    assert tel.counter_value("serving/toggles") == 4
    assert tel.gauge_value("serving/backlog_depth") == 2
    assert m.plan_latencies_ms == [12.5]
    assert m.peak_backlog == 2
