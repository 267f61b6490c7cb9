"""One declarative provisioning API: ``provision(ProvisionSpec(...))``.

The spec is three pytree-registered frozen dataclasses plus options:

  * :class:`~repro.core.costs.CostModel` — ``P``/``beta_on``/``beta_off`` as
    scalars or ``(n_levels,)`` arrays (heterogeneous fleets); the critical
    interval Δ is always *derived* per level (paper eq. 12), never passed;
    typed fleets come from ``CostModel.from_groups(ServerGroup(...), ...)``
    — d server types in routing-priority order, with ``group_cost`` on the
    result breaking every schedule's spend down per type;
  * :class:`Workload` — demand ``(T,)`` or ``(B, T)``, an optional
    ``predicted`` trace, or an optional :class:`PredictionNoise` model that
    synthesizes one (paper Sec. V-C);
  * :class:`PolicySpec` — policy name, a single ``window`` or a ``windows``
    sweep axis (α = (w+1)/Δ), and the PRNG ``key`` for A2/A3.

:func:`provision` runs the whole (noise-stds × windows × traces × levels)
grid as one jitted device program and returns a :class:`ProvisionResult`
carrying the schedule, total/energy/toggle costs, and the per-level cost
breakdown.  Passing ``mesh=`` shards the level axis over the mesh through
the fused Pallas grid scan (:mod:`repro.kernels.provision_scan`) — the
same sweep axes, one kernel program per (noise-std, window, trace) cell,
bit-exact against the unsharded path.

Shape convention: the result keeps a leading windows axis iff the spec used
``windows=``, a batch axis iff demand was ``(B, T)``, and an outermost
noise axis iff ``PredictionNoise.std_frac`` was a ``(S,)`` sweep — mirroring
the inputs, so ``result.x`` is ``(T,)``, ``(B, T)``, ``(W, T)``,
``(W, B, T)`` … up to ``(S, W, B, T)``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..deferral import DeferralSpec
from ..obs import provenance as _prov
from ..obs.telemetry import get_telemetry
from . import jax_provision as _engine
from .costs import CostModel


@dataclasses.dataclass(frozen=True, eq=False)
class PredictionNoise:
    """Zero-mean Gaussian prediction error, std = ``std_frac`` × actual load.

    The JAX-native form of :func:`repro.core.traces.with_prediction_error`
    (paper Sec. V-C): the peek step reads ``max(round(a + ε), 0)`` with
    ``ε ~ N(0, (std_frac · a)²)`` drawn from ``key``.

    ``std_frac`` is a float, or a ``(S,)`` array to sweep error levels as a
    leading axis of the result (like ``PolicySpec.windows``): the normal
    draw is shared across the sweep (common random numbers), only its scale
    varies, so ratio curves over S are variance-reduced and the ``S=1``
    sweep reduces to the scalar row exactly.
    """

    std_frac: float | jax.Array
    key: jax.Array

    def apply(self, demand: jax.Array) -> jax.Array:
        """(T,) draws from ``key`` directly; (B, T) splits it per trace —
        the same convention as ``PolicySpec.key``, so batched noise studies
        reduce to their unbatched rows exactly.  A ``(S,)`` ``std_frac``
        prepends an S axis to the result."""
        a = jnp.asarray(demand, jnp.float32)

        if a.ndim == 2:
            z = jax.vmap(lambda k, ai: jax.random.normal(k, ai.shape))(
                jax.random.split(self.key, a.shape[0]), a
            )
        else:
            z = jax.random.normal(self.key, a.shape)
        std = jnp.asarray(self.std_frac, jnp.float32)
        if std.ndim == 1:
            std = std.reshape((std.shape[0],) + (1,) * a.ndim)
        elif std.ndim > 1:
            raise ValueError(
                f"std_frac must be a scalar or a (S,) sweep, got shape {std.shape}"
            )
        return jnp.maximum(jnp.rint(a + std * z * a), 0.0).astype(jnp.int32)


jax.tree_util.register_dataclass(
    PredictionNoise, data_fields=["std_frac", "key"], meta_fields=[]
)


@dataclasses.dataclass(frozen=True, eq=False)
class Workload:
    """Demand trace(s) plus what the peek step is allowed to see.

    ``demand``: (T,) or (B, T) integer concurrency per slot.  ``predicted``:
    optional trace(s) of the same shape the prediction window reads (the
    dispatcher always sees the true current slot).  ``noise``: optional
    :class:`PredictionNoise` that synthesizes ``predicted`` from ``demand``
    (its ``std_frac`` may be a ``(S,)`` sweep axis); mutually exclusive with
    an explicit ``predicted``.  ``deferral``: optional
    :class:`~repro.deferral.DeferralSpec` marking the demand as *arrivals
    with slack* rather than rigid load — :func:`provision` then water-fills
    the arrivals into the deferred service profile before the engine sees
    them (defer-then-provision) and reports queue metrics on the result.
    A zero-slack spec is bit-exact with no spec at all.
    """

    demand: jax.Array
    predicted: jax.Array | None = None
    noise: PredictionNoise | None = None
    deferral: DeferralSpec | None = None

    def resolve_predicted(self, demand_i32: jax.Array) -> jax.Array | None:
        if self.predicted is not None and self.noise is not None:
            raise ValueError("pass either predicted= or noise=, not both")
        if self.noise is not None:
            return self.noise.apply(demand_i32)
        if self.predicted is not None:
            return jnp.asarray(self.predicted, jnp.int32)
        return None


jax.tree_util.register_dataclass(
    Workload,
    data_fields=["demand", "predicted", "noise", "deferral"],
    meta_fields=[],
)


@dataclasses.dataclass(frozen=True, eq=False)
class PolicySpec:
    """Which algorithm runs, with how much future, under which key.

    ``name``: one of ``repro.core.jax_provision.POLICIES``.  ``window``: the
    number of future slots the peek sees (α = (window+1)/Δ per level).
    ``windows``: optional (W,) sweep axis — evaluates every window in one
    program and puts a leading W axis on the result; overrides ``window``.
    ``key``: explicit PRNG key, required for the randomized A2/A3 and the
    typed-fleet AQ-rand (split per trace for batched demand).  The
    Albers–Quedenfeld pair ``AQ-det``/``AQ-rand`` never peeks, so both
    ignore ``window``/``windows`` (the sweep axis broadcasts).
    """

    name: str = "A1"
    window: int = 0
    windows: jax.Array | None = None
    key: jax.Array | None = None

    def validate(self) -> "PolicySpec":
        """Raise ValueError for unknown policy names or a missing key on the
        randomized policies; returns self (chainable)."""
        _engine._check_policy(self.name)
        if self.name in _engine.KEYED:
            _engine._require_key(self.name, self.key)
        return self


jax.tree_util.register_dataclass(
    PolicySpec, data_fields=["windows", "key"], meta_fields=["name", "window"]
)


@dataclasses.dataclass(frozen=True, eq=False)
class ProvisionSpec:
    """The complete declarative input of one provisioning computation.

    ``n_levels``: fleet size; defaults to the cost model's per-level length,
    else ``max(demand) + 1`` (concrete demand only — under jit/vmap pass it
    explicitly).  ``mesh``/``mesh_axis``: shard the level axis over a mesh
    axis through the fused Pallas grid scan — the full (noise-std × window
    × trace) sweep runs as one program per grid cell and level block, with
    results bit-exact against the unsharded path (online policies only;
    ``offline`` has no slot scan).  ``use_pallas=False`` keeps the lax.scan
    body per cell.
    """

    costs: CostModel
    workload: Workload
    policy: PolicySpec
    n_levels: int | None = None
    mesh: Mesh | None = None
    mesh_axis: str = "data"
    use_pallas: bool = True


jax.tree_util.register_dataclass(
    ProvisionSpec,
    data_fields=["costs", "workload", "policy"],
    meta_fields=["n_levels", "mesh", "mesh_axis", "use_pallas"],
)


@dataclasses.dataclass(frozen=True, eq=False)
class ProvisionResult:
    """What one :func:`provision` call produced (all device arrays).

    ``x``: powered-on servers per slot, (..., T) int32.  ``cost`` =
    ``energy`` + ``toggle_cost`` (paper eq. 5, forced x(T)=a(T) boundary).
    ``level_cost``: (..., N) per-level totals — the heterogeneous-fleet
    breakdown (which server types the money went to).  ``group_cost``:
    (..., d) per-type totals for typed fleets (``CostModel.from_groups``,
    one column per server type in routing-priority order); None for
    ungrouped models.

    Deferral-enabled workloads (``Workload(deferral=...)``) additionally
    carry the queue's view of the schedule, all None otherwise:
    ``backlog`` (..., T) work still queued after each slot;
    ``max_delay`` / ``p99_delay`` (...) worst and 99th-percentile queueing
    delay in slots over served units; ``deadline_misses`` (...) units that
    expired while queued; ``unserved`` (...) units left at the horizon
    (0 whenever the schedule covers the deferred profile).

    ``provision(spec, record_decisions=True)`` fills the provenance pair
    (both None by default): ``decisions`` (..., T, N) uint8 per-slot reason
    bitmask (:mod:`repro.obs.provenance` — demand-rise / wait-expired /
    peek-fired / toggle-off), and ``decision_counts``, a dict of the four
    aggregate per-level counters (..., N) int32 keyed by
    ``repro.obs.provenance.COUNT_ORDER`` names.  The sharded (mesh) route
    records the aggregate counters only — ``decisions`` stays None there
    (see docs/observability.md).
    """

    x: jax.Array
    cost: jax.Array
    energy: jax.Array
    toggle_cost: jax.Array
    level_cost: jax.Array
    group_cost: jax.Array | None = None
    backlog: jax.Array | None = None
    max_delay: jax.Array | None = None
    p99_delay: jax.Array | None = None
    deadline_misses: jax.Array | None = None
    unserved: jax.Array | None = None
    decisions: jax.Array | None = None
    decision_counts: dict | None = None


jax.tree_util.register_dataclass(
    ProvisionResult,
    data_fields=["x", "cost", "energy", "toggle_cost", "level_cost",
                 "group_cost", "backlog", "max_delay", "p99_delay",
                 "deadline_misses", "unserved", "decisions",
                 "decision_counts"],
    meta_fields=[],
)


def _prepare(spec: ProvisionSpec, pol: PolicySpec) -> dict:
    """Normalize a validated spec into engine-shaped inputs (shared by
    :func:`provision` and :func:`provision_stream`).

    Applies deferral water-filling, resolves the predicted trace / noise
    sweep, infers ``n_levels``, broadcasts the cost fields per level and
    derives the squeeze conventions.  Returns a dict of everything the
    engine bodies consume plus the true ``arrivals`` (queue metrics are
    always measured on those, not on the deferred profile).
    """
    a = jnp.asarray(spec.workload.demand, jnp.int32)
    if a.ndim not in (1, 2):
        raise ValueError(f"demand must be (T,) or (B, T), got shape {a.shape}")
    defer = spec.workload.deferral
    arrivals = a
    if defer is not None:
        # defer-then-provision: the engine (predictions, noise, n_levels
        # inference, the offline baseline) runs on the water-filled service
        # profile; queue metrics are measured on the true arrivals
        a = defer.validate().apply(a)
    squeeze_b = a.ndim == 1
    ab = a[None] if squeeze_b else a
    noise = spec.workload.noise
    squeeze_s = noise is None or jnp.ndim(noise.std_frac) == 0
    pred = spec.workload.resolve_predicted(a)
    if pred is None:
        predb = ab
    else:
        want = (
            a.shape
            if squeeze_s
            else (jnp.shape(noise.std_frac)[0],) + a.shape
        )
        if pred.shape != want:
            raise ValueError(
                f"predicted shape {pred.shape} must match demand shape "
                f"{a.shape}"
                + ("" if squeeze_s else
                   f" with a leading noise-sweep axis (expected {want})")
            )
        predb = jnp.expand_dims(pred, -2) if squeeze_b else pred

    spec.costs.validate_groups()
    n_levels = spec.n_levels
    if n_levels is None:
        n_levels = spec.costs.n_levels
    if n_levels is None:
        if isinstance(jnp.asarray(ab), jax.core.Tracer):
            # int(ab.max()) below would die with an opaque
            # ConcretizationTypeError when the caller traces provision()
            # under jit/vmap — name the actual fix instead
            raise ValueError(
                "n_levels cannot be derived from demand inside jit/vmap "
                "(the demand is a tracer, so max(demand) is not concrete): "
                "pass ProvisionSpec(n_levels=...) explicitly or use a "
                "CostModel with (n_levels,) per-level fields"
            )
        n_levels = int(ab.max()) + 1        # needs concrete demand
    P_lv, bon_lv, boff_lv = spec.costs.per_level(n_levels)
    delta_lv = jnp.broadcast_to(
        jnp.asarray(spec.costs.delta, jnp.float32), (n_levels,)
    )

    squeeze_w = pol.windows is None
    windows = (
        jnp.asarray([pol.window], jnp.int32)
        if squeeze_w
        else jnp.asarray(pol.windows, jnp.int32)
    )

    keys = None
    if pol.name in _engine.KEYED:
        keys = (
            pol.key[None] if squeeze_b else jax.random.split(pol.key, ab.shape[0])
        )
    return dict(
        arrivals=arrivals, defer=defer, ab=ab, predb=predb,
        squeeze_b=squeeze_b, squeeze_w=squeeze_w, squeeze_s=squeeze_s,
        windows=windows, keys=keys, n_levels=n_levels,
        P_lv=P_lv, bon_lv=bon_lv, boff_lv=boff_lv, delta_lv=delta_lv,
        max_h=spec.costs.delta_slots(),
    )


def _spec_axes(out: dict, pr: dict, mesh: bool) -> dict:
    """Squeeze an engine body's output back to the spec's axes.

    The mesh routes and the noise sweeps return the full (S, W, B, ...)
    grid; the single-trace scan bodies return (W, B, ...) with no noise
    axis.
    """
    grid = mesh or not pr["squeeze_s"]
    lead = 1 if grid else 0
    if pr["squeeze_b"]:
        out = jax.tree.map(lambda o: jnp.squeeze(o, axis=lead + 1), out)
    if pr["squeeze_w"]:
        out = jax.tree.map(lambda o: jnp.squeeze(o, axis=lead), out)
    if grid and pr["squeeze_s"]:
        out = jax.tree.map(lambda o: jnp.squeeze(o, axis=0), out)
    return out


def _counts_from_rows(out: dict) -> dict:
    """The aggregate per-level counters of a body that records them as
    ``decision_counts`` rows (..., 4, N)."""
    rows = out.pop("decision_counts")
    return {name: rows[..., i, :] for i, name in enumerate(_prov.COUNT_ORDER)}


def _result(spec: ProvisionSpec, pr: dict, out: dict, decisions,
            counts) -> ProvisionResult:
    """Per-level and fleet costs, queue metrics and the result, from the
    engine's per-level energy and toggle totals."""
    level_cost = out["energy"] + out["on_cost"] + out["off_cost"]
    defer = pr["defer"]
    queue = {} if defer is None else defer.metrics(pr["arrivals"], out["x"])
    group_cost = None
    if spec.costs.group_sizes is not None:
        with get_telemetry().span("provision/finish/group_cost"):
            group_cost = spec.costs.group_reduce(level_cost)
    return ProvisionResult(
        x=out["x"],
        cost=level_cost.sum(axis=-1),
        energy=out["energy"].sum(axis=-1),
        toggle_cost=(out["on_cost"] + out["off_cost"]).sum(axis=-1),
        level_cost=level_cost,
        group_cost=group_cost,
        backlog=queue.get("backlog"),
        max_delay=queue.get("max_delay"),
        p99_delay=queue.get("p99_delay"),
        deadline_misses=queue.get("deadline_misses"),
        unserved=queue.get("unserved"),
        decisions=decisions,
        decision_counts=counts,
    )


def _layout_gauges(tel, spec: ProvisionSpec, pr: dict, policy: str) -> None:
    """On the ``mesh=`` route, its storage layout and wait table as gauges
    on a live registry: ``provision/layout_lanes`` and
    ``provision/layout_pad_lanes`` (the group-aligned lanes and how many
    of them are pad), ``provision/wait_table_bytes`` (the time-varying
    (K, T, lanes) float32 threshold table the kernel reads, K from
    ``_n_wait_tables``; 0 for constant thresholds)."""
    if spec.mesh is None or not tel.enabled:
        return
    n_layout, pad = _engine._layout_lanes(
        pr["n_levels"], spec.costs.group_sizes, spec.mesh.shape[spec.mesh_axis])
    B, T = pr["ab"].shape
    tel.gauge("provision/layout_lanes", n_layout)
    tel.gauge("provision/layout_pad_lanes", pad)
    tel.gauge("provision/wait_table_bytes", 4 * T * n_layout
              * _engine._n_wait_tables(policy, pr["windows"].shape[0], B))


def provision(spec: ProvisionSpec, *, record_decisions: bool = False) -> ProvisionResult:
    """Run a :class:`ProvisionSpec` end-to-end as one jitted device program.

    Batching is the demand's leading axis, the α-sweep is
    ``PolicySpec.windows``, sharding is ``mesh=``.  The cost model's fields flow through jit as data, so re-pricing the fleet
    does not recompile; only (policy, shapes, Δ's static scan bound) do.

    ``record_decisions=True`` fills ``ProvisionResult.decisions`` /
    ``decision_counts`` with per-slot reason codes out of the slot scan
    (:mod:`repro.obs.provenance`); it is a *static* switch — the default-off
    path traces exactly today's program, bit-for-bit and compile-for-compile
    (gated in ``provision_bench.py --smoke``).  Rejected for ``offline``,
    which is a closed form with no slot scan to record.
    """
    tel = get_telemetry()
    route = "mesh" if spec.mesh is not None else "scan"
    with tel.span("provision", policy=spec.policy.name, route=route,
                  record=record_decisions) as outer:
        pol = spec.policy.validate()
        if record_decisions and pol.name == "offline":
            raise ValueError(
                "record_decisions=True: 'offline' is the closed-form hindsight "
                "optimum — it has no slot scan, so there are no per-slot "
                "decisions to record"
            )
        with tel.span("provision/prepare"):
            pr = _prepare(spec, pol)
        n_levels = pr["n_levels"]
        outer.set(n_levels=n_levels)
        _layout_gauges(tel, spec, pr, pol.name)
        engine_in = (pr["ab"], pr["predb"], pr["windows"], pr["delta_lv"],
                     pr["P_lv"], pr["bon_lv"], pr["boff_lv"])
        with tel.span("provision/dispatch"):
            if spec.mesh is not None:
                # the fleet path takes the same (S, W, B) grid as the
                # lax.scan programs: predb normalized to (S, B, T)
                ab, predb, *rest = engine_in
                predb3 = predb[None] if predb.ndim == 2 else predb
                out = _engine._sharded_run(
                    spec.mesh, spec.mesh_axis, ab, predb3, *rest,
                    n_levels=n_levels, max_h=pr["max_h"], policy=pol.name,
                    keys=pr["keys"], use_pallas=spec.use_pallas,
                    group_sizes=spec.costs.group_sizes,
                    record=record_decisions,
                )
            else:
                # noise sweep: the engine vmapped over the (S,) predicted
                # axis with the demand, windows and keys held fixed —
                # common random numbers across error levels, one compiled
                # program for the whole (S, W, B) grid
                body = _engine._run if pr["squeeze_s"] else _engine._run_noise_sweep
                out = body(
                    *engine_in, pr["keys"], n_levels=n_levels,
                    max_h=pr["max_h"], policy=pol.name,
                    record=record_decisions,
                )
        with tel.span("provision/finish"):
            out = _spec_axes(out, pr, mesh=spec.mesh is not None)
            decisions = out.pop("decisions", None)
            counts = None
            if record_decisions:
                if decisions is not None:
                    # lax.scan route: full per-slot codes; the aggregate
                    # counters are one reduction away (same rows the mesh
                    # route records)
                    counts = {
                        name: ((decisions & bit) != 0).sum(axis=-2).astype(jnp.int32)
                        for name, bit in zip(_prov.COUNT_ORDER, _prov.COUNT_BITS)
                    }
                else:
                    counts = _counts_from_rows(out)
            return _result(spec, pr, out, decisions, counts)


def provision_stream(
    spec: ProvisionSpec,
    *,
    t_chunk: int | None = None,
    record_decisions: bool = False,
) -> ProvisionResult:
    """:func:`provision` for production-length traces: same spec, same
    result, O(t_chunk · levels) working set per cell instead of the
    monolithic scan's O(T · levels) on-matrix.

    Both engine routes stream the trace in ``t_chunk``-slot tiles with an
    explicit carry — the lax.scan route through the chunked
    ``_run_stream`` bodies, the ``mesh=`` route through the HBM-resident
    double-buffered Pallas kernel
    (:func:`repro.kernels.provision_scan.provision_scan_stream`).  Results
    are **bit-exact** against :func:`provision` on every field for every
    online policy: the carry preserves the engine state across tiles, the
    peek reads into the next tile so chunking never truncates the window,
    and the randomized policies consume the same absolute-slot wait draws
    (CRN parity; their (T, N) uniform tables, and on the ``mesh=`` route
    the (K, T, N) threshold table the kernel reads, are the O(T)
    allocations the streaming path keeps — the live registry's
    ``provision/wait_table_bytes`` gauge reports the threshold table's size
    on every ``mesh=`` call; docs/provisioning_engine.md "Streaming & long
    traces").

    Two deliberate differences: ``offline`` is rejected (the hindsight
    optimum is a closed form over the whole trace — there is nothing to
    stream), and ``record_decisions=True`` fills ``decision_counts`` only
    (aggregate per-level counters, the fleet-path convention) — per-slot
    ``decisions`` codes are exactly the O(T · N) buffer streaming exists to
    avoid.  ``t_chunk`` defaults to
    :data:`repro.kernels.provision_scan.DEFAULT_T_CHUNK` and is clamped to
    the trace length; it is a compile key but never changes results.
    """
    from repro.kernels.provision_scan import DEFAULT_T_CHUNK

    tel = get_telemetry()
    route = "mesh" if spec.mesh is not None else "scan"
    with tel.span("provision_stream", policy=spec.policy.name, route=route,
                  record=record_decisions) as outer:
        pol = spec.policy.validate()
        if pol.name == "offline":
            raise ValueError(
                "provision_stream is online-only: 'offline' is the closed-form "
                "hindsight optimum over the whole trace — use provision()"
            )
        with tel.span("provision/prepare"):
            pr = _prepare(spec, pol)
        n_levels = pr["n_levels"]
        T = int(pr["ab"].shape[-1])
        if t_chunk is None:
            t_chunk = DEFAULT_T_CHUNK
        t_chunk = int(min(max(int(t_chunk), 1), max(T, 1)))
        outer.set(n_levels=n_levels, t_chunk=t_chunk)
        _layout_gauges(tel, spec, pr, pol.name)
        engine_in = (pr["ab"], pr["predb"], pr["windows"], pr["delta_lv"],
                     pr["P_lv"], pr["bon_lv"], pr["boff_lv"])
        with tel.span("provision/dispatch"):
            if spec.mesh is not None:
                ab, predb, *rest = engine_in
                predb3 = predb[None] if predb.ndim == 2 else predb
                out = _engine._sharded_run(
                    spec.mesh, spec.mesh_axis, ab, predb3, *rest,
                    n_levels=n_levels, max_h=pr["max_h"], policy=pol.name,
                    keys=pr["keys"], use_pallas=spec.use_pallas,
                    group_sizes=spec.costs.group_sizes, t_chunk=t_chunk,
                    record=record_decisions,
                )
            else:
                body = (
                    _engine._run_stream if pr["squeeze_s"]
                    else _engine._run_stream_noise
                )
                out = body(
                    *engine_in, pr["keys"], n_levels=n_levels,
                    max_h=pr["max_h"], policy=pol.name, t_chunk=t_chunk,
                    record=record_decisions,
                )
        with tel.span("provision/finish"):
            out = _spec_axes(out, pr, mesh=spec.mesh is not None)
            counts = _counts_from_rows(out) if record_decisions else None
            return _result(spec, pr, out, None, counts)
