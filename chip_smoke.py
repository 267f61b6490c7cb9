#!/usr/bin/env python3
"""Chip smoke test: the provisioning engine's main path on a TPU.

Runs the paper's own evaluation deployment (Sec. V, MSR trace): one
datacenter of about 10^4 servers (N = 10,240 levels), one month of
10-minute slots (T = 4,320) at peak-to-mean ratio 4.63, priced with
``PAPER_COSTS`` (Delta = 6 slots).  The demand is generated from
``--seed``; nothing outside the repository is read.

Every phase goes through the entry points a user calls:

* scan route   -- ``provision(spec)`` without a mesh, for A1 over windows
                  0..5, A3 (window 2, explicit key) and delayedoff;
* grid kernel  -- the same specs with ``mesh=`` a one-device mesh, which
                  runs ``provision_scan_grid`` compiled;
* stream kernel -- the same specs through ``provision_stream(spec,
                  mesh=...)``, plus one long trace (A1, T = 2^20, N = 128);
* live stepper -- ``FleetProvisioner.advance()`` on the 10,240-level fleet
                  at chunk sizes 1, 64 and 1024, with 0 recompiles allowed
                  after warm-up;
* typed fleet  -- four server generations (12,391 levels, the benchmark's
                  ``msr-dc-typed4``) under AQ-rand with an explicit key,
                  through ``provision_stream(spec, mesh=...)`` against
                  ``provision(spec)`` on the scan route.

Correctness gates ``ok``: every Pallas result equals the scan route (``x``
exactly, ``level_cost`` to rtol 1e-6); the scan route's A1 and delayedoff
schedules and costs equal the numpy reference ``fluid_scan``; every online
cost over the offline optimum lies within the paper's bound for its alpha;
the stepper's schedule equals the scan route's; the typed fleet's streaming
kernel equals the scan route bit for bit on ``x``, ``level_cost`` and
``group_cost``.  The route is checked too:
the ``kernels/pallas_interpret`` gauge reads 0 in every Pallas phase and the
compiled programs hold a ``tpu_custom_call``.

``--chips 4`` runs only the level-sharded routes (``provision`` and
``provision_stream`` on a 4-device mesh, 2,560 levels per shard) and the
one-device scan route they are compared with.

Usage::

    python chip_smoke.py [--seed 0] [--chips 1|4]

It exits non-zero, with no result line, when JAX finds no TPU or any
phase fails.  Times printed along the way are bring-up observations, not
metrics.  The last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

#: the deployment (paper Sec. V): N levels, T slots, PMR, mean load
N_LEVELS = 10_240
T_SLOTS = 4_320
TARGET_PMR = 4.63
MEAN_JOBS = 2_160.0          # peak = PMR * mean, about 10^4 servers
N_WINDOWS = 6                # A1 windows 0..Delta-1
A3_WINDOW = 2
#: the long trace the streaming kernel exists for
LONG_T = 2**20
LONG_N = 128
LONG_MEAN_JOBS = 26.0        # peak about 120 < LONG_N
#: ``FleetProvisioner.advance()`` chunk sizes
CHUNKS = (1, 64, 1024)
#: the typed fleet: (name, servers, P) of four generations, beta = 3 each
TYPED4 = (("type1", 6_732, 1.0), ("type2", 3_863, 1.25),
          ("type3", 1_001, 1.5), ("type4", 795, 2.0))
TYPED4_MEAN_JOBS = 2_614.0   # peak about 12,100 < 12,391 levels


class SmokeFailure(AssertionError):
    """A phase produced a wrong result or took the wrong route."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu():
    """The device list, or exit non-zero when JAX finds no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU found (JAX platform is "
            f"{devices[0].platform!r}); this script runs on the chip only"
        )
    return devices


def observe(label: str, fn):
    """Run ``fn`` twice to completion; print the cold and warm seconds."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t2 = time.perf_counter()
    print(f"[observation] {label}: first call {t1 - t0:.3f} s "
          f"(compile + run), warm call {t2 - t1:.3f} s", flush=True)
    return out


def host(res) -> dict:
    import numpy as np

    out = {"x": np.asarray(res.x), "level_cost": np.asarray(res.level_cost)}
    if res.group_cost is not None:
        out["group_cost"] = np.asarray(res.group_cost)
    return out


def same(label: str, got: dict, want: dict) -> None:
    """Pallas result == scan route: x exactly, level_cost to rtol 1e-6."""
    import numpy as np

    check(got["x"].shape == want["x"].shape,
          f"{label}: x shape {got['x'].shape} != {want['x'].shape}")
    bad = int((got["x"] != want["x"]).sum())
    check(bad == 0, f"{label}: x differs from the scan route in {bad} slots")
    check(np.allclose(got["level_cost"], want["level_cost"], rtol=1e-6, atol=0),
          f"{label}: level_cost differs from the scan route beyond rtol 1e-6")
    print(f"  {label}: equals the scan route", flush=True)


def pallas_run(label: str, fn) -> dict:
    """One Pallas-route phase: the traced call must take the compiled
    route (the ``kernels/pallas_interpret`` gauge reads 0)."""
    from repro.obs import telemetry_session

    with telemetry_session() as tel:
        out = host(observe(label, fn))
    gauge = tel.gauge_value("kernels/pallas_interpret")
    check(gauge == 0.0,
          f"{label}: kernels/pallas_interpret gauge is {gauge}, expected 0")
    return out


def check_compiled(label: str, entry, spec) -> None:
    """The program ``entry(spec)`` compiles to holds the Mosaic kernel."""
    import jax

    from repro.core import Workload

    def x_of(demand):
        return entry(dataclasses.replace(
            spec, workload=Workload(demand=demand))).x

    text = jax.jit(x_of).lower(spec.workload.demand).compile().as_text()
    check("tpu_custom_call" in text,
          f"{label}: no tpu_custom_call in the compiled program")
    print(f"  {label}: compiled program holds tpu_custom_call", flush=True)


def msr_demand(seed: int, n_slots: int, mean_jobs: float, n_levels: int):
    """(n_slots,) int32 MSR-like demand at PMR 4.63, clipped to the fleet."""
    from repro.scenarios import Scenario, make_workload

    sc = Scenario("msr_diurnal", seed=seed, target_pmr=TARGET_PMR,
                  mean_jobs=mean_jobs)
    return make_workload(sc, 1, n_slots, clip_to=n_levels).demand[0]


def specs(demand, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import PAPER_COSTS, PolicySpec, ProvisionSpec, Workload

    def spec(policy):
        return ProvisionSpec(costs=PAPER_COSTS, workload=Workload(demand=demand),
                             policy=policy, n_levels=N_LEVELS)

    return {
        "A1": spec(PolicySpec("A1", windows=jnp.arange(N_WINDOWS))),
        "A3": spec(PolicySpec("A3", window=A3_WINDOW,
                              key=jax.random.key(seed))),
        "delayedoff": spec(PolicySpec("delayedoff")),
    }


def scan_route(sp: dict) -> dict:
    from repro.core import provision

    return {name: host(observe(f"scan/{name}", lambda s=s: provision(s)))
            for name, s in sp.items()}


def check_reference(demand, ref: dict) -> None:
    """Scan route vs numpy ``fluid_scan``, and each competitive ratio vs
    the paper's bound (with the statistical slack ``cr_eval`` allows)."""
    import numpy as np

    from repro.core import PAPER_COSTS, fluid_cost, fluid_scan
    from repro.eval.harness import EvalGrid, _bound

    a = np.asarray(demand)
    delta = float(PAPER_COSTS.delta)
    opt = fluid_cost(a, "offline", PAPER_COSTS).cost
    rows = [("A1", w, ref["A1"]["x"][w], ref["A1"]["level_cost"][w])
            for w in range(N_WINDOWS)]
    rows.append(("A3", A3_WINDOW, ref["A3"]["x"], ref["A3"]["level_cost"]))
    rows.append(("delayedoff", 0, ref["delayedoff"]["x"],
                 ref["delayedoff"]["level_cost"]))
    for name, w, x, level_cost in rows:
        cost = float(level_cost.astype(np.float64).sum())
        if name != "A3":
            want = fluid_scan(a, name, PAPER_COSTS, window=w)
            check(np.array_equal(x, want.x),
                  f"{name} w={w}: x differs from fluid_scan")
            check(abs(cost - want.cost) <= 1e-6 * abs(want.cost),
                  f"{name} w={w}: cost {cost} != fluid_scan {want.cost}")
        alpha = min(1.0, (w + 1) / delta)
        bound = _bound(name, alpha)
        ratio = cost / opt
        check(ratio <= bound + EvalGrid.tol,
              f"{name} w={w}: competitive ratio {ratio:.4f} > bound {bound:.4f}")
        print(f"  {name} w={w}: cost {cost:.0f}, ratio to offline "
              f"{ratio:.4f} <= bound {bound:.4f}"
              + ("" if name == "A3" else ", equals fluid_scan"), flush=True)


def mesh_of(devices):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), ("data",))


def grid_phase(sp: dict, ref: dict, mesh, tag: str) -> None:
    from repro.core import provision

    for name, s in sp.items():
        s = dataclasses.replace(s, mesh=mesh)
        got = pallas_run(f"grid{tag}/{name}", lambda s=s: provision(s))
        same(f"grid{tag}/{name}", got, ref[name])
    check_compiled(f"grid{tag}", provision,
                   dataclasses.replace(sp["A1"], mesh=mesh))


def stream_phase(sp: dict, ref: dict, mesh, tag: str) -> None:
    from repro.core import provision_stream

    for name, s in sp.items():
        s = dataclasses.replace(s, mesh=mesh)
        got = pallas_run(f"stream{tag}/{name}",
                         lambda s=s: provision_stream(s))
        same(f"stream{tag}/{name}", got, ref[name])
    check_compiled(f"stream{tag}", provision_stream,
                   dataclasses.replace(sp["A1"], mesh=mesh))


def long_trace_phase(seed: int, mesh) -> None:
    from repro.core import (
        PAPER_COSTS,
        PolicySpec,
        ProvisionSpec,
        Workload,
        provision,
        provision_stream,
    )

    demand = msr_demand(seed, LONG_T, LONG_MEAN_JOBS, LONG_N)
    spec = ProvisionSpec(costs=PAPER_COSTS, workload=Workload(demand=demand),
                         policy=PolicySpec("A1", window=2),
                         n_levels=LONG_N)
    want = host(observe("scan/long-A1", lambda: provision(spec)))
    s = dataclasses.replace(spec, mesh=mesh)
    got = pallas_run("stream/long-A1", lambda: provision_stream(s))
    same(f"stream/long-A1 (T={LONG_T}, N={LONG_N})", got, want)


def typed_phase(seed: int, mesh) -> None:
    """AQ-rand on the four-generation fleet: the streaming kernel equals the
    scan route exactly on ``x``, ``level_cost`` and ``group_cost``."""
    import jax
    import numpy as np

    from repro.core import (
        CostModel,
        PolicySpec,
        ProvisionSpec,
        ServerGroup,
        Workload,
        provision,
        provision_stream,
    )

    costs = CostModel.from_groups(*(ServerGroup(name, n, P=p) for name, n, p in TYPED4))
    n_levels = sum(costs.group_sizes)
    demand = msr_demand(seed, T_SLOTS, TYPED4_MEAN_JOBS, n_levels)
    spec = ProvisionSpec(costs=costs, workload=Workload(demand=demand),
                         policy=PolicySpec("AQ-rand", key=jax.random.key(seed)),
                         n_levels=n_levels)
    want = host(observe("scan/typed4-AQ-rand", lambda: provision(spec)))
    s = dataclasses.replace(spec, mesh=mesh)
    got = pallas_run("stream/typed4-AQ-rand", lambda: provision_stream(s))
    for k in ("x", "level_cost", "group_cost"):
        check(np.array_equal(got[k], want[k]),
              f"stream/typed4-AQ-rand: {k} differs from the scan route")
    print(f"  stream/typed4-AQ-rand (N={n_levels}, groups {costs.group_sizes}): "
          f"equals the scan route bit for bit, group_cost {want['group_cost']}",
          flush=True)


def stepper_phase(demand, ref: dict) -> None:
    """``advance()`` at chunk sizes 1, 64, 1024: a warm-up pass, then a
    second pass that may add no compiles; the committed schedule equals
    the scan route's delayedoff schedule on the same slots."""
    import numpy as np

    from repro.core import PAPER_COSTS
    from repro.obs import CompileWatcher
    from repro.obs.jaxwatch import engine_fns
    from repro.serving import FleetProvisioner, stepper_chunk, stepper_tick

    a = np.asarray(demand)
    fleet = FleetProvisioner(PAPER_COSTS, policy="delayedoff",
                             max_replicas=N_LEVELS)
    xs, t = [], 0
    for rnd in ("warm-up", "steady"):
        watch = CompileWatcher(fns=engine_fns() + (stepper_chunk, stepper_tick))
        with watch:
            for n in CHUNKS:
                t0 = time.perf_counter()
                xs.append(fleet.advance(a[t:t + n]))
                print(f"[observation] advance/{rnd}/chunk={n}: "
                      f"{time.perf_counter() - t0:.4f} s", flush=True)
                t += n
        print(f"  advance {rnd} pass: {watch.added} compiles", flush=True)
    check(watch.added == 0,
          f"advance(): {watch.added} compiles after warm-up, expected 0")
    x = np.concatenate(xs)
    check(np.array_equal(x, ref["delayedoff"]["x"][:t]),
          "advance(): schedule differs from the scan route's delayedoff")
    print(f"  advance(): {t} slots equal the scan route", flush=True)


def smoke(seed: int, devices, chips: int) -> None:
    demand = msr_demand(seed, T_SLOTS, MEAN_JOBS, N_LEVELS)
    print(f"workload: msr_diurnal seed={seed}, T={T_SLOTS}, N={N_LEVELS}, "
          f"peak={int(demand.max())}, mean={float(demand.mean()):.1f}",
          flush=True)
    sp = specs(demand, seed)
    ref = scan_route(sp)
    if chips == 4:
        mesh = mesh_of(devices[:4])
        grid_phase(sp, ref, mesh, "@4")
        stream_phase(sp, ref, mesh, "@4")
        return
    check_reference(demand, ref)
    mesh = mesh_of(devices[:1])
    grid_phase(sp, ref, mesh, "")
    stream_phase(sp, ref, mesh, "")
    long_trace_phase(seed, mesh)
    stepper_phase(demand, ref)
    typed_phase(seed, mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated demand and the A3 key")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the level-sharded routes on a 4-chip mesh")
    args = ap.parse_args(argv)
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env in ("1", "true", "yes", "on"):
        sys.exit("chip_smoke: REPRO_PALLAS_INTERPRET forces interpret mode; "
                 "unset it to run the compiled kernels")

    devices = require_tpu()
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {len(devices)}")
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    smoke(args.seed, devices, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
