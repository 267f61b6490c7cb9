"""Closed-loop callers of the entries a cell's window drives.

A traffic file names its ``entry``: the file ``bench/entries/<entry>.py``,
whose ``Caller`` is one of the classes here or a class of its own:

- :class:`PlanCaller`: a planning entry of ``repro.core`` (``provision``,
  ``provision_stream``) over a pool of seeded traces; the traffic's
  ``mesh`` (a number of devices) routes the spec through the program's
  ``mesh=`` path;
- :class:`LiveCaller`: ``repro.serving.FleetProvisioner.advance(chunk)``,
  one chunk of slots per tick, the fleet's state carried from tick to tick.

A caller builds its inputs from the seed (:meth:`setup`), warms up every
shape the window uses (:meth:`warm`), runs the window (:meth:`window`),
reports its end-to-end numbers (:meth:`end_to_end`) and, once the window
has closed, compares what the window produced with the plain reference
(:meth:`check`).
"""
from __future__ import annotations

import time

import numpy as np

from . import compare, demand, discover, reference


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def derived_seed(seed: int, *parts: int) -> int:
    """A 31-bit integer drawn from ``(seed, *parts)``: PRNG keys for JAX,
    which takes no seed above 64 bits."""
    return int(np.random.default_rng((seed,) + parts).integers(0, 2**31 - 1))


class Caller:
    """What every entry shares: the configuration, the traffic, the seed."""

    def __init__(self, config: dict, traffic: dict, seed: int, root=discover.ROOT):
        self.root = root
        self.config = config
        self.traffic = traffic
        # the reference's rule for the policy; a randomized one (with
        # ``waits``) gets a PRNG key per trace
        self.rule = reference.policy_rule(traffic["policy"], root)
        self.seed = int(seed) % 2**64
        self.n_levels = int(config["fleet"]["n_levels"])
        self.n_slots = int(config["n_slots"])
        self.costs = {k: float(config["costs"][k]) for k in ("P", "beta_on", "beta_off")}
        self.first_calls: dict[str, float] = {}

    def trace(self, index: int) -> np.ndarray:
        return demand.generate(self.config["demand"], self.seed, index,
                               self.n_slots, self.n_levels, self.root)

    def cost_model(self):
        from repro.core import CostModel

        return CostModel(**self.costs)

    def timed_first(self, label: str, fn):
        t0 = time.perf_counter()
        out = fn()
        self.first_calls[label] = time.perf_counter() - t0
        return out


class PlanCaller(Caller):
    """A planning entry of ``repro.core``, named by ``ENTRY``, over a pool
    of seeded traces kept on the device; one trace per call, the pool
    cycled in order, every window of the traffic's ``windows`` in each."""

    ENTRY = "provision"

    def setup(self):
        import importlib

        import jax
        import jax.numpy as jnp

        from repro.core import PolicySpec, ProvisionSpec, Workload

        tr = self.traffic
        self.policy = tr["policy"]
        self.windows = [int(w) for w in tr["windows"]]
        self.pool = [self.trace(i) for i in range(int(tr["pool"]))]
        self.keys = [jax.random.key(derived_seed(self.seed, i, 1))
                     for i in range(len(self.pool))] if hasattr(self.rule, "waits") else None
        mesh = None
        if "mesh" in tr:
            from jax.sharding import Mesh

            mesh = Mesh(np.asarray(jax.devices()[:int(tr["mesh"])]), ("data",))
        costs = self.cost_model()
        self.specs = []
        for i, a in enumerate(self.pool):
            pol = PolicySpec(self.policy, windows=jnp.asarray(self.windows, jnp.int32),
                             key=None if self.keys is None else self.keys[i])
            self.specs.append(ProvisionSpec(
                costs=costs, workload=Workload(demand=jax.device_put(jnp.asarray(a, jnp.int32))),
                policy=pol, n_levels=self.n_levels, mesh=mesh))
        self.entry = getattr(importlib.import_module("repro.core"), self.ENTRY)
        self.decisions_per_call = len(self.windows) * self.n_slots * self.n_levels

    def call(self, i: int):
        import jax

        with _annotate("bench/entry"):
            t0 = time.perf_counter()
            res = self.entry(self.specs[i])
            t1 = time.perf_counter()
        with _annotate("bench/block"):
            jax.block_until_ready(res)
            cost = np.asarray(res.cost)
        return res, cost, t1 - t0

    def warm(self):
        self.timed_first(self.traffic["entry"], lambda: self.call(0))
        for i in range(len(self.specs)):
            self.call(i)

    def window(self, seconds: float):
        self.last = {}
        self.costs_by_trace = {i: [] for i in range(len(self.specs))}
        self.host_s = []
        t_start = time.perf_counter()
        n = 0
        while True:
            i = n % len(self.specs)
            res, cost, host = self.call(i)
            self.last[i] = res
            self.costs_by_trace[i].append(cost)
            self.host_s.append(host)
            n += 1
            t_end = time.perf_counter()
            if t_end - t_start >= seconds:
                break
        self.calls = n
        self.window_s = t_end - t_start
        return n

    def end_to_end(self) -> dict:
        return {"plan_decisions_per_s": self.calls * self.decisions_per_call / self.window_s}

    def layer_inputs(self) -> dict:
        return {"kind": "plan", "calls": self.calls,
                "host_s": self.host_s, "window_s": self.window_s,
                "n_levels": self.n_levels, "n_slots": self.n_slots,
                "windows": len(self.windows), "traces_per_call": 1}

    def drop_state(self):
        """Keep the host copies the check needs; free the device state."""
        self.kept = {i: (np.asarray(r.x), np.asarray(r.level_cost))
                     for i, r in self.last.items()}
        self.last = self.specs = None

    def reference(self, i: int, acc_dtype="float64"):
        waits = None
        if self.keys is not None:
            delta = (self.costs["beta_on"] + self.costs["beta_off"]) / self.costs["P"]
            waits = self.rule.waits(self.keys[i], self.n_slots, self.n_levels,
                                    self.windows, delta)
        return reference.slot_loop(self.pool[i], self.n_levels, self.costs,
                                   policy=self.rule, windows=self.windows,
                                   waits=waits, acc_dtype=acc_dtype)

    def check(self):
        parts, failed = [], 0
        limits = self.traffic["limits"]
        for i, (x, lc) in sorted(self.kept.items()):
            ref = self.reference(i)
            nums = compare.numbers(x, lc, np.stack(self.costs_by_trace[i]), ref)
            parts.append(nums)
            if not compare.verdict(nums, limits)[0]:
                failed += len(self.costs_by_trace[i])
        return compare.merge(parts), failed


class LiveCaller(Caller):
    """``FleetProvisioner.advance()`` one chunk per tick; the demand walks
    the seeded trace and wraps around, the fleet's state carries over."""

    def setup(self):
        from repro.serving import FleetProvisioner

        tr = self.traffic
        self.policy = tr["policy"]
        self.chunk = int(tr["chunk"])
        self.demand = self.trace(0)
        self.fleet = FleetProvisioner(self.cost_model(), policy=self.policy,
                                      max_replicas=self.n_levels)
        self.t = 0
        self.xs, self.level_costs, self.lat_s = [], [], []

    def tick(self):
        idx = (self.t + np.arange(self.chunk)) % self.n_slots
        a = self.demand[idx]
        t0 = time.perf_counter()
        with _annotate("bench/advance"):
            x = self.fleet.advance(a)
        dt = time.perf_counter() - t0
        self.t += self.chunk
        self.xs.append(x)
        self.level_costs.append(self.fleet.last_plan.level_cost)
        return dt

    def warm(self):
        self.timed_first("advance", self.tick)
        for _ in range(int(self.traffic.get("warm_ticks", 3))):
            self.tick()

    def window(self, seconds: float):
        t_start = time.perf_counter()
        while True:
            self.lat_s.append(self.tick())
            t_end = time.perf_counter()
            if t_end - t_start >= seconds:
                break
        self.window_s = t_end - t_start
        self.calls = len(self.lat_s)
        return self.calls

    def end_to_end(self) -> dict:
        ms = np.asarray(self.lat_s) * 1e3
        return {"advance_p50_ms": float(np.percentile(ms, 50)),
                "advance_p99_ms": float(np.percentile(ms, 99))}

    def layer_inputs(self) -> dict:
        return {"kind": "live", "calls": self.calls, "host_s": self.lat_s,
                "window_s": self.window_s, "n_levels": self.n_levels,
                "n_slots": self.chunk, "windows": 1, "traces_per_call": 1}

    def drop_state(self):
        import jax

        self.kept_lc = np.sum([np.asarray(v, np.float64)
                               for v in jax.device_get(self.level_costs)], axis=0)
        self.level_costs = None
        self.fleet = None

    def stream(self):
        return self.demand[np.arange(self.t) % self.n_slots]

    def reference(self, acc_dtype="float64"):
        return reference.slot_loop(self.stream(), self.n_levels, self.costs,
                                   policy=self.rule, final_off=False,
                                   acc_dtype=acc_dtype)

    def check(self):
        ref = self.reference()
        x = np.concatenate(self.xs)[None]
        nums = compare.numbers(x, self.kept_lc[None], None, ref)
        ok = compare.verdict(nums, self.traffic["limits"])[0]
        if ok:
            return nums, 0
        bad = int((np.concatenate(self.xs) != ref["x"][0]).sum())
        return nums, max(bad, 1)


def make(config: dict, traffic: dict, seed: int, root=discover.ROOT) -> Caller:
    """The caller of the traffic's entry, ``bench/entries/<entry>.py``."""
    mod = discover.module("entries", traffic["entry"], root)
    return mod.Caller(config, traffic, seed, root)
