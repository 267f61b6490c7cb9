"""``repro.core.provision_stream(spec)`` on a typed fleet: the cost model
built from the configuration's ``groups`` (``CostModel.from_groups``), each
pooled trace checked against the typed reference
(:mod:`bench.typed_reference`) on ``x``, ``level_cost``, ``cost`` and the
per-type ``group_cost``."""
import numpy as np

from bench import compare, discover, reference, typed_reference
from bench.callers import PlanCaller


def group_cost_max_rel_err(got, ref) -> float:
    """The largest relative gap of a per-type total, against the reference's
    ``group_cost``."""
    want = np.asarray(ref["group_cost"], np.float64)
    got = np.asarray(got, np.float64).reshape(want.shape)
    return float((np.abs(got - want) / np.maximum(np.abs(want), 1.0)).max())


class Caller(PlanCaller):
    ENTRY = "provision_stream"

    def __init__(self, config: dict, traffic: dict, seed: int, root=discover.ROOT):
        self.root = root
        self.config = config
        self.traffic = traffic
        self.rule = reference.policy_rule(traffic["policy"], root)
        self.seed = int(seed) % 2**64
        self.groups = config["groups"]
        self.n_levels = sum(int(g["n_servers"]) for g in self.groups)
        self.n_slots = int(config["n_slots"])
        self.first_calls: dict[str, float] = {}

    def cost_model(self):
        from repro.core import CostModel, ServerGroup

        return CostModel.from_groups(*(
            ServerGroup(name=g["name"], n_servers=int(g["n_servers"]), P=float(g["P"]),
                        beta_on=float(g["beta_on"]), beta_off=float(g["beta_off"]))
            for g in self.groups))

    def drop_state(self):
        """Keep ``group_cost`` with the host copies the check needs."""
        self.kept_gc = {i: np.asarray(r.group_cost) for i, r in self.last.items()}
        super().drop_state()

    def reference(self, i: int, acc_dtype="float64"):
        waits = None
        if self.keys is not None:
            delta = typed_reference.per_level(self.groups)[3]
            waits = self.rule.waits(self.keys[i], self.n_slots, self.n_levels,
                                    self.windows, delta)[0]
        return typed_reference.slot_loop(self.pool[i], self.groups, waits=waits,
                                         acc_dtype=acc_dtype)

    def numbers(self, i: int, ref) -> dict:
        """The comparison of trace ``i``'s kept output with ``ref``."""
        x, lc = self.kept[i]
        nums = compare.numbers(x, lc, np.stack(self.costs_by_trace[i]), ref)
        nums["group_cost_max_rel_err"] = group_cost_max_rel_err(self.kept_gc[i], ref)
        return nums

    def check(self):
        parts, failed = [], 0
        for i in sorted(self.kept):
            nums = self.numbers(i, self.reference(i))
            parts.append(nums)
            if not compare.verdict(nums, self.traffic["limits"])[0]:
                failed += len(self.costs_by_trace[i])
        return compare.merge(parts), failed
