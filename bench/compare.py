"""The comparison that decides ``correct``: engine output against the plain
reference, number by number, each against the limit its cell's traffic
file states.

- ``x_mismatch``: slots (over every window and compared trace) where the
  engine's schedule differs from the reference's.  Exact: limit 0.
- ``level_cost_max_abs_err``: the largest gap of a per-level cost.  Each is
  a sum of whole slots and toggles, exact in float32: limit 0.
- ``cost_max_rel_err``: the largest relative gap of a call's total cost,
  a float32 sum over the fleet in the engine.
"""
from __future__ import annotations

import numpy as np

NAMES = ("x_mismatch", "level_cost_max_abs_err", "cost_max_rel_err")


def numbers(x, level_cost, costs, ref) -> dict:
    """Compare one trace's output with the reference's ``ref``.

    ``x`` (W, T) and ``level_cost`` (W, N) of the call kept at the end of
    the window; ``costs``: the (W,) total costs of every call on this
    trace (rows of a (calls, W) array), or None where the cell has none.
    """
    x = np.asarray(x).reshape(ref["x"].shape)
    lc = np.asarray(level_cost, np.float64).reshape(ref["level_cost"].shape)
    out = {
        "x_mismatch": int((x != ref["x"]).sum()),
        "level_cost_max_abs_err": float(
            np.abs(lc - np.asarray(ref["level_cost"], np.float64)).max()),
    }
    if costs is not None:
        want = np.asarray(ref["cost"], np.float64)
        got = np.asarray(costs, np.float64).reshape(-1, want.shape[0])
        out["cost_max_rel_err"] = float(
            (np.abs(got - want) / np.maximum(np.abs(want), 1.0)).max())
    return out


def merge(parts) -> dict:
    """Worst reading of each number over several traces."""
    out = {}
    for p in parts:
        for k, v in p.items():
            out[k] = max(out.get(k, v), v)
    return out


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit.  A number without a limit, or a limit without a number, fails."""
    checks = {}
    ok = True
    for name in sorted(set(nums) | set(limits)):
        value, limit = nums.get(name), limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if value is None or limit is None or not value <= limit:
            ok = False
    return ok, checks
