"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 3] [--seconds 2] [--out FILE]

In one process, for each seed: the cell's inputs, a short window through
the same compiled entry as a run, and the comparison numbers of what the
window produced against the plain reference (the program's readings).  On
the first ``--control-seeds`` seeds it also reads the control: the
reference computed in bfloat16, put in the program's place, and the
verdict that the run's comparison gives it (``control_correct``, which has
to read false).  A limit lies above the largest program reading and below
the smallest control reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import callers, compare, harness  # noqa: E402


def readings(cl, control: bool):
    """(program numbers, control numbers or None) of a caller whose window
    has run and whose state is dropped."""
    if isinstance(cl, callers.LiveCaller):
        import numpy as np

        ref = cl.reference()
        prog = compare.numbers(np.concatenate(cl.xs)[None], cl.kept_lc[None], None, ref)
        ctl = None
        if control:
            c = cl.reference("bfloat16")
            ctl = compare.numbers(c["x"], c["level_cost"], None, ref)
        return prog, ctl
    import numpy as np

    progs, ctls = [], []
    for i, (x, lc) in sorted(cl.kept.items()):
        ref = cl.reference(i)
        progs.append(compare.numbers(x, lc, np.stack(cl.costs_by_trace[i]), ref))
        if control:
            c = cl.reference(i, "bfloat16")
            ctls.append(compare.numbers(c["x"], c["level_cost"], c["cost"][None], ref))
    return compare.merge(progs), (compare.merge(ctls) if control else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    root = harness.ROOT
    bench = harness.load_benchmark(root)
    cell, config, traffic = harness.load_cell(root, bench, args.workload)
    sys.path.insert(0, str(root / "src"))
    harness.devices_for(int(cell["chips"]), True)
    harness.enable_cache(root)
    rows = []
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cl = callers.make(config, traffic, seed)
        cl.setup()
        cl.warm()
        cl.window(args.seconds)
        cl.drop_state()
        prog, ctl = readings(cl, k < args.control_seeds)
        row = {"workload": args.workload, "seed": seed, "calls": cl.calls,
               "program": prog, "control": ctl,
               "control_correct": None if ctl is None else compare.verdict(
                   ctl, traffic["limits"])[0],
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
    lower = compare.merge(r["program"] for r in rows)
    upper = {}
    for r in rows:
        for name, v in (r["control"] or {}).items():
            upper[name] = min(upper.get(name, v), v)
    summary = {"workload": args.workload, "seeds": len(rows), "lower": lower,
               "upper": upper, "limits": traffic["limits"]}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            for r in rows + [summary]:
                f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
