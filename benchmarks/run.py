"""Benchmark harness: one module per paper table/figure + substrate benches.

Prints ``name,us_per_call,derived`` CSV lines.  Roofline terms for the
(arch x shape) cells come from the dry-run artifacts (see
``python -m repro.launch.dryrun`` and ``python -m repro.launch.roofline``).

Runs the same either way::

    PYTHONPATH=src python -m benchmarks.run      # package form
    PYTHONPATH=src python benchmarks/run.py      # script form

The script form has no parent package, so the relative ``from . import``
raises ImportError there; the fallback puts this directory on ``sys.path``
and imports the sibling modules absolutely (they only import ``repro.*``
themselves, so both routes load identical code).

``--profile DIR`` wraps the whole run in ``jax.profiler.trace`` (view with
TensorBoard's profile plugin or Perfetto).
"""
from __future__ import annotations

import argparse
import pathlib
import sys


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="write a jax.profiler trace of the run to DIR")
    args = ap.parse_args()

    try:
        from . import kernel_bench, paper_figs, provision_bench
    except ImportError:  # script form: no parent package for `from .`
        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
        import kernel_bench
        import paper_figs
        import provision_bench

    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs.jaxwatch import profile_to

    enable_compile_cache()

    rows: list[str] = []
    with profile_to(args.profile):
        paper_figs.run(rows)
        provision_bench.run(rows)
        kernel_bench.run(rows)

    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    print(f"# {len(rows)} benchmark rows", file=sys.stderr)


if __name__ == "__main__":
    main()
