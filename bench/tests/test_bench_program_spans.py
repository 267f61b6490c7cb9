"""The readers of the program's own phase spans (``bench/program_spans.py``)
in traced tiny runs on the CPU: each entry's phases are read, the other
entry's are not, and the process's registry is the disabled default
afterwards."""
import json
import pathlib
import shutil
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402

PLAN_SPANS = ("plan_prepare_ms", "plan_dispatch_ms", "plan_finish_ms")
ADVANCE_SPANS = tuple(f"advance_{p}_ms" for p in
                      ("prepare", "dispatch", "fetch", "cost", "record"))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout of the benchmark's data with msr-dc cut to 128 levels
    and 300 slots."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "data"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    path = root / "bench" / "configs" / "msr-dc.json"
    cfg = json.loads(path.read_text())
    cfg["fleet"]["n_levels"], cfg["n_slots"] = 128, 300
    cfg["demand"]["mean_jobs"] = 25.0
    path.write_text(json.dumps(cfg))
    return root


@pytest.mark.parametrize("workload", ["msr-dc.plan-a1", "msr-dc.live-delayedoff"])
def test_traced_run_reads_the_programs_spans(tiny_root, workload):
    """A traced run reads its entry's phase spans, positive and within the
    benchmark's own reading of the call, and none of the other entry's;
    afterwards the process's registry is the disabled default again."""
    from repro.obs import NullTelemetry, get_telemetry

    res = harness.run_cell(tiny_root, workload, 2**31 + 99, 0.3, True,
                           t_start=time.perf_counter(), require_accelerator=False,
                           cache=False)
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    mine, other = ((PLAN_SPANS, ADVANCE_SPANS) if workload.endswith("plan-a1")
                   else (ADVANCE_SPANS, PLAN_SPANS))
    assert all(got[k] > 0 for k in mine), got
    assert not set(other) & set(got)
    if mine == PLAN_SPANS:
        assert sum(got[k] for k in mine) <= got["plan_host_ms"]
    assert isinstance(get_telemetry(), NullTelemetry)
