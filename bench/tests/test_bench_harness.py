"""The harness at tiny sizes on the CPU: discovery by name, whole runs of
each entry with the chip check skipped, planted faults that must read
``correct: false``, and the refusals without a chip or without the
program."""
import json
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402

TINY = {"msr-dc": (128, 300)}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout of the benchmark's data with every configuration cut to
    128 levels and a few hundred slots."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "data"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    for name, (n, T) in TINY.items():
        path = root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["fleet"]["n_levels"], cfg["n_slots"] = n, T
        cfg["demand"]["mean_jobs"] = 25.0
        path.write_text(json.dumps(cfg))
    return root


def run(root, workload, trace=False, hook=None, seconds=0.3):
    return harness.run_cell(root, workload, 2**31 + 99, seconds, trace,
                            t_start=time.perf_counter(), require_accelerator=False,
                            cache=False, hook=hook)


FLAT_GENERATOR = """
import numpy as np


def trace(seed, index, n_slots, *, level):
    rng = np.random.default_rng((seed, index))
    return level + rng.integers(0, level, n_slots) * (np.arange(n_slots) % 48 < 12)
"""

A2_RULE = """
from bench.reference import peek_horizon as horizon, peek_static_wait as static_wait
from bench.reference import wait_tables


def waits(key, n_slots, n_levels, windows, delta):
    return wait_tables(key, n_slots, n_levels, windows, delta, atom=False)
"""

STREAM_SCAN_ENTRY = """
from bench.callers import PlanCaller


class Caller(PlanCaller):
    ENTRY = "provision_stream"
"""


def test_new_files_are_found_by_name(tiny_root, tmp_path):
    """A configuration with a new demand generator, traffic with a new
    policy rule and a mesh, a new entry and a new per-layer metric: each a
    new file, found by its name, with no edit elsewhere."""
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    b = root / "bench"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((b / "configs/msr-dc.json").read_text())
    cfg["n_slots"], cfg["demand"] = 200, {"generator": "flat", "level": 30}
    (b / "configs/flat-dc.json").write_text(json.dumps(cfg))
    (b / "generators/flat.py").write_text(FLAT_GENERATOR)
    (b / "policies/A2.py").write_text(A2_RULE)
    (b / "entries/stream_scan.py").write_text(STREAM_SCAN_ENTRY)
    limits = {"x_mismatch": 0, "level_cost_max_abs_err": 0.0, "cost_max_rel_err": 1e-5}
    (b / "traffic/grid-a2.json").write_text(json.dumps(
        {"entry": "plan", "policy": "A2", "windows": [1, 3], "mesh": 1,
         "pool": 2, "limits": limits}))
    (b / "traffic/stream-do.json").write_text(json.dumps(
        {"entry": "stream_scan", "policy": "delayedoff", "windows": [0], "pool": 2,
         "limits": limits}))
    (b / "metrics/calls_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['run']['calls'])\n")
    bench["configs"].append({"name": "flat-dc", "source": "https://arxiv.org/abs/1112.0442",
                             "file": "bench/configs/flat-dc.json", "reduced": [],
                             "why": "test"})
    cells = ["flat-dc.grid-a2", "flat-dc.stream-do"]
    for cell in cells:
        bench["workloads"].append({"name": cell, "config": "flat-dc",
                                   "traffic": cell.split(".")[1], "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "plan_decisions_per_s":
            m["workloads"] += cells
    bench["per_layer"].append({"name": "calls_seen", "unit": "calls", "better": "higher",
                               "source": "host_clock", "layer": "test",
                               "moves": "plan_decisions_per_s", "workloads": cells[:1]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run(root, cells[0], trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["calls_seen"]["value"] == res["attempted"]
    res = run(root, cells[1])
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"plan_decisions_per_s", "setup_s"}


def test_per_layer_metric_names_its_cells():
    bench = harness.load_benchmark(ROOT)
    assert all(m["workloads"] for m in bench["per_layer"])
    bench["per_layer"].append({"name": "anywhere", "moves": "plan_decisions_per_s"})
    with pytest.raises(KeyError):
        harness.reported(bench, bench["workloads"][0])


def test_interpret_mode_on_the_chip_is_refused(monkeypatch):
    import types

    import jax

    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(platform="tpu")])
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(harness.NoAccelerator, match="interpret"):
        harness.devices_for(1, True)


@pytest.mark.parametrize("workload", ["msr-dc.plan-a1", "msr-dc.plan-a3",
                                      "msr-dc.stream-a1", "msr-dc.live-delayedoff"])
def test_tiny_run_is_correct(tiny_root, workload):
    res = run(tiny_root, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) >= {"setup_s"}
    assert list(res)[-1] == "checks"


def _alter_answer(cl):
    """A served answer altered where it is produced: one slot of x is off
    by one."""
    entry = cl.entry

    def altered(spec):
        res = entry(spec)
        x = res.x.at[..., 7].add(1)
        return type(res)(**{**res.__dict__, "x": x})

    cl.entry = altered


def _half_batch(cl):
    """Half of the window sweep left out: the second half of the windows
    repeats the first half's answers."""
    entry = cl.entry

    def halved(spec):
        res = entry(spec)
        W = res.x.shape[0]

        def fold(v):
            return v.at[W // 2:].set(v[:W - W // 2])

        return type(res)(**{**res.__dict__, "x": fold(res.x),
                            "level_cost": fold(res.level_cost), "cost": fold(res.cost)})

    cl.entry = halved


def _stale_state(cl):
    """A step that returns its state unchanged: after the first tick the
    fleet's carried state never moves again."""
    fleet = cl.fleet
    advance = fleet.advance

    def stale(chunk):
        state = fleet.state
        x = advance(chunk)
        if state is not None:
            fleet.state = state
        return x

    fleet.advance = stale


def _answer_altered_live(cl):
    fleet = cl.fleet
    advance = fleet.advance

    def altered(chunk):
        x = advance(chunk)
        return x + (fleet.state.t == 20)

    fleet.advance = altered


@pytest.mark.parametrize("workload,fault", [
    ("msr-dc.plan-a1", _alter_answer),
    ("msr-dc.plan-a1", _half_batch),
    ("msr-dc.plan-a3", _half_batch),
    ("msr-dc.stream-a1", _alter_answer),
    ("msr-dc.live-delayedoff", _stale_state),
    ("msr-dc.live-delayedoff", _answer_altered_live),
])
def test_planted_fault_reads_incorrect(tiny_root, workload, fault):
    res = run(tiny_root, workload, hook=fault)
    assert not res["correct"], res["checks"]


def test_no_accelerator_exits_without_a_result(tiny_root):
    with pytest.raises(harness.NoAccelerator):
        harness.run_cell(tiny_root, "msr-dc.plan-a1", 1, 0.1, False,
                         t_start=time.perf_counter(), cache=False)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "msr-dc.plan-a1", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
