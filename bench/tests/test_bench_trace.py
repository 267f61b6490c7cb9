"""Trace reduction: on hand-made intervals, and on a 2-second traced window
of the streaming kernel (three ``provision_stream`` calls on a year-long
trace of 10,240 levels) recorded on one TPU v5e and checked in."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

SAMPLE = ROOT / "bench" / "data" / "stream-a1.xplane.pb"


def test_union_merges_overlaps():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]


def test_reduce_on_hand_made_intervals():
    raw = {
        "devices": {"/device:TPU:0": [(10, 20, "fusion", ""), (15, 30, "k", "my_kernel"),
                                      (50, 60, "copy", "")]},
        "spans": [(0, 40, "bench/entry"), (40, 100, "bench/block")],
    }
    s = trace.reduce(raw, window_ns=(0, 100), kernels={"k": "my_kernel"})
    assert s["busy_s"] == pytest.approx(30e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["kernels"]["k"] == pytest.approx(15e-9)
    # idle [0, 10) is named by the entry span; [30, 50) and [60, 100) by the
    # block span open at their midpoints
    assert s["gaps"]["bench/entry"] == pytest.approx(10e-9)
    assert s["gaps"]["bench/block"] == pytest.approx(60e-9)
    assert trace.top(s["ops"], 1)[0][0] in ("fusion", "k")


def test_short_name_of_an_hlo_op():
    assert trace.short_name("%while.13 = (s32[]) while(%tuple.46), body=%b") == "while.13"
    long = '%k.1 = (s32[1]) custom-call(%c), custom_call_target="tpu_custom_call", x'
    assert trace.short_name(long) == "k.1 (tpu_custom_call)"


def test_gap_outside_any_span_is_named_so():
    raw = {"devices": {"/device:TPU:0": [(10, 20, "op", "")]},
           "spans": [(0, 4, "bench/entry")]}
    s = trace.reduce(raw, window_ns=(0, 30))
    assert s["gaps"]["(no span)"] == pytest.approx(20e-9)


def test_recorded_chip_trace():
    raw = trace.read(str(SAMPLE))
    assert raw["devices"], "no device plane with XLA Ops"
    assert {name for _, _, name in raw["spans"]} >= {"bench/entry", "bench/block"}
    s = trace.reduce(raw, kernels={"stream_kernel": "tpu_custom_call"})
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["kernels"]["stream_kernel"] > 0
    assert s["kernels"]["stream_kernel"] <= s["busy_s"] * 1.0001
    assert set(s["gaps"]) <= {"bench/entry", "bench/block", "(no span)"}
