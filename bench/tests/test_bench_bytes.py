"""Spec-I/O byte count and the peaks table."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec_bytes  # noqa: E402


def test_spec_io_bytes_hand_worked():
    # 2 traces, 3 windows, T = 10 slots, N = 128 levels:
    #   in:  2 traces x (demand + predicted) x 10 x 4 B   =   160
    #        4 per-level parameters x 128 x 4 B            = 2,048
    #   out: 6 cells x (10 x 4 B of x + 3 x 128 x 4 B)     = 9,456
    assert spec_bytes.spec_io_bytes(traces=2, windows=3, n_slots=10, n_levels=128) \
        == 160 + 2048 + 9456


def test_stream_cell_reads_a_few_hundred_kilobytes():
    b = spec_bytes.spec_io_bytes(traces=1, windows=1, n_slots=1008, n_levels=10240)
    assert b == 2 * 1008 * 4 + 4 * 10240 * 4 + 1008 * 4 + 3 * 10240 * 4


def test_peaks_of_v5e():
    p = spec_bytes.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in the peaks table"):
        spec_bytes.peaks("cpu")
